"""Find a serve cell's knee, once, on the chip: the highest of a ladder of
offered rates at which at least 99 % of the rows are goodput (answered
correctly within the mix's limit of the instant they were due) and the queue
is not growing when the traffic stops.  The number goes into the mix's file
(``knee_rows_per_s``; the cell then offers a FIXED ``offered_rows_per_s``) and
the table into PERF.md.  A run of the benchmark never searches.

    python3 benchmarks/knee_sweep.py --workload lr_tb.serve_rows \
        --rates 2000,4000,6000,8000,12000,16000 --seconds 8 [--seed 1]

(rates in ascending order)

One set-up serves every rate.  Prints one JSON line per rate and a last line
with the knee; none of them is a result line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MIN_GOOD_SHARE = 0.99


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.drivers import serve_open_loop
    from benchmarks.harness import launch, manifest

    ctx = launch.context(
        manifest.load(), args.workload, seed=args.seed, seconds=args.seconds,
        trace=False, rehearsal=args.rehearsal, t0=time.perf_counter(),
        work_name=args.workload + ".sweep",
    )
    traffic, dev, work = ctx.traffic, ctx.device, ctx.work
    served = serve_open_loop.Served(ctx)
    knee, climbing = None, True
    try:
        served.offer(float(args.rates.split(",")[0]), traffic["warmup_s"], args.seed + 1)
        for i, rate in enumerate(map(float, args.rates.split(","))):
            got = served.offer(rate, args.seconds, args.seed + 10 * i)
            share = got["good"] / max(got["offered"], 1)
            sustained = share >= MIN_GOOD_SHARE and got["depth_at_end"] < 64
            # the ladder is climbed in order; the knee is below the first
            # rate that is not sustained
            climbing = climbing and sustained
            if climbing:
                knee = rate
            print(json.dumps({
                "sweep": args.workload, "device": dev,
                "offered_rows_per_s": rate,
                "goodput_rows_per_s": got["good"] / got["seconds"],
                "good_share": share,
                "shed_share": got["shed"] / max(got["offered"], 1),
                "depth_at_end": got["depth_at_end"],
                "latency_ms": got["latency_ms"], "late_ms": got["late_ms"],
                "batch_rows_mean": got["serve_stats"]["batch_fill_mean"],
                "wrong": got["wrong"], "errors": got["errors"],
                "sustained": sustained,
            }), flush=True)
    finally:
        served.close()
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"sweep": args.workload, "knee_rows_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` in this process on the machine it is
started on, and prints as its last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``) and
``device`` (with ``--trace 1`` also ``busy_s``, ``window_s`` and a
``breakdown``), and last ``compared``: each number ``correct`` rests on
beside its limit (``value``, ``op``, ``limit``: the check holds where
``value op limit`` does), which are also the run's last lines on standard
error.
Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.

``--rehearsal`` shrinks every size (the ``rehearsal`` blocks of the
configuration and the mix), runs on whatever backend JAX finds, and ends in a
line that says ``rehearsal`` and carries counts only: never a rate, a time or
a share, and never the keys of a result.

Everything else a run has to say goes to standard error and to
``.bench_cache/<cell>.last.json``; a traced run leaves the profiler's own
file beside it (``<cell>.xplane.pb``).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import device, launch, manifest, trace_reduce

    doc = manifest.load()

    ctx = launch.context(
        doc, args.workload, seed=args.seed,
        seconds=args.seconds if args.seconds is not None else doc["run_seconds"],
        trace=bool(args.trace), rehearsal=args.rehearsal, t0=T_PROCESS_START,
    )
    driver = manifest.driver(ctx.traffic["kind"])
    readers = {
        m["name"]: manifest.layer_metric(m["name"])
        for m in manifest.metrics_of(doc, "per_layer", args.workload)
    }
    outcome = driver.run(ctx)
    setup_s = outcome.window_start - T_PROCESS_START

    correct = all(outcome.checks.values())
    e2e = {**outcome.end_to_end, "setup_s": setup_s}
    outcome.run["setup_s"] = setup_s
    layer = {}
    for name, reader in readers.items():
        value = reader.read(outcome.run)
        if value is not None:
            layer[name] = value
    units = {
        m["name"]: m["unit"] for m in doc["end_to_end"] + doc["per_layer"]
    }
    named = {
        m["name"] for m in manifest.metrics_of(doc, "end_to_end", args.workload)
    }
    chosen = layer if args.trace else {k: v for k, v in e2e.items() if k in named}
    result = {
        "correct": correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": {
            k: {"value": v, "unit": units[k]} for k, v in chosen.items()
        },
        "device": {
            **ctx.device, "memory_peak_bytes": outcome.run["memory_peak_bytes"]
        },
    }
    reduced = outcome.run.get("trace")
    if args.trace and reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    with open(os.path.join(ROOT, ".bench_cache", f"{args.workload}.last.json"), "w") as f:
        json.dump({
            "args": vars(args), "checks": outcome.checks,
            "compared": outcome.compared,
            "end_to_end": e2e, "per_layer": layer,
            "compiles": ctx.meter.snapshot(),
            "memory_stats": device.memory_stats(),
            "run": outcome.run,
        }, f, indent=1, default=str)
    if reduced:
        os.replace(
            trace_reduce.find_xplane(os.path.join(ctx.work, "trace")),
            os.path.join(ROOT, ".bench_cache", f"{args.workload}.xplane.pb"),
        )
    shutil.rmtree(ctx.work, ignore_errors=True)
    ctx.log(f"checks {outcome.checks}")
    for name, pair in outcome.compared.items():
        print(
            f"compared {name} {pair['value']!r} {pair['op']} limit {pair['limit']!r}",
            file=sys.stderr,
        )
    if args.rehearsal:
        print(json.dumps({
            "rehearsal": True, "workload": args.workload, "backend": ctx.device,
            "checks": outcome.checks, "counts": outcome.counts,
            "end_to_end_reported": sorted(named & set(e2e)),
            "per_layer_reported": sorted(layer),
            "compared": outcome.compared,
        }), flush=True)
        return 0 if correct else 1
    result["compared"] = outcome.compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""``python -m xflow_tpu.stream`` — the continuous-training CLI.

    python -m xflow_tpu.stream run --stream-dir DIR --workdir DIR \
        --model lr --table-size-log2 22 [--metrics-out RUN.jsonl] \
        [--export-every-steps N] [--compact-every K] [--replicas R] \
        [--freshness-slo-s S] [--resume auto] ...

Tails ``--stream-dir`` for packed-v2 shards, trains continuously, cuts
incremental delta exports, and hot-swaps them onto an in-process
replica fleet through the staged-rollout canary gate, reporting
``freshness`` rows (docs/CONTINUOUS.md).  SIGTERM/SIGINT stop the loop
gracefully: the ingestion cursor and metrics flush, so a restarted run
resumes mid-stream.
"""

from __future__ import annotations

import argparse
import signal
import sys

from xflow_tpu.config import Config
from xflow_tpu.stream.driver import StreamDriver
from xflow_tpu.train import build_parser, config_from_args
from xflow_tpu.utils.compile_cache import enable_compile_cache


def _stream_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="xflow_tpu.stream",
        description="continuous training: streaming ingestion + delta "
        "export + SLO-gated hot-swap (docs/CONTINUOUS.md)",
    )
    sub = p.add_subparsers(dest="command", required=True)
    run = sub.add_parser(
        "run", help="run the continuous train→export→swap loop",
        # inherit every trainer config flag (--model, --table-size-log2,
        # --metrics-out, --chaos-spec, --store-mode, ...) so the stream
        # CLI never forks the config surface
        parents=[build_parser()], add_help=False, conflict_handler="resolve",
    )
    run.add_argument(
        "--stream-dir", required=True,
        help="directory another process appends complete shards to "
        "(atomic-rename writers; io/packed.py)",
    )
    run.add_argument(
        "--workdir", required=True,
        help="driver state: ingestion cursor + exported artifacts",
    )
    run.add_argument("--replicas", type=int, default=2)
    run.add_argument(
        "--export-every-steps", type=int, default=50,
        help="cut a servable export every N train steps",
    )
    run.add_argument(
        "--compact-every", type=int, default=8,
        help="cut a fresh FULL base after this many deltas",
    )
    run.add_argument("--canary-frac", type=float, default=0.25)
    run.add_argument("--min-canary-requests", type=int, default=16)
    run.add_argument("--max-error-frac", type=float, default=0.0)
    run.add_argument("--max-p99-ms", type=float, default=None)
    run.add_argument(
        "--freshness-slo-s", type=float, default=60.0,
        help="event-to-servable SLO stamped into freshness rows "
        "(obs doctor ranks a stream past it as servable_stale)",
    )
    run.add_argument("--rollout-timeout-s", type=float, default=60.0)
    run.add_argument("--poll-interval-s", type=float, default=0.5)
    run.add_argument(
        "--idle-stop-s", type=float, default=None,
        help="stop after this much idle with no new shards "
        "(default: follow forever)",
    )
    run.add_argument("--max-steps", type=int, default=None)
    run.add_argument("--max-commits", type=int, default=None)
    return p


def main(argv: list[str] | None = None) -> int:
    args = _stream_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    enable_compile_cache()
    cfg = config_from_args(args)
    driver = StreamDriver(
        cfg,
        args.stream_dir,
        args.workdir,
        replicas=args.replicas,
        export_every_steps=args.export_every_steps,
        compact_every=args.compact_every,
        canary_frac=args.canary_frac,
        min_canary_requests=args.min_canary_requests,
        max_error_frac=args.max_error_frac,
        max_p99_ms=args.max_p99_ms,
        freshness_slo_s=args.freshness_slo_s,
        rollout_timeout_s=args.rollout_timeout_s,
        poll_interval_s=args.poll_interval_s,
        idle_stop_s=args.idle_stop_s,
        max_steps=args.max_steps,
        max_commits=args.max_commits,
        resume=args.resume,
        log=lambda s: print(s, file=sys.stderr),
    )

    def on_signal(signum, frame):
        print(
            f"signal {signum}: draining the stream loop (cursor + "
            "metrics flush on close)",
            file=sys.stderr,
        )
        driver.request_stop()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, on_signal)
    summary = driver.run()
    print(
        f"stream run: {summary['steps']} steps over "
        f"{summary['shards_ingested']} shard(s), {summary['exports']} "
        f"export(s), {summary['commits']} commit(s), "
        f"{summary['aborts']} abort(s), servable {summary['servable']}",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

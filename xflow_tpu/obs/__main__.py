"""CLI: ``python -m xflow_tpu.obs <summarize|validate|compare|merge|doctor>``

    summarize run.jsonl       phase/throughput/percentile tables per run
    compare   a b             side-by-side diff of two metrics JSONL
                              files (last run each);
                              --fail-on-regress FRAC exits 3 when B's
                              throughput fell more than FRAC below A's
    validate  run.jsonl       strict schema check (exit 1 on violations)
    merge     a.jsonl b.jsonl combine per-host metrics files into one
                              rank-tagged, time-aligned stream
                              (--out FILE, default stdout)
    doctor    run.jsonl       ranked diagnosis of a sick (or healthy)
                              run: stall causes, stragglers, recompile
                              suspicion (--flight DUMP);
                              exit 0 only when clean
    live      run.jsonl ...   streaming doctor: tail growing metrics
                              files, run the doctor checks plus the
                              SLO alert rules continuously
                              (--interval-s, --max-seconds, --once)

Pure host-side file processing — never imports jax, so it runs
anywhere (including hosts with no accelerator runtime).
Docs: docs/OBSERVABILITY.md ("Diagnosing a sick run",
"Operating a live fleet").
"""

from __future__ import annotations

import argparse
import sys

from xflow_tpu.obs.schema import load_jsonl, validate_rows
from xflow_tpu.obs.summary import check_regress, compare, summarize


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m xflow_tpu.obs",
        description="metrics JSONL toolchain (docs/OBSERVABILITY.md)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("summarize", help="per-run phase/throughput tables")
    ps.add_argument("path")
    pv = sub.add_parser("validate", help="strict schema check")
    pv.add_argument("path")
    pc = sub.add_parser(
        "compare", help="diff two metrics files"
    )
    pc.add_argument("path_a")
    pc.add_argument("path_b")
    pc.add_argument(
        "--fail-on-regress",
        type=float,
        default=None,
        metavar="FRAC",
        help="exit 3 when B's throughput is more than FRAC (e.g. 0.05) "
        "below A's",
    )
    pm = sub.add_parser(
        "merge", help="combine per-host metrics files into one stream"
    )
    pm.add_argument("paths", nargs="+")
    pm.add_argument("--out", default="", help="output file (default stdout)")
    pd = sub.add_parser("doctor", help="ranked diagnosis of a run")
    pd.add_argument("path", help="metrics JSONL (single-host or merged)")
    pd.add_argument(
        "--flight", default="", help="flight dump (Config.obs_flight_out)"
    )
    pl = sub.add_parser(
        "live", help="streaming doctor over growing metrics files"
    )
    pl.add_argument(
        "paths", nargs="+",
        help="metrics JSONL file(s), possibly still being written "
        "(one per host)",
    )
    pl.add_argument(
        "--interval-s", type=float, default=2.0,
        help="poll cadence (default 2s)",
    )
    pl.add_argument(
        "--max-seconds", type=float, default=0.0,
        help="stop after this long (default 0 = until Ctrl-C)",
    )
    pl.add_argument(
        "--once", action="store_true",
        help="single pass over what exists now, then exit — a "
        "file-tolerant `doctor` for still-growing files",
    )
    args = p.parse_args(argv)

    if args.cmd == "summarize":
        print(summarize(args.path))
        return 0
    if args.cmd == "validate":
        errors = validate_rows(load_jsonl(args.path))
        for e in errors:
            print(e, file=sys.stderr)
        print(f"{args.path}: {'FAIL' if errors else 'OK'} "
              f"({len(errors)} violation(s))")
        return 1 if errors else 0
    if args.cmd == "compare":
        try:
            print(compare(args.path_a, args.path_b))
            if args.fail_on_regress is not None:
                verdict = check_regress(
                    args.path_a, args.path_b, args.fail_on_regress
                )
                if verdict is not None:
                    print(verdict, file=sys.stderr)
                    return 3
        except ValueError as e:  # empty/headerless file: diagnose, not crash
            print(f"error: {e}", file=sys.stderr)
            return 1
        return 0
    if args.cmd == "merge":
        from xflow_tpu.obs.doctor import merge_rows_tolerant, write_jsonl

        try:
            rows, skipped = merge_rows_tolerant(args.paths)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        torn = (
            f", {skipped} torn final line(s) skipped (still-appended "
            "file)" if skipped else ""
        )
        if args.out:
            with open(args.out, "w") as f:
                write_jsonl(rows, f)
            print(
                f"{args.out}: {len(rows)} rows merged from "
                f"{len(args.paths)} file(s){torn}",
                file=sys.stderr,
            )
        else:
            write_jsonl(rows, sys.stdout)
            if skipped:
                print(
                    f"{skipped} torn final line(s) skipped "
                    "(still-appended file)",
                    file=sys.stderr,
                )
        return 0
    if args.cmd == "live":
        from xflow_tpu.obs.live import run_live

        return run_live(
            args.paths,
            interval_s=args.interval_s,
            max_seconds=args.max_seconds,
            once=args.once,
        )
    if args.cmd == "doctor":
        from xflow_tpu.obs.doctor import doctor

        try:
            text, rc = doctor(args.path, flight_path=args.flight or None)
        except (ValueError, OSError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(text)
        return rc
    return 2  # unreachable (subparsers required)


if __name__ == "__main__":
    sys.exit(main())

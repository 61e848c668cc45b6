"""Serving SLO gate: judge a load-generator run's ``serve_bench`` row
against explicit SLO thresholds.

The load generator (serve/loadgen.py, ``python -m xflow_tpu.serve
loadgen``) is OPEN-loop: offered traffic arrives on its own clock, so
a tier past capacity shows up as shed fraction and tail latency, not
as a quietly lower throughput number.  This script turns that row into
a verdict:

* ``errors`` must not exceed ``--max-error-frac`` of offered traffic
  (default 0: a failed request is never an SLO trade);
* ``shed_frac`` must stay under ``--max-shed-frac`` (shedding is the
  tier *defending* the deadline budget — some is policy, a storm is a
  capacity failure);
* client-observed ``e2e_p99`` must stay under ``--max-p99-ms`` when
  given (0 disables: absolute latency on a degraded CI container
  measures the box, not the code — pass a bar only where the numbers
  are trustworthy);
* ``achieved_qps / offered_qps_actual`` must reach
  ``--min-achieved-frac`` when given;
* ``outstanding`` (admitted requests the tier never resolved before
  the loadgen drain timeout) must not exceed ``--max-outstanding``
  (default 0: a black-holed request is neither an error nor a shed
  and must not pass silently);
* ``--compare-transports`` switches to two-leg mode: the newest
  ``transport == "binary"`` row is judged against every gate above AND
  must beat the newest ``transport == "http"`` row's achieved QPS by
  ``--min-transport-ratio`` with a p99 no worse — a binary transport
  that is not faster than HTTP on the same fleet is a regression, not
  a feature;
* ``--qos-ordering`` asserts the admission-control shed ORDER on the
  judged row: ``bidding`` must shed nothing, and any shedding at all
  must include ``best_effort`` — overload is supposed to land on the
  class that bid for it.

The metrics file must pass obs/schema.py validation first — a gate
that reads torn rows gates nothing.  The NEWEST ``serve_bench`` row is
judged (a file may accumulate runs).

Run from the repo root:

    python scripts/check_serve_slo.py serve_metrics.jsonl \
        --max-shed-frac 0.05 --max-p99-ms 250

Wired into tier-1 via tests/test_serve.py::test_check_serve_slo_gate
(a healthy loadgen run passes; an injected latency regression exits
non-zero).
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("metrics", help="JSONL file with serve_bench row(s)")
    p.add_argument(
        "--max-shed-frac", type=float, default=0.05,
        help="max admission-control shed fraction (default 0.05)",
    )
    p.add_argument(
        "--max-error-frac", type=float, default=0.0,
        help="max failed-request fraction of offered traffic "
        "(default 0.0 — errors are never an SLO trade)",
    )
    p.add_argument(
        "--max-p99-ms", type=float, default=0.0,
        help="max client-observed e2e p99 in ms (0 = disabled; "
        "absolute latency on degraded CI boxes measures the box)",
    )
    p.add_argument(
        "--min-achieved-frac", type=float, default=0.0,
        help="min achieved_qps / offered_qps_actual (0 = disabled)",
    )
    p.add_argument(
        "--max-outstanding", type=int, default=0,
        help="max requests still unresolved when the loadgen drain "
        "timed out (default 0: a black-holed request is neither an "
        "error nor a shed and must not pass silently)",
    )
    p.add_argument(
        "--compare-transports", action="store_true",
        help="two-leg mode: judge the newest transport=binary row "
        "(all standard gates) and require it to beat the newest "
        "transport=http row on achieved QPS with a p99 no worse",
    )
    p.add_argument(
        "--min-transport-ratio", type=float, default=1.0,
        help="with --compare-transports: min binary/http achieved-QPS "
        "ratio (default 1.0 — binary must at least match HTTP)",
    )
    p.add_argument(
        "--qos-ordering", action="store_true",
        help="assert shed order on the judged row: bidding sheds "
        "nothing and any shedding includes best_effort (row must "
        "carry qos_shed — run loadgen with --qos-mix)",
    )
    args = p.parse_args(argv)

    from xflow_tpu.obs.schema import load_jsonl, validate_rows

    try:
        rows = load_jsonl(args.metrics)
    except OSError as e:
        print(f"FAIL: cannot read {args.metrics}: {e}", file=sys.stderr)
        return 2
    errors = validate_rows(rows)
    if errors:
        for e in errors:
            print(f"FAIL: schema violation: {e}", file=sys.stderr)
        return 2
    bench = [r for r in rows if r.get("kind") == "serve_bench"]
    if not bench:
        print(
            f"FAIL: {args.metrics} has no serve_bench row — run "
            "`python -m xflow_tpu.serve loadgen ... --metrics-out` "
            "first",
            file=sys.stderr,
        )
        return 2
    http_row = None
    if args.compare_transports:
        by = {"binary": None, "http": None}
        for r in bench:  # newest of each transport wins
            t = r.get("transport")
            if t in by:
                by[t] = r
        missing = [t for t, r in by.items() if r is None]
        if missing:
            print(
                "FAIL: --compare-transports needs one serve_bench row "
                f"per transport; missing {missing} in {args.metrics} "
                "(run loadgen once with --binary-addr and once with "
                "--url against the same server)",
                file=sys.stderr,
            )
            return 2
        row, http_row = by["binary"], by["http"]
    else:
        row = bench[-1]
    if "offered_qps_actual" not in row:
        print(
            "FAIL: newest serve_bench row carries no offered_qps_actual "
            "— that is a closed-loop `bench` row, not a loadgen run; "
            "every gate below would compare defaults against defaults "
            "and pass vacuously.  Run `python -m xflow_tpu.serve "
            "loadgen ... --metrics-out` and gate that file.",
            file=sys.stderr,
        )
        return 2

    offered = float(row.get("offered_qps_actual", 0.0)) or float(
        row.get("offered_qps", 0.0)
    )
    submitted = max(
        1.0, offered * float(row.get("seconds", 0.0))
    )
    p99_ms = 1e3 * float(row.get("e2e_p99", 0.0))
    shed_frac = float(row.get("shed_frac", 0.0))
    error_frac = float(row.get("errors", 0)) / submitted
    outstanding = int(row.get("outstanding", 0))
    achieved_frac = (
        float(row.get("achieved_qps", 0.0)) / offered if offered else 0.0
    )

    checks: list[tuple[str, bool, str]] = [
        (
            "error_frac",
            error_frac <= args.max_error_frac,
            f"{error_frac:.4f} (max {args.max_error_frac}, "
            f"{row.get('errors', 0)} error(s))",
        ),
        (
            "shed_frac",
            shed_frac <= args.max_shed_frac,
            f"{shed_frac:.4f} (max {args.max_shed_frac}, by cause "
            f"{row.get('shed_by_cause', {})})",
        ),
        (
            "outstanding",
            outstanding <= args.max_outstanding,
            f"{outstanding} unresolved at drain timeout "
            f"(max {args.max_outstanding})",
        ),
    ]
    if args.max_p99_ms > 0:
        checks.append((
            "e2e_p99",
            p99_ms <= args.max_p99_ms,
            f"{p99_ms:.1f}ms (max {args.max_p99_ms}ms)",
        ))
    if args.min_achieved_frac > 0:
        checks.append((
            "achieved/offered",
            achieved_frac >= args.min_achieved_frac,
            f"{achieved_frac:.3f} (min {args.min_achieved_frac}, "
            f"{row.get('achieved_qps')} of {offered} qps)",
        ))
    if http_row is not None:
        bin_qps = float(row.get("achieved_qps", 0.0))
        http_qps = float(http_row.get("achieved_qps", 0.0))
        ratio = bin_qps / http_qps if http_qps else float("inf")
        checks.append((
            "transport_qps",
            ratio >= args.min_transport_ratio,
            f"binary {bin_qps} vs http {http_qps} qps achieved "
            f"({ratio:.2f}x, min {args.min_transport_ratio}x)",
        ))
        bin_p99 = 1e3 * float(row.get("e2e_p99", 0.0))
        http_p99 = 1e3 * float(http_row.get("e2e_p99", 0.0))
        checks.append((
            "transport_p99",
            bin_p99 <= http_p99,
            f"binary {bin_p99:.1f}ms vs http {http_p99:.1f}ms "
            "(binary must be no worse)",
        ))
    if args.qos_ordering:
        qshed = row.get("qos_shed")
        if not isinstance(qshed, dict):
            print(
                "FAIL: --qos-ordering needs a qos_shed map on the "
                "judged serve_bench row — run loadgen with --qos-mix",
                file=sys.stderr,
            )
            return 2
        bidding = int(qshed.get("bidding", 0))
        best_effort = int(qshed.get("best_effort", 0))
        total = sum(int(v) for v in qshed.values())
        checks.append((
            "qos_bidding_shed",
            bidding == 0,
            f"{bidding} bidding request(s) shed (must be 0: the top "
            "class is the last to go)",
        ))
        checks.append((
            "qos_shed_order",
            total == 0 or best_effort > 0,
            f"{total} total shed, {best_effort} from best_effort "
            "(any shedding must include the lowest class)",
        ))

    failed = 0
    for name, ok, detail in checks:
        print(f"{'ok  ' if ok else 'FAIL'} {name}: {detail}")
        failed += 0 if ok else 1
    if failed:
        print(
            f"FAIL: {failed} SLO gate(s) breached by the newest "
            f"serve_bench row in {args.metrics}",
            file=sys.stderr,
        )
        return 1
    print(
        f"OK: serve SLO gates passed ({row.get('requests')} requests "
        f"at {row.get('achieved_qps')} qps achieved / "
        f"{offered} offered, p99 {p99_ms:.1f}ms, shed "
        f"{100 * shed_frac:.1f}%)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Bench-trajectory regress check: compare the newest committed
``BENCH_r*.json`` against the best prior run via ``python -m
xflow_tpu.obs compare --fail-on-regress``.

Two metrics gate:

* the train metric (``value``) against the best non-degraded prior;
* ``e2e_packed_examples_per_sec`` — the packed input-path throughput
  the fan-out work (ISSUE 14 / ROADMAP 1) optimizes — against the best
  non-degraded prior that MEASURES it (older artifacts predate the
  metric; a degraded round never becomes either bar).

The committed bench artifacts accumulated for five PRs without ever
gating anything; this script turns the trajectory into a signal.  It
is WARN-ONLY by default (exit 0 with a loud message): the containers
the tier-1 suite runs in are routinely degraded (CPU backend,
``degraded: true`` in the artifact) and wildly different in core
count, so a hard gate would fail on environment, not on code.
``--strict`` makes a regression (or a missing baseline) exit non-zero
for environments where the numbers are trustworthy.

Run from the repo root:

    python scripts/check_bench_regress.py [--frac 0.10] [--strict]

Wired into tier-1 (warn-only) via tests/test_observability.py::
test_check_bench_regress_script.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def find_bench_artifacts(root: str) -> list[str]:
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument(
        "--frac", type=float, default=0.10,
        help="fail threshold: fraction below the best prior run "
        "(default 0.10 = 10%%)",
    )
    p.add_argument(
        "--strict", action="store_true",
        help="exit non-zero on regression (default: warn only — "
        "tier-1 containers produce degraded numbers)",
    )
    p.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    from xflow_tpu.obs.__main__ import main as obs_main
    from xflow_tpu.obs.summary import load_bench_result

    # one read per artifact: every later filter/lookup goes through
    # this memo (an artifact rewritten mid-run can't be seen in two
    # different states).  load_bench_result only swallows parse
    # errors; an artifact that can't be READ (racing delete, bad
    # perms) must degrade to "not usable", not crash the gate.
    results: dict[str, dict | None] = {}
    unreadable = []
    for p_ in find_bench_artifacts(args.root):
        try:
            results[p_] = load_bench_result(p_)
        except OSError as e:
            results[p_] = None
            unreadable.append(f"{p_} ({e.strerror or e})")
    usable = [p_ for p_, r in results.items() if r is not None]
    if len(usable) < 2:
        detail = (
            "; unreadable: " + ", ".join(unreadable) if unreadable else ""
        )
        print(
            f"SKIP: {len(usable)} usable bench artifact(s) under "
            f"{args.root} — need a latest and at least one prior"
            f"{detail}"
        )
        return 1 if args.strict else 0
    latest = usable[-1]
    # A degraded run (CPU fallback where an accelerator was expected —
    # bench.py _finalize_artifact) must never become the bar: its
    # "value" measures the container, not the code.  Baseline
    # candidates are the non-degraded priors; when every prior is
    # degraded (a whole stretch of chip-less rounds) fall back to all of
    # them rather than skipping the check entirely.
    priors = [
        p_ for p_ in usable[:-1] if not results[p_].get("degraded")
    ]
    if not priors:
        print(
            "WARNING: every prior bench artifact is degraded — "
            "comparing against degraded baselines"
        )
        priors = usable[:-1]
    best_prior = max(priors, key=lambda p_: float(results[p_]["value"]))
    print(f"comparing latest {latest} against best prior {best_prior}:")
    rc = obs_main([
        "compare", "--fail-on-regress", str(args.frac), best_prior, latest,
    ])
    regressions = []
    if rc == 3:
        regressions.append(
            f"bench regression: {latest} fell more than "
            f"{100 * args.frac:.0f}% below {best_prior}"
        )
    elif rc != 0:
        print(f"FAIL: obs compare exited {rc}", file=sys.stderr)
        return rc
    else:
        print(f"OK: {latest} within {100 * args.frac:.0f}% of {best_prior}")

    # secondary gate: the packed input-path metric.  Its baseline is
    # chosen among priors that HAVE it (it postdates the early rounds),
    # still skipping degraded ones.
    e2e = "e2e_packed_examples_per_sec"
    latest_e2e = results[latest].get(e2e)
    e2e_priors = [p_ for p_ in priors if results[p_].get(e2e)]
    if latest_e2e and e2e_priors:
        best_e2e = max(e2e_priors, key=lambda p_: float(results[p_][e2e]))
        a = float(results[best_e2e][e2e])
        b = float(latest_e2e)
        drop = (a - b) / a if a > 0 else 0.0
        if drop > args.frac:
            regressions.append(
                f"input-path regression: {latest} {e2e}={b:.0f} is "
                f"{100 * drop:.1f}% below {best_e2e} ({a:.0f})"
            )
        else:
            print(
                f"OK: {e2e} {b:.0f} within {100 * args.frac:.0f}% of "
                f"best prior {best_e2e} ({a:.0f})"
            )
    elif not latest_e2e and e2e_priors:
        # priors measure the metric but the latest doesn't: the e2e
        # bench leg broke or was skipped — the gate must not silently
        # stop measuring the very metric it exists to protect
        regressions.append(
            f"missing metric: latest artifact {latest} has no {e2e} "
            "while prior artifacts measure it — the e2e packed bench "
            "leg did not run"
        )
    for msg in regressions:
        if args.strict:
            print(f"FAIL: {msg}", file=sys.stderr)
        else:
            print(f"WARN (non-gating): {msg}", file=sys.stderr)
    return 1 if (regressions and args.strict) else 0


if __name__ == "__main__":
    sys.exit(main())

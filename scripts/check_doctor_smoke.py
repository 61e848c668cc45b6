"""Doctor smoke lint: run the toy pipeline WITH the stall watchdog and
flight recorder armed, then assert the diagnosis toolchain's healthy
path end to end:

* the run emits no `health` rows and writes no flight dump (a healthy
  toy run must not trip the watchdog — a false positive here means the
  thresholds or the idle-phase handling regressed);
* the emitted metrics file (including the new run_start hostname/pid
  fields) still passes `obs validate` strictly;
* `python -m xflow_tpu.obs doctor` exits 0 and prints a clean
  diagnosis — the first-responder command keeps working on the boring
  case, so it can be trusted on the interesting one.

One finding is a reading of the host's clock and not of the program:
`recompile_suspicion`, the step-time shape of an epoch of SEVEN steps,
which one step that the scheduler held for 25 ms on a loaded host
satisfies (it failed the driver's tier-1 run of PR 33 on a tree that
compiled nothing twice).  A run whose ONLY complaint is that one is run
again, up to `ATTEMPTS` times; any other finding, a schema violation, a
`health` row, a watchdog trip or a flight dump fails at once, on
whichever attempt it shows.

Run from the repo root:

    JAX_PLATFORMS=cpu python scripts/check_doctor_smoke.py

Wired into tier-1 via tests/test_observability.py::
test_check_doctor_smoke_script, like the schema and serve smokes.
"""

from __future__ import annotations

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


ATTEMPTS = 5
# doctor findings that a busy host alone produces on a healthy toy run
TIMING_CODES = {"recompile_suspicion"}


def attempt() -> tuple[list[str], list[str], int]:
    """One toy run: (wrong values, timing findings, metrics rows)."""
    from tests.gen_data import generate_dataset
    from xflow_tpu.config import Config
    from xflow_tpu.obs.__main__ import main as obs_main
    from xflow_tpu.obs.doctor import diagnose
    from xflow_tpu.obs.schema import load_jsonl, validate_rows
    from xflow_tpu.trainer import Trainer

    errors: list[str] = []
    with tempfile.TemporaryDirectory() as root:
        ds = generate_dataset(
            os.path.join(root, "data"),
            num_train_shards=2,
            lines_per_shard=200,
            num_fields=10,
            vocab_per_field=8,
            seed=7,
            scale=3.0,
        )
        metrics = os.path.join(root, "metrics.jsonl")
        flight = os.path.join(root, "flight.json")
        cfg = Config(
            train_path=ds.train_prefix,
            test_path=ds.test_prefix,
            model="lr",
            epochs=2,
            batch_size=64,
            table_size_log2=14,
            max_nnz=24,
            num_devices=1,
            metrics_out=metrics,
            obs_flight_out=flight,
            obs_watchdog=True,  # default thresholds: must NOT trip
        )
        with Trainer(cfg) as t:
            t.train()
            t.evaluate()
            wd = t._watchdog
            if wd is None:
                errors.append("obs_watchdog=True built no watchdog")
            elif wd.trip_count:
                errors.append(
                    f"healthy toy run tripped the watchdog "
                    f"{wd.trip_count}x — thresholds or idle handling "
                    "regressed"
                )
        rows = load_jsonl(metrics)
        errors.extend(validate_rows(rows))
        if any(r.get("kind") == "health" for r in rows):
            errors.append("healthy run emitted `health` rows")
        if os.path.exists(flight):
            errors.append(
                "healthy run wrote a flight dump (nothing crashed, "
                "nothing stalled)"
            )

        problems = [
            d for d in diagnose(rows) if d.severity in ("crit", "warn")
        ]
        timing = sorted({d.code for d in problems} & TIMING_CODES)
        errors.extend(
            f"`obs doctor` on a healthy run: {d.code}: {d.message[:160]}"
            for d in problems if d.code not in TIMING_CODES
        )
        # the command itself, as an operator runs it: its exit code says
        # what the findings say
        rc = obs_main(["doctor", metrics])
        if rc != (1 if problems else 0):
            errors.append(
                f"`obs doctor` exited {rc} with {len(problems)} finding(s) "
                "at warn or above"
            )
    return errors, timing, len(rows)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for n in range(1, ATTEMPTS + 1):
        errors, timing, rows = attempt()
        if errors or not timing:
            break
        print(
            f"attempt {n} of {ATTEMPTS}: only {timing}, a "
            "reading of the host's clock and not of the program",
            file=sys.stderr,
        )
    if not errors:  # then ``timing`` is the last of ATTEMPTS such runs
        errors = [
            f"`obs doctor` raised {code} on each of {ATTEMPTS} healthy runs"
            for code in timing
        ]
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    if errors:
        return 1
    print(
        f"OK: watchdog armed, 0 trips; {rows} metrics rows validated; "
        f"obs doctor reports clean (attempt {n})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

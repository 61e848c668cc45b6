"""Metrics-schema lint: run the toy 2-epoch pipeline end to end and
validate every emitted JSONL row against the schema (obs/schema.py),
plus the phase-accounting invariant the summarize tool relies on —
main-thread phases must account for >= 90% of the run's wall-clock.

Run from the repo root:

    JAX_PLATFORMS=cpu python scripts/check_metrics_schema.py

Wired into tier-1 as a fast test (tests/test_observability.py::
test_check_metrics_schema_script), so a schema drift — a new field
missing from SCHEMA, a renamed kind, a broken phase counter — fails CI
instead of surfacing later as an unreadable metrics file.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_toy_pipeline(root: str) -> str:
    """2-epoch toy train + eval with metrics on; returns the JSONL path."""
    from tests.gen_data import generate_dataset
    from xflow_tpu.config import Config
    from xflow_tpu.trainer import Trainer

    ds = generate_dataset(
        os.path.join(root, "data"),
        num_train_shards=2,
        lines_per_shard=200,
        num_fields=10,
        vocab_per_field=8,
        seed=7,
        scale=3.0,
    )
    out = os.path.join(root, "metrics.jsonl")
    cfg = Config(
        train_path=ds.train_prefix,
        test_path=ds.test_prefix,
        model="lr",
        epochs=2,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
        metrics_out=out,
        # resource telemetry on (obs/export.py): the sampler thread
        # emits at least the start + close `resource` rows here, so
        # the schema lint covers the live-telemetry kinds too
        obs_resource_every_s=0.2,
    )
    with Trainer(cfg) as t:
        t.train()
        t.evaluate()
    return out


def check(path: str) -> list[str]:
    from xflow_tpu.obs.schema import SCHEMA, load_jsonl, validate_rows
    from xflow_tpu.obs.summary import split_runs

    rows = load_jsonl(path)
    errors = validate_rows(rows)

    kinds = {r.get("kind") for r in rows}
    for expected in ("run_start", "train_epoch", "eval", "shard",
                     "resource", "startup"):
        if expected not in kinds:
            errors.append(f"toy pipeline emitted no {expected!r} row")
    # ISSUE 55: one start-up row a trainer, and every epoch row says
    # what the process compiled during it (OPTIONAL in the schema for
    # the files from before; emitted by every current epoch)
    startups = sum(r.get("kind") == "startup" for r in rows)
    if startups != 1:
        errors.append(f"{startups} 'startup' rows for one trainer")
    for r in rows:
        if r.get("kind") == "train_epoch":
            for key in ("compiles", "compiles_cached", "compile_seconds"):
                if key not in r:
                    errors.append(f"train_epoch {r.get('epoch')} lacks {key!r}")
    unknown = kinds - set(SCHEMA)
    if unknown:
        errors.append(f"kinds missing from SCHEMA: {sorted(unknown)}")

    # the live-telemetry row constructors must themselves produce
    # schema-valid rows — alert rows come from the SLO evaluator
    # (obs/live.py), not the toy pipeline, so mint one directly
    from xflow_tpu.obs.schema import alert_row, resource_row

    synthetic = [
        dict(alert_row(
            rule="serve_error_frac", state="firing", value=0.5,
            threshold=0.05, short_s=60.0, long_s=300.0, samples=3,
            detail="lint",
        ), t=0.0, kind="alert"),
        dict(resource_row(
            rss_bytes=1, cpu_seconds=0.1, threads=1, open_fds=1,
            gc_collections=0,
        ), t=0.0, kind="resource"),
    ]
    errors.extend(
        f"constructor row: {e}" for e in validate_rows(synthetic)
    )

    # the summarize accounting contract: exclusive phases cover the
    # run's wall-clock (ISSUE 1 acceptance: >= 90%)
    for run in split_runs(rows):
        wall = run.wall_seconds()
        if not wall:
            continue
        accounted = sum(run.phase_totals()[0].values())
        if accounted / wall < 0.90:
            errors.append(
                f"phases account for only {accounted / wall:.1%} of "
                f"wall-clock (need >= 90%): phases "
                f"{json.dumps(run.phase_totals()[0])}, wall {wall:.3f}s"
            )
    return errors


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as root:
        path = run_toy_pipeline(root)
        errors = check(path)
        n = sum(1 for _ in open(path))
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    if errors:
        return 1
    print(f"OK: {n} rows validated against obs/schema.py")
    return 0


if __name__ == "__main__":
    sys.exit(main())

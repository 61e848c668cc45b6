"""Chip probe of the dense update's two forms for ONE table (PERF.md section
6, PRs 56 and 57): (a) a zeroed [T, D] gradient buffer, the write of the
batch's rows into it and the optimizer over the whole table (the dense
arm's: TrainStep._zero_gbufs, _cold_accumulate, _optimizer_pass), against
(b) the optimizer on the rows the batch names alone, three row gathers,
the recurrence on [n, D] and three sets (TrainStep._apply_touched_rows),
at the geometry of one real batch of a one-chip train cell.

    chiprun -- python scripts/probe_touched_rows.py [--seed N] [--calls 10]
        [--configs fibinet_ftrl_criteo_tb,mvm_ftrl_criteo_tb,...] [--variants]
        [--out chiprun_out/probe_touched_rows.json]

The batch is the benchmark cell's own (probe_cold_gather.cell_batch); the
table is the cell's widest, its rows the dictionary's and the tail's as
step.py::dict_scatter_plan codes them (capacity padding as the sentinel T),
the gradients N(0, 1) on the live rows.  Each form is timed as ``--calls``
chained calls on a donated state closed by one fetch (ms a call, ns an
index), then traced for its device operations by name (ms a call), with
the bytes of temporaries the compiler gives it.  ``equal``: from the same
drawn state, two chained updates (the second with every other row's
gradient exactly 0) leave param, n and z the same bits under (b) as under
(a), by a wrapping sum of every array's words and by the touched rows
themselves; it is asked only where the rows are DISTINCT (a dictionary
alone: a tail repeats rows, and a set keeps one of a repeated row's
updates).  ``--variants`` times (b) with ``unique_indices`` on the sets
and with the rows in rising order and ``indices_are_sorted`` besides.

Where (b) wins by the table's padded elements per index is
``step.TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX``.  A measurement path: exits 1
without a TPU, every row names the device it ran on, and distinct rows
that do not come out equal end the probe with a non-zero exit code.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import numpy as np  # noqa: E402

from probe_cold_gather import cell_batch  # noqa: E402

CONFIGS = (
    "fibinet_ftrl_criteo_tb", "autoint_ftrl_criteo_tb",
    "mvm_ftrl_criteo_tb", "dcn_ftrl_criteo_tb",
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=5700000007)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--out", default="chiprun_out/probe_touched_rows.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest, trace_reduce
    from xflow_tpu.config import Config
    from xflow_tpu.models import make_model
    from xflow_tpu.ops import window
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel import step as step_mod
    from xflow_tpu.parallel.mesh import make_mesh

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU (found {device.platform}): nothing measured",
              file=sys.stderr)
        return 1
    stamp = {"platform": device.platform, "device_kind": device.device_kind}
    rows_out: list[dict] = []

    def emit(row: dict) -> None:
        rows_out.append({**row, **stamp})
        print(json.dumps(rows_out[-1]), flush=True)

    def probe(config: str) -> None:
        fields, cb = cell_batch(args.seed, config)
        cfg = Config(**{
            k: v for k, v in fields.items() if k not in manifest.CONFIG_META
        })
        step = step_mod.TrainStep(
            make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1)
        )
        spec = max(step.model.tables(), key=lambda s: s.dim)
        t, d = cfg.table_size, spec.dim
        wire = jax.tree.map(jnp.asarray, cb.wire(False))
        lane = window.lane_select_tpu
        plan = jax.jit(functools.partial(
            step_mod.expand_dict_wire,
            types.SimpleNamespace(max_nnz=cfg.max_nnz, hot_nnz=cfg.hot_nnz),
            lane,
        ))(wire)["cold_plan"]
        rows = jax.jit(
            lambda p: step_mod.dict_scatter_plan(p, t, lane)["rows"]
        )(plan)
        n = rows.shape[0]
        live = np.asarray(rows) < t
        distinct = len(np.unique(np.asarray(rows)[live])) == int(live.sum())
        cap_u, cap_t = len(cb.cu), len(cb.ct)
        emit({
            "at": config, "table": [t, d], "cap_cu": cap_u, "cap_ct": cap_t,
            "live_rows": int(live.sum()), "distinct": distinct,
            "padded_elements_per_index":
                t * step_mod.padded_columns(d) / max(n, 1),
            "selected": spec.name in step._touched_rows_names(
                cap_u, cap_t, bool(cfg.hot_size)
            ),
        })
        key = jax.random.key(args.seed & 0x7FFFFFFF)
        g1 = jax.random.normal(key, (n, d), jnp.float32) * live[:, None]
        g2 = g1 * (jnp.arange(n) % 2)[:, None]

        @jax.jit
        def drawn():
            param = 0.01 * jax.random.normal(key, (t, d), jnp.float32)
            zeros = jnp.zeros_like(param)  # (param * 0 has the sign of param)
            return {"param": param, "n": zeros, "z": zeros}

        def dense(table, rows, g):
            (gbuf,) = step._zero_gbufs({spec.name: table}).values()
            return step._optimizer_pass(
                table, step._cold_accumulate(gbuf, rows, g)
            )

        def sets(**kw):
            def form(table, rows, g):
                if kw.get("indices_are_sorted"):
                    order = jnp.argsort(rows)
                    rows, g = rows[order], g[order]
                state = {k: a.at[rows].get(mode="clip") for k, a in table.items()}
                new = step.optimizer.update_rows(state, g)
                return {
                    k: table[k].at[rows].set(new[k], mode="drop", **kw)
                    for k in table
                }
            return form

        @jax.jit
        def digest(table):
            return {
                k: (
                    jnp.sum(jax.lax.bitcast_convert_type(a, jnp.uint32),
                            dtype=jnp.uint32),
                    a.at[rows].get(mode="clip"),
                )
                for k, a in table.items()
            }

        want: list = []  # (a)'s digest, for the forms after it

        def measure(form: str, fn, check: bool):
            f = jax.jit(fn, donate_argnums=0)
            compiled = f.lower(drawn(), rows, g1).compile()
            row = {
                "at": config, "form": form, "indices": n,
                "temp_gib": compiled.memory_analysis().temp_size_in_bytes / 2**30,
            }
            table = f(f(drawn(), rows, g1), rows, g2)
            left = jax.device_get(digest(table))
            for _ in range(2):  # warm
                table = f(table, rows, g1)
            jax.block_until_ready(table)
            t0 = time.perf_counter()
            for _ in range(args.calls):
                table = f(table, rows, g1)
            jax.block_until_ready(table)
            row["ms"] = (time.perf_counter() - t0) / args.calls * 1e3
            row["ns_per_index"] = row["ms"] * 1e6 / n
            trace_dir = os.path.join(ROOT, ".bench_cache", "probe_touched_rows")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "probe"):
                    for _ in range(args.calls):
                        table = f(table, rows, g1)
                    jax.block_until_ready(table)
            finally:
                jax.profiler.stop_trace()
            del table
            trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(
                trace, trace_reduce.span_window(trace, "probe"),
                steps=args.calls, top=8,
            )
            row["device_ms"] = reduced["busy_s_per_step"] * 1e3
            row["ops_ms"] = {
                name: round(s / args.calls * 1e3, 3)
                for name, s in reduced["device_ops"]
            }
            if check:
                row["equal"] = all(
                    np.array_equal(
                        np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32)
                    )
                    for a, b in zip(
                        jax.tree.leaves(left), jax.tree.leaves(want[0])
                    )
                )
            else:
                want.append(left)
            emit(row)

        measure("(a) dense arm: zeroed buffer + table write + FTRL over the "
                "table", dense, check=False)
        measure("(b) touched rows: 3 gathers + FTRL on the rows + 3 sets",
                step._apply_touched_rows, check=distinct)
        if args.variants:
            measure("(b) with unique_indices=True on the sets",
                    sets(unique_indices=True), check=distinct)
            measure("(b) with the rows in rising order and "
                    "indices_are_sorted, unique_indices",
                    sets(unique_indices=True, indices_are_sorted=True),
                    check=distinct)

    for config in args.configs.split(","):
        probe(config)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows_out, f, indent=1)
    return 0 if all(r.get("equal", True) for r in rows_out) else 1


if __name__ == "__main__":
    sys.exit(main())

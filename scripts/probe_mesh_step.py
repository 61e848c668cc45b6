"""Chip probe of the train step across a mesh (PERF.md section 6, PR 27):
a few chained ``TrainStep.train`` calls at the full geometry of a
benchmark configuration on its chips, before any whole run of its cell.

    chiprun --chips 4 -- python scripts/probe_mesh_step.py \
        [--config fm_ftrl_criteo_tb] [--steps 3] [--old OLD_STEP.py]
    JAX_PLATFORMS=cpu python scripts/probe_mesh_step.py --aot

The batches are the benchmark's own rows (benchmarks/generators/rows.py)
through its hot remap and the loader's steering, made on the host from
``--seed``.  It prints, as JSON rows: the collectives of the compiled
program (parallel/exchange.py::collectives_in: how many, the largest
leading dimension, how many sit in a loop); milliseconds a step over
``--steps`` chained calls closed by one fetch; and, from a profiler trace
of as many steps again, the first device's seconds a step inside
collectives and the part of them nothing else overlapped
(benchmarks/harness/trace_reduce.py).  ``--old`` names a copy of another
tree's parallel/step.py, whose TrainStep is probed the same way AFTER
this tree's (so that a form that never ends costs only the call's
limit): the logloss of its steps must agree with this tree's.

A measurement path: exits 1 without the TPU chips the configuration asks
for, and every row names the device it ran on.  ``--aot`` measures
nothing: it compiles this tree's step for a DESCRIBED v5e:2x2
(on-chip-measurement guide, section 2.3) and prints the collectives and
``memory_analysis()`` of the program alone.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

GIB = float(1 << 30)


def make_batches(cfg, count: int, seed: int) -> list:
    """``count`` batches of the benchmark's rows at ``cfg``'s geometry, as
    the loader would steer them."""
    from benchmarks.generators.rows import RowGenerator, RowSpec
    from benchmarks.harness import corpus, manifest
    from xflow_tpu.io.batch import make_batch

    spec = RowSpec.from_params(manifest.traffic("replay_packed_zipf")["rows"])
    gen = RowGenerator(spec, seed)
    remap, _ = corpus.hot_remap(gen, cfg.table_size, cfg.hot_size, seed)
    width = cfg.max_nnz + cfg.hot_nnz
    out = []
    for shard in range(count):
        gid, labels = corpus.shard_rows(gen, shard, cfg.batch_size)
        rows = remap[gen.keys(gid, cfg.table_size, seed)]
        keys = np.zeros((cfg.batch_size, width), np.int32)
        mask = np.zeros((cfg.batch_size, width), np.float32)
        keys[:, : rows.shape[1]] = rows
        mask[:, : rows.shape[1]] = 1.0
        out.append(make_batch(
            keys, np.zeros_like(keys), mask.copy(), mask,
            labels.astype(np.float32), np.ones(cfg.batch_size, np.float32),
            cfg.hot_size, cfg.hot_nnz,
        ))
    return out


def summary(found: list[dict]) -> dict:
    by_op: dict[str, int] = {}
    for c in found:
        by_op[c["op"]] = by_op.get(c["op"], 0) + 1
    return {
        "collectives": len(found), "by_op": by_op,
        "in_loops": sum(c["in_loop"] for c in found),
        "largest_rows": max((c["rows"] for c in found), default=0),
    }


def aot(cfg, batches: list, hlo_out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.exchange import collectives_in
    from xflow_tpu.parallel.mesh import make_mesh, replicated, table_sharding
    from xflow_tpu.parallel.step import TrainStep

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(cfg.num_devices, devices=list(topo.devices))
    model = make_model(cfg)
    step = TrainStep(model, make_optimizer(cfg), cfg, mesh)
    wire, _ = step.host_wire_np(batches[0])

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = {
        "tables": {
            spec.name: {
                name: shaped(
                    (cfg.table_size, spec.dim), jnp.float32, table_sharding(mesh)
                )
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {},
        "step": shaped((), jnp.int32, replicated(mesh)),
    }
    arrays = {k: shaped(v.shape, v.dtype, step._bsharding) for k, v in wire.items()}
    compiled = step.train.lower(state, arrays).compile()
    text = compiled.as_text()
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(text)
    found = collectives_in(text)
    ma = compiled.memory_analysis()
    peak = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    for c in found:
        print(json.dumps(c))
    print(json.dumps({
        "aot": "compiled for a described v5e:2x2, not run",
        "devices": int(mesh.devices.size), **summary(found),
        "program_peak_gib_per_device": round(peak / GIB, 3),
    }))
    return 0


def probe(name: str, step_cls, cfg, mesh, batches: list, steps: int, stamp: dict) -> dict:
    """One tree's TrainStep: compile, ``steps`` chained calls and a fetch,
    then as many again under the profiler."""
    import jax

    from benchmarks.harness import trace_reduce
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.exchange import collectives_in
    from xflow_tpu.parallel.step import abstract_like, init_state

    model, opt = make_model(cfg), make_optimizer(cfg)
    step = step_cls(model, opt, cfg, mesh)
    state = init_state(model, opt, cfg, mesh)
    staged = [step.put_batch(b) for b in batches]
    t0 = time.perf_counter()
    compiled = step.train.lower(
        abstract_like(state), abstract_like(staged[0])
    ).compile()
    row = {
        "form": name, **stamp, **summary(collectives_in(compiled.as_text())),
        "compile_s": time.perf_counter() - t0,
    }
    print(json.dumps(row), flush=True)

    def chain(state):
        losses = []
        for i in range(steps):
            state, metrics = step.train(state, staged[i % len(staged)])
            losses.append(metrics["logloss"])
        return state, [float(x) for x in jax.device_get(losses)]

    t0 = time.perf_counter()
    state, first = chain(state)  # the first call loads or compiles
    row["first_chain_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, losses = chain(state)
    row["ms_per_step"] = (time.perf_counter() - t0) / steps * 1e3
    row["logloss"] = first + losses
    print(json.dumps(row), flush=True)

    trace_dir = os.path.join(ROOT, ".bench_cache", "probe_mesh", name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "probe"):
            state, _ = chain(state)
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    reduced = trace_reduce.reduce(
        trace, trace_reduce.span_window(trace, "probe"), steps=steps
    )
    row.update({
        "busy_ms_per_step": reduced["busy_s_per_step"] * 1e3,
        "collective_ms_per_step": reduced["collective_s"] / steps * 1e3,
        "collective_exposed_ms_per_step": reduced["collective_exposed_s"] / steps * 1e3,
        "device_ops": reduced["device_ops"],
    })
    print(json.dumps(row), flush=True)
    del state, staged
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="fm_ftrl_criteo_tb")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--old", default="")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--hlo-out", default="", help="with --aot: keep the module's text")
    args = ap.parse_args()

    from benchmarks.harness import manifest
    from xflow_tpu.config import Config

    doc = manifest.config_file(f"benchmarks/configs/{args.config}.json")
    cfg = Config(seed=args.seed, **{
        k: v for k, v in doc.items() if k not in manifest.CONFIG_META
    })
    batches = make_batches(cfg, args.steps, args.seed)
    if args.aot:
        return aot(cfg, batches, args.hlo_out)

    import jax

    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep
    from xflow_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cfg.num_devices:
        print(
            f"needs {cfg.num_devices} TPU chips (found {len(devices)} x "
            f"{devices[0].platform}): nothing measured", file=sys.stderr,
        )
        return 1
    enable_compile_cache()
    stamp = {
        "platform": devices[0].platform, "device_kind": devices[0].device_kind,
        "devices": cfg.num_devices,
    }
    mesh = make_mesh(cfg.num_devices)
    rows = [probe("this_tree", TrainStep, cfg, mesh, batches, args.steps, stamp)]
    if args.old:
        spec = importlib.util.spec_from_file_location("old_step", args.old)
        old_step = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old_step)
        rows.append(probe(
            "old", old_step.TrainStep, cfg, mesh, batches, args.steps, stamp
        ))
        worst = max(abs(a - b) for a, b in zip(*(r["logloss"] for r in rows)))
        rows.append({"check": "logloss agrees with --old", "worst": worst,
                     "equal": worst <= 1e-5, **stamp})
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_mesh_step.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r.get("equal", True) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

"""Does the train program of a configuration that owns DENSE replicated
parameters fit its chip, and what did the TPU's compiler make of it?  Asked
with no chip: the step is compiled for a described ``v5e:2x2`` topology
(on-chip-measurement guide, section 2.3).  Nothing runs, so this says nothing
about times.

    JAX_PLATFORMS=cpu python3 scripts/aot_dense_step.py \
        --config dcn_ftrl_criteo_tb [--table-size-log2 25] [--batch-size N] \
        [--seed 1] [--hlo-out FILE]

``benchmarks/aot_memory.py`` builds its state with ``"dense": {}`` and so
cannot size such a family (PERF.md section 7); this hands the step the
shapes of ``model.dense_init`` beside the tables', and the wire planes of
one real batch of the benchmark's rows (``aot_memory.one_batch``).  Beside
the memory it reads the compiled text for what PR 34 taught to look for
before a chip run: table-sized copies of the state, ``[B, K, 1]``
column planes of the cold slots, and the largest arrays the program
makes beside its tables (an xDeepFM step whose ``[B, H, m, D]`` pair
tensor came back whole shows here, on the CPU).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

GIB = float(1 << 30)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--table-size-log2", type=int)
    ap.add_argument("--batch-size", type=int)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--hlo-out", help="write the compiled program's text here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks import aot_memory
    from benchmarks.harness import manifest
    from xflow_tpu.config import Config
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh, replicated, table_sharding
    from xflow_tpu.parallel.step import TrainStep

    doc = manifest.config_file(f"benchmarks/configs/{args.config}.json")
    fields = {
        k: v for k, v in manifest.apply_rehearsal(doc, False).items()
        if k not in manifest.CONFIG_META
    }
    if args.table_size_log2:
        fields["table_size_log2"] = args.table_size_log2
    if args.batch_size:
        fields["batch_size"] = args.batch_size
    cfg = Config(**fields, seed=args.seed)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(cfg.num_devices, devices=list(topo.devices))
    model = make_model(cfg)
    step = TrainStep(model, make_optimizer(cfg), cfg, mesh)

    work = os.path.join(ROOT, ".bench_cache", "aot_dense_step")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        wire, _ = step.host_wire_np(aot_memory.one_batch(fields, args.seed, work))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows, whole = table_sharding(mesh), replicated(mesh)
    dense = jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
    state = {
        "tables": {
            spec.name: {
                name: shaped((cfg.table_size, spec.dim), jnp.float32, rows)
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {
            name: shaped(a.shape, a.dtype, whole) for name, a in dense.items()
        },
        "step": shaped((), jnp.int32, whole),
    }
    batch = {k: shaped(v.shape, v.dtype, step._bsharding) for k, v in wire.items()}
    compiled = step.train.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(hlo)
    # the state is donated, so the outputs that alias it take no new room
    peak = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    t, b = cfg.table_size, cfg.batch_size
    copies = [
        line.split("metadata")[0].strip() for line in hlo.splitlines()
        if re.search(rf"= \(?f32\[{t},[0-9]+\][^=]* copy(?:-start)?\(", line)
    ]
    planes = set(re.findall(rf"(\S+) = f32\[{b},{cfg.max_nnz},1\]", hlo))
    # the largest arrays any instruction makes that are not the tables'
    # own shapes: a CIN's pair tensor come back whole (B * H * m * D
    # elements, models/blocks.py::cin_stack) would lead this list
    table_shapes = {(t, spec.dim) for spec in model.tables()} | {(t,)}
    made: dict[str, int] = {}
    for kind, dims in re.findall(r" = \(?([a-z]+[0-9]+)\[([0-9,]+)\]", hlo):
        shape = tuple(map(int, dims.split(",")))
        if shape not in table_shapes:
            made[f"{kind}[{dims}]"] = math.prod(shape) * int(re.sub(r"\D", "", kind)) // 8
    largest = sorted(made.items(), key=lambda kv: -kv[1])[:5]
    print(json.dumps({
        "config": args.config,
        "table_size_log2": cfg.table_size_log2,
        "batch_size": b,
        "wire": step.wire_format,
        "wire_planes": {k: [list(v.shape), str(v.dtype)] for k, v in wire.items()},
        "dense_shapes": {name: list(a.shape) for name, a in dense.items()},
        "per_device_gib": {
            "arguments": round(ma.argument_size_in_bytes / GIB, 3),
            "temporaries": round(ma.temp_size_in_bytes / GIB, 3),
            "outputs": round(ma.output_size_in_bytes / GIB, 3),
            "aliased": round(ma.alias_size_in_bytes / GIB, 3),
            "program_peak": round(peak / GIB, 3),
        },
        "table_sized_copies": copies,
        "cold_column_planes": len(planes),
        "largest_temporaries_mib": {k: round(v / 2**20, 1) for k, v in largest},
        "note": "compiled for a described v5e:2x2, not run; one program, "
                "not what else the process keeps on the device",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

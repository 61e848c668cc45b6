"""Does a configuration's train program fit its chips?  Asked of the TPU's
compiler, with no chip: the program is compiled for a described ``v5e:2x2``
topology (on-chip-measurement guide, section 2.3) and its
``memory_analysis()`` read.  Nothing runs, so this says nothing about times.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_memory.py \
        --config lr_ftrl_criteo_tb [--table-size-log2 29] [--seed 1]

(a configuration across chips gets ``num_devices`` of the described four)

The wire planes get the shapes of one real batch of the benchmark's rows,
made on the host through the program's own parser and packer (the
dictionary wire's plane capacities depend on the content).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

GIB = float(1 << 30)
COLLECTIVES = (
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute",
)


def one_batch(fields: dict, seed: int, work: str):
    """The first batch of a one-batch text corpus of the benchmark's rows at
    this geometry, as the loader hands it to the step."""
    from benchmarks.generators.rows import RowGenerator, RowSpec
    from benchmarks.harness import corpus, manifest
    from xflow_tpu.io.loader import ShardLoader, make_parse_fn

    spec = RowSpec.from_params(manifest.traffic("replay_packed_zipf")["rows"])
    gen = RowGenerator(spec, seed)
    text, remap = corpus.text_corpus(
        gen, work, {**fields, "input_streams": 1}, 1, seed
    )
    table_size = 1 << fields["table_size_log2"]
    hot_size = 1 << fields["hot_size_log2"]
    loader = ShardLoader(
        os.path.join(work, text["shards"][0]), batch_size=fields["batch_size"],
        max_nnz=fields["max_nnz"], table_size=table_size, hash_seed=seed,
        parse_fn=make_parse_fn(table_size, True, seed), remap=remap,
        hot_size=hot_size, hot_nnz=fields["hot_nnz"],
    )
    return next(iter(loader.iter_batches()))[0]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--table-size-log2", type=int)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

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
    cfg = Config(**fields, seed=args.seed)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(cfg.num_devices, devices=list(topo.devices))
    model = make_model(cfg)
    step = TrainStep(model, make_optimizer(cfg), cfg, mesh)

    work = os.path.join(ROOT, ".bench_cache", "aot_memory")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        wire, _ = step.host_wire_np(one_batch(fields, args.seed, work))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows = table_sharding(mesh)
    state = {
        "tables": {
            spec.name: {
                name: shaped((cfg.table_size, spec.dim), jnp.float32, rows)
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {},
        "step": shaped((), jnp.int32, replicated(mesh)),
    }
    batch = {k: shaped(v.shape, v.dtype, step._bsharding) for k, v in wire.items()}
    compiled = step.train.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    # the state is donated, so the outputs that alias it take no new room
    peak = (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )
    print(json.dumps({
        "config": args.config,
        "table_size_log2": cfg.table_size_log2,
        "devices": int(mesh.devices.size),
        "wire": step.wire_format,
        "wire_planes": {k: [list(v.shape), str(v.dtype)] for k, v in wire.items()},
        "per_device_gib": {
            "arguments": round(ma.argument_size_in_bytes / GIB, 3),
            "temporaries": round(ma.temp_size_in_bytes / GIB, 3),
            "outputs": round(ma.output_size_in_bytes / GIB, 3),
            "aliased": round(ma.alias_size_in_bytes / GIB, 3),
            "program_peak": round(peak / GIB, 3),
        },
        "collectives": {
            name: len(re.findall(rf"\b{name}(?:-start)?\(", hlo))
            for name in COLLECTIVES
        },
        "note": "compiled for a described v5e:2x2, not run; one program, "
                "not what else the process keeps on the device",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

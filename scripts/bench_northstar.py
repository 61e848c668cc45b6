"""North-star shape bench: T=2^28 LR+FTRL on one chip (BASELINE.md
targets table: "hashed 2^28 features").

Proves HBM fit of the full-size table (w,n,z = 3 x [2^28,1] f32 =
3 GiB) and records examples/sec for update_mode in {dense, sparse} and
for the flagship hot/cold geometry, on REAL zipf batches off the CSR
binary cache (full 64-bit keys stored, so the same cache re-keys at any
table size without re-parsing — docs/PERF.md collision section).

At T=2^28 the dense mode's full-table FTRL elementwise pass touches
3 GiB/step; the sparse mode consolidates to unique keys and updates
only touched rows — this is the shape where the two modes genuinely
diverge, which is why BASELINE.md wants both numbers.

Run: python scripts/bench_northstar.py [--iters N]
One JSON line per config; paste into docs/PERF.md.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, ".")

import bench
from xflow_tpu.config import Config
from xflow_tpu.io import freq

T_LOG2 = 28
BATCH = 131072
NBATCH = 4


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()

    from xflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    accel = jax.devices()
    if accel[0].platform == "cpu":
        raise SystemExit(
            "bench_northstar: JAX found no accelerator (platform 'cpu'); "
            "the T=2^28 shape is a chip measurement"
        )

    # shared data prep (synth shard + CSR cache, both disk-cached)
    probe_cfg = Config(
        model="lr", optimizer="ftrl", table_size_log2=T_LOG2,
        batch_size=BATCH, max_nnz=40, num_devices=1,
    )
    _, csr, _, _ = bench.prepare_real_data(probe_cfg, 2_000_000)

    base = dict(
        model="lr",
        optimizer="ftrl",
        table_size_log2=T_LOG2,
        batch_size=BATCH,
        num_devices=1,
    )
    # dense vs sparse hot-off (the mode comparison), plus the flagship
    # hot geometry at 2^28 (hot path is table-size independent; the
    # cold section re-keys at 2^28)
    sweeps = [
        ("dense, hot off", dict(max_nnz=40, update_mode="dense"), False),
        ("sparse, hot off", dict(max_nnz=40, update_mode="sparse"), False),
        (
            "dense, hot 2^12x32 cold 16 (flagship)",
            dict(max_nnz=16, hot_size_log2=12, hot_nnz=32,
                 update_mode="dense"),
            True,
        ),
    ]

    counts = remap = None
    for name, kw, want_hot in sweeps:
        cfg = Config(**{**base, **kw})
        mass = None
        r = None
        if want_hot:
            if counts is None:
                counts = bench.cached_counts(csr, T_LOG2)
                remap = freq.build_remap(counts, cfg.hot_size)
            r = remap
            mass = freq.hot_mass(counts, r, cfg.hot_size)
        batches, trunc = bench.real_batches(cfg, csr, r, NBATCH)
        step, state = bench.build(accel, cfg)
        t0 = time.time()
        _, eps = bench.run(step, state, batches, iters=args.iters)
        print(json.dumps({
            "config": name,
            "table_size_log2": T_LOG2,
            "examples_per_sec": round(eps, 0),
            "truncated_frac": round(trunc, 5),
            "hot_mass": None if mass is None else round(mass, 4),
            "compile_plus_run_secs": round(time.time() - t0, 1),
            "backend": accel[0].platform,
            "device_kind": accel[0].device_kind,
        }), flush=True)


if __name__ == "__main__":
    main()

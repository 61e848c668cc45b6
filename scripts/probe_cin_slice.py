"""Time ``blocks.cin_stack`` (forward and backward, alone) on the chip over
the examples a slice holds, by default at the geometry of
``xdeepfm_tb.train_packed``:
what ``blocks.CIN_PAIR_BYTES`` was fitted to (PERF.md section 6).

    chiprun -- python scripts/probe_cin_slice.py [--slices 64,128,256,512,1024]
        [--geometry B,m,D,maps,layers]

Prints one JSON object (ms a step and the program's peak by slice, each
slice's largest difference from the first's in the pooled maps and the
tower's gradient) and writes it to ``chiprun_out/cin_probe.json``.  Exit 1
without a TPU: a CPU run times nothing worth writing down."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.models import blocks

GEOMETRY = "16384,40,10,200,3"  # B, max_fields, emb_dim, cin_maps, cross_layers


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slices", default="64,128,256,512,1024")
    ap.add_argument("--geometry", default=GEOMETRY)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    b, m, d, maps, layers = map(int, args.geometry.split(","))
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    keys = jax.random.split(jax.random.PRNGKey(0), layers + 2)
    widths = [m] + [maps] * layers
    weights = [
        jax.random.normal(k, (o, i, m), jnp.float32) / np.sqrt(i * m)
        for k, i, o in zip(keys, widths[:-1], widths[1:])
    ]
    tower = jax.random.normal(keys[-2], (b, m, d), jnp.float32) * 0.1
    ct = jax.random.normal(keys[-1], (b, layers * maps), jnp.float32)
    out: dict = {"device": jax.devices()[0].device_kind}
    first = None
    for s in map(int, args.slices.split(",")):
        def both(ws, t, s=s):
            pooled, vjp = jax.vjp(lambda ws, t: blocks.cin_stack(ws, t, s), ws, t)
            return pooled, vjp(ct)

        step = jax.jit(both).lower(weights, tower).compile()
        got = jax.block_until_ready(step(weights, tower))
        start = time.perf_counter()
        for _ in range(args.steps):
            got = step(weights, tower)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - start) / args.steps * 1e3
        pooled, (_, d_tower) = got
        first = first or (pooled, d_tower)
        out[f"S{s}"] = {
            "ms": ms,
            "peak_gib": step.memory_analysis().temp_size_in_bytes / 2**30,
            "pooled_off_first": float(
                jnp.max(jnp.abs(pooled - first[0])) / jnp.max(jnp.abs(first[0]))
            ),
            "d_tower_off_first": float(
                jnp.max(jnp.abs(d_tower - first[1])) / jnp.max(jnp.abs(first[1]))
            ),
        }
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/cin_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

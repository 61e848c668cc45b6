"""Time ``blocks.field_attention_stack`` (forward and backward, alone) on the
chip over the examples a slice holds, by default at the geometry of
``autoint_tb.train_packed``: what ``blocks.ATTN_SLICE_BYTES`` was read at
(PERF.md section 6).

    chiprun -- python scripts/probe_attn_slice.py [--slices 128,256,512,1024,2048]
        [--geometry B,m,d,heads,head_dim,layers]

Prints one JSON object (ms a step and the program's temporaries by slice, each
slice's largest difference from the first's in the fields' vectors and the
tower's gradient) and writes it to ``chiprun_out/attn_probe.json``.  One row
in 200 lacks a field (as a dropped entry leaves it), and every row lacks the
last (the 40th bucket of 39 fields).  Exit 1 without a TPU: a CPU run times
nothing worth writing down."""

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

GEOMETRY = "16384,40,16,2,32,3"  # B, max_fields, emb_dim, attn_heads, attn_dim, cross_layers


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slices", default="128,256,512,1024,2048")
    ap.add_argument("--geometry", default=GEOMETRY)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    b, m, d, heads, head, layers = map(int, args.geometry.split(","))
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    width = heads * head
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 4 * layers + 3))
    weights = [
        tuple(
            jax.random.normal(next(keys), (d_in, width), jnp.float32)
            * np.sqrt(2.0 / (d_in + width))
            for _ in range(4)
        )
        for d_in in [d] + [width] * (layers - 1)
    ]
    present = (jax.random.uniform(next(keys), (b, m)) > 0.005).astype(jnp.float32)
    present = present.at[:, -1].set(0.0)
    tower = jax.random.normal(next(keys), (b, m, d), jnp.float32) * 0.1
    tower = tower * present[..., None]
    ct = jax.random.normal(next(keys), (b, m, width), jnp.float32)
    out: dict = {"device": jax.devices()[0].device_kind}
    first = None
    for s in map(int, args.slices.split(",")):
        def both(ws, t, s=s):
            e, vjp = jax.vjp(
                lambda ws, t: blocks.field_attention_stack(ws, t, present, heads, s),
                ws, t,
            )
            return e, vjp(ct)

        step = jax.jit(both).lower(weights, tower).compile()
        got = jax.block_until_ready(step(weights, tower))
        start = time.perf_counter()
        for _ in range(args.steps):
            got = step(weights, tower)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - start) / args.steps * 1e3
        e, (_, d_tower) = got
        first = first or (e, d_tower)
        out[f"S{s}"] = {
            "ms": ms,
            "temp_gib": step.memory_analysis().temp_size_in_bytes / 2**30,
            "out_off_first": float(
                jnp.max(jnp.abs(e - first[0])) / jnp.max(jnp.abs(first[0]))
            ),
            "d_tower_off_first": float(
                jnp.max(jnp.abs(d_tower - first[1])) / jnp.max(jnp.abs(first[1]))
            ),
        }
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Time DLRM's dot interaction (forward and backward) on the chip in the
forms it could take, alone and inside the dense half it sits in, by default
at the geometry of ``dlrm_tb.train_packed``: what chose the writing of
``blocks.pairwise_dots`` (PERF.md section 6).

    chiprun -- python scripts/probe_interact.py [--geometry B,n,d]

The forms, each of ``[Z_ij for i > j]`` with ``Z = T T^T`` an example, ``T [B,
n, d]``:

* ``shipped``: ``blocks.pairwise_dots``: one batched product at HIGHEST, the
  pairs picked by a ``[n n, P]`` one-hot product at HIGHEST (exact: a
  one-hot is exact in bfloat16);
* ``batched_concat``: the same product, the rows' lower parts cut out of ``[B,
  n, n]`` and laid side by side;
* ``batched_gather``: the same product, the pairs picked by one constant-index
  gather out of ``[B, n n]``;
* ``lanes``: the lane form of ``field_attention_layer``'s scores: the batch
  relaid with the examples on the 128 lanes, ``[1, d, n, B]``, every pair's
  products summed over the major axis d by ``blocks._lane_pair`` (a Mosaic
  kernel), the rows' lower parts cut out of ``[n, n, B]`` along major axes
  and relaid back; the relayouts counted.  In the cell's whole step it read
  104.03 ms against the shipped form's 102.63 (PR 58), so it lives here.

``alone`` is the block's forward and backward under a random cotangent;
``in_dense`` the same inside the whole dense half at the paper's widths (the
bottom stack over ``[B, 13]`` values, the concatenation with ``[B, n - 1, d]``
embeddings, the pairs, the top stack, a sum), gradients to every dense array
and to the embeddings: a block's price alone is not its price in the program
around it (PERF.md section 6, PR 52).  Each form's largest difference from
``shipped`` is beside its time.  Prints one JSON object and writes it to
``chiprun_out/interact_probe.json``.  Exit 1 without a TPU: a CPU run times
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
from xflow_tpu.models.dlrm import DLRMModel

GEOMETRY = "32768,27,128"  # B, interacting vectors, emb_dim
HIGHEST = jax.lax.Precision.HIGHEST


def _products(t: jax.Array) -> jax.Array:
    return jnp.einsum("bid,bjd->bij", t, t, precision=HIGHEST).reshape(len(t), -1)


def batched_concat(t: jax.Array) -> jax.Array:
    n = t.shape[1]
    z = _products(t).reshape(len(t), n, n)
    return jnp.concatenate([z[:, i, :i] for i in range(1, n)], axis=-1)


def batched_gather(t: jax.Array) -> jax.Array:
    i, j = np.tril_indices(t.shape[1], -1)
    return _products(t)[:, i * t.shape[1] + j]


def lanes(t: jax.Array) -> jax.Array:
    relaid = t.transpose(2, 1, 0)[None]  # [1, d, n, B]
    z = blocks._lane_pair(relaid, relaid)[0]  # [n, n, B]: z[b, a] = <t_b, t_a>
    return jnp.concatenate([z[i, :i] for i in range(1, t.shape[1])], axis=0).T


FORMS = {
    "shipped": blocks.pairwise_dots,
    "batched_concat": batched_concat,
    "batched_gather": batched_gather,
    "lanes": lanes,
}


def _time(step, args, steps: int):
    got = jax.block_until_ready(step(*args))
    start = time.perf_counter()
    for _ in range(steps):
        got = step(*args)
    jax.block_until_ready(got)
    return got, (time.perf_counter() - start) / steps * 1e3


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--geometry", default=GEOMETRY)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    b, n, d = map(int, args.geometry.split(","))
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    model = DLRMModel(
        emb_dim=d, numeric_fields=13, mlp_bottom=(512, 256, d),
        mlp_top=(1024, 1024, 512, 256), max_fields=13 + n,
    )
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 5))
    dense = model.dense_init(next(keys))
    t = jax.random.normal(next(keys), (b, n, d), jnp.float32)
    ct = jax.random.normal(next(keys), (b, blocks.vector_pairs(n)), jnp.float32)
    numeric = jax.random.uniform(next(keys), (b, 13), jnp.float32) * 6.0
    emb = jax.random.normal(next(keys), (b, n - 1, d), jnp.float32) * 0.05

    out: dict = {"device": jax.devices()[0].device_kind, "geometry": [b, n, d]}
    first = None
    for name, form in FORMS.items():
        def alone(t, form=form):
            pairs, vjp = jax.vjp(form, t)
            return pairs, vjp(ct)[0]

        def in_dense(dense, numeric, emb, form=form):
            def loss(dense, emb):
                z = blocks.mlp_stack(dense, numeric, 3, prefix="bot_")
                pairs = form(jnp.concatenate([z[:, None, :], emb], axis=1))
                g = blocks.mlp_stack(
                    dense, jnp.concatenate([z, pairs], axis=-1), 4, prefix="top_"
                )
                return jnp.sum(blocks.dense_dot(g, dense["w_out"]))
            return jax.grad(loss, argnums=(0, 1))(dense, emb)

        row: dict = {}
        try:
            step = jax.jit(alone).lower(t).compile()
            (pairs, d_t), row["alone_ms"] = _time(step, (t,), args.steps)
            row["alone_temp_gib"] = step.memory_analysis().temp_size_in_bytes / 2**30
            first = first or (pairs, d_t)
            for what, a, ref in zip(("pairs", "d_t"), (pairs, d_t), first):
                row[f"{what}_off_shipped"] = float(
                    jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref))
                )
            del pairs, d_t
            step = jax.jit(in_dense).lower(dense, numeric, emb).compile()
            _, row["in_dense_ms"] = _time(step, (dense, numeric, emb), args.steps)
            row["in_dense_temp_gib"] = (
                step.memory_analysis().temp_size_in_bytes / 2**30
            )
        except Exception as err:  # a form the chip refuses is a reading
            row["refused"] = str(err).splitlines()[0][:200]
        out[name] = row
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/interact_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Time FiBiNET's gate-and-bilinear block (forward and backward, alone) on the
chip in the forms it could take, by default at the geometry of
``fibinet_tb.train_packed``: what chose ``blocks.bilinear_pairs``' writing and
``blocks.BILINEAR_WHOLE_BYTES`` (PERF.md section 6).

    chiprun -- python scripts/probe_bilinear.py [--slices 1024,4096]
        [--geometry B,m,D,r]

The forms, each of ``c = [pairs(P, e) ; pairs(Q, a e)]`` with the SENET gates
``a`` from ``blocks.senet_gates``:

* ``shipped.S<rows>``: ``blocks.senet_bilinear`` whole (``S<B>``) and in
  slices of the batch;
* ``kept``: the whole block WITHOUT its ``jax.checkpoint``, the towers' left
  products kept for the backward (faster alone, slower in the cell's step:
  ``blocks.senet_bilinear``; PR 52's first call read it under the name
  ``by_field``);
* ``block``: one ``[B, m D] x [m D, P D]`` product a tower, the pair matrices
  laid in the blocks of a matrix that is zero elsewhere (the MXU does 40 times
  the multiplies and is fed whole tiles);
* ``pairs``: the equation as written, ``einsum("bpd,pde->bpe", e[:, i], W) *
  e[:, j]`` (the plain reference's form: ``[B, P, D]`` arrays).

Prints one JSON object (ms a step and the program's temporaries by form, each
form's largest difference from the ``pairs`` form in c, in the tower's
gradient and in ``bil_q``'s) and writes it to ``chiprun_out/bilinear_probe.json``.
One row in 200 lacks a field (as a dropped entry leaves it), and every row
lacks the last (the 40th bucket of 39 fields).  Exit 1 without a TPU: a CPU
run times nothing worth writing down."""

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

GEOMETRY = "16384,40,10,3"  # B, max_fields, emb_dim, senet_reduction


def pairs_form(w: jax.Array, tower: jax.Array) -> jax.Array:
    """The equation as written: a pair's two fields picked, [B, P, D] arrays."""
    i, j = np.triu_indices(tower.shape[1], 1)
    left = jnp.einsum(
        "bpd,pde->bpe", tower[:, i], w, precision=jax.lax.Precision.HIGHEST
    )
    return (left * tower[:, j]).reshape(tower.shape[0], -1)


def block_form(w: jax.Array, tower: jax.Array) -> jax.Array:
    """One product a tower with the pair matrices laid in blocks."""
    b, m, d = tower.shape
    flat = tower.reshape(b, m * d)
    first = np.zeros((m, w.shape[0]), np.float32)
    first[np.triu_indices(m, 1)[0], np.arange(w.shape[0])] = 1.0
    full = (first[:, None, :, None] * w.transpose(1, 0, 2)[None]).reshape(m * d, -1)
    right = jnp.concatenate([flat[:, (k + 1) * d:] for k in range(m - 1)], axis=-1)
    return blocks.dense_dot(flat, full) * right


def gated(pairs_of):
    """``c`` of a form that turns (w, tower) into a tower's pairs."""
    def c_of(s1, s2, wp, wq, tower):
        v = blocks.senet_gates(s1, s2, tower)[..., None] * tower
        return jnp.concatenate([pairs_of(wp, tower), pairs_of(wq, v)], axis=-1)
    return c_of


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slices", default="1024,4096")
    ap.add_argument("--geometry", default=GEOMETRY)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args(argv)
    b, m, d, r = map(int, args.geometry.split(","))
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    pairs, squeezed = blocks.field_pairs(m), max(m // r, 1)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 7))
    weights = (
        jax.random.normal(next(keys), (m, squeezed), jnp.float32) * np.sqrt(2.0 / m),
        jax.random.normal(next(keys), (squeezed, m), jnp.float32) * np.sqrt(2.0 / squeezed),
        jax.random.normal(next(keys), (pairs, d, d), jnp.float32) * np.sqrt(1.0 / d),
        jax.random.normal(next(keys), (pairs, d, d), jnp.float32) * np.sqrt(1.0 / d),
    )
    present = (jax.random.uniform(next(keys), (b, m)) > 0.005).astype(jnp.float32)
    present = present.at[:, -1].set(0.0)
    tower = jax.random.normal(next(keys), (b, m, d), jnp.float32) * present[..., None]
    ct = jax.random.normal(next(keys), (b, 2 * pairs * d), jnp.float32)

    forms = {
        "pairs": gated(pairs_form),
        **{
            f"shipped.S{s}": (lambda *a, s=s: blocks.senet_bilinear(*a, s))
            for s in [b, *map(int, args.slices.split(","))]
        },
        "kept": gated(blocks.bilinear_pairs),
        "block": gated(block_form),
    }
    out: dict = {"device": jax.devices()[0].device_kind, "geometry": [b, m, d, r]}
    first = None
    for name, form in forms.items():
        def both(ws, t, form=form):
            c, vjp = jax.vjp(lambda ws, t: form(*ws, t), ws, t)
            return c, vjp(ct)

        try:
            step = jax.jit(both).lower(weights, tower).compile()
            got = jax.block_until_ready(step(weights, tower))
        except Exception as err:  # a form the chip's memory refuses is a reading
            out[name] = {"refused": str(err).splitlines()[0][:200]}
            continue
        start = time.perf_counter()
        for _ in range(args.steps):
            got = step(weights, tower)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - start) / args.steps * 1e3
        c, ((_, _, _, d_q), d_tower) = got
        first = first or (c, d_tower, d_q)
        out[name] = {
            "ms": ms,
            "temp_gib": step.memory_analysis().temp_size_in_bytes / 2**30,
            **{
                f"{what}_off_pairs": float(
                    jnp.max(jnp.abs(a - ref)) / jnp.max(jnp.abs(ref))
                )
                for what, a, ref in zip(("c", "d_tower", "d_bil_q"), (c, d_tower, d_q), first)
            },
        }
        del got, c, d_tower, d_q
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bilinear_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

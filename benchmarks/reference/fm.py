"""2-way factorization machine as the reference computes it
(``src/model/fm/fm_worker.cc``).

Forward (fm_worker.cc:63-86), WITHOUT the usual factor 1/2 on the pair term:

    logit = sum_i w_i x_i + sum_d [ (sum_i v_id x_i)^2 - sum_i (v_id x_i)^2 ]

Backward (fm_worker.cc:140-142), explicit, and the gradient of the
1/2-scaled forward: d/dw_i = x_i, d/dv_id = (sum_j v_jd x_j - v_id x_i) x_i.
The two disagree by a factor of two on the pair term; that is the reference,
so the gradient is written out and not derived.
"""

from __future__ import annotations

import jax.numpy as jnp

V_DIM = 10  # ftrl.h:16
TABLES = {"w": 1, "v": V_DIM}


def logit(rows: dict, x):
    vx = rows["v"] * x[..., None]  # [B, K, D]
    pair = jnp.sum(vx, axis=1) ** 2 - jnp.sum(vx * vx, axis=1)  # [B, D]
    return jnp.sum(rows["w"][..., 0] * x, axis=-1) + jnp.sum(pair, axis=-1)


def grad_logit(rows: dict, x) -> dict:
    vx = rows["v"] * x[..., None]
    sum_vx = jnp.sum(vx, axis=1, keepdims=True)  # [B, 1, D]
    return {"w": x[..., None], "v": (sum_vx - vx) * x[..., None]}

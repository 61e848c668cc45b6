"""Deep & Cross Network (Wang et al., "Deep & Cross Network for Ad Click
Predictions", ADKDD 2017) as the program defines its family
(``models/dcn.py``): the embedding tower of ``reference/wide_deep.py`` under
an explicit cross stack beside one hidden layer, and a sparse linear term.

    x_0     = flatten(tower)                            [P = F * E]
    x_{l+1} = x_0 (x_l . w_l) + b_l + x_l               l = 0 .. L - 1
    h       = ReLU(x_0 W1 + b1)                         [P] -> [H]
    logit   = sum_i w_i x_i  +  [x_L ; h] W_out + b_out

``w`` and ``emb`` are rows of hashed tables under FTRL; ``cross_w [L, P],
cross_b [L, P], w1, b1, w_out [P + H, 1], b_out`` are dense replicated
parameters under plain SGD (``reference/ftrl.py``: the ``DENSE`` protocol).
Widths and depth are read off the arrays.  The program's family as it
stands, not a published configuration: no cell runs it yet.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.wide_deep import EMB_DIM, relu, tower

TABLES = {"w": 1, "emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["w"] [B, K, 1], rows["emb"] [B, K, E] gathered rows -> [B]."""
    wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
    x0 = tower(rows["emb"], x, slots, num_fields)
    xl = x0
    for w_l, b_l in zip(dense["cross_w"], dense["cross_b"]):
        xl = x0 * jnp.sum(xl * w_l, axis=-1, keepdims=True) + b_l + xl
    h = relu(x0 @ dense["w1"] + dense["b1"])
    out = jnp.concatenate([xl, h], axis=-1) @ dense["w_out"] + dense["b_out"]
    return wide + out[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The ``[B, k] x [k, n]`` products of one forward pass, from the dense
    arrays' shapes: the hidden layer, the output and a dot with ``w_l`` a
    cross layer."""
    layers, p = shapes["cross_w"]
    return [tuple(shapes["w1"]), tuple(shapes["w_out"])] + [(p, 1)] * layers

"""xDeepFM as its paper runs it on the Criteo Display Ads data (Lian, Zhou,
Zhang, Chen, Xie, Sun, "xDeepFM: Combining Explicit and Implicit Feature
Interactions for Recommender Systems", KDD 2018, arXiv:1803.05170, section
4.1): a Compressed Interaction Network beside a stack of ReLU layers over one
embedding tower, the two concatenated into one logit.  With ``X^0 [m, D]`` the
field tower (m fields, embeddings of D) and ``H_0 = m``:

    X^k[h, :] = sum_{i < H_{k-1}} sum_{j < m} W^k[h, i, j] (X^{k-1}[i, :] * X^0[j, :])
    p^k[h]    = sum_d X^k[h, d]                 p+ = [p^1 ; ... ; p^L]
    h_n       = ReLU(h_{n-1} W_n + b_n),  h_0 = flatten(X^0)
    logit     = sum_i w_i x_i  +  [p+ ; h_n] w_out + b_out

This is the paper's equations 6-8 with the identity in place of an activation
on the maps (its section 4.4 reports that best) and every map of every layer
pooled into the output (no split of a layer into a kept and a passed-on
half).  The paper's Criteo setting is D = 10, L = 3 layers of 200 maps beside
n = 2 layers of 400; the benchmark's configuration
(``configs/xdeepfm_ftrl_criteo_tb.json``) has m = 40 for the rows' 39 fields.

``w`` and ``emb`` are rows of hashed tables under FTRL; ``cin_w1 .. cin_wL``
(``[H_k, H_{k-1}, m]``), ``w1 [m * D, H], b1, ... wn [H, H], bn``, ``w_out
[L * H_k + H, 1]`` and ``b_out`` are dense replicated parameters under plain
SGD (``reference/ftrl.py``: the ``DENSE`` protocol, gradients by ``jax.vjp``
of this definition).  Depths and the dense widths are read off the arrays;
``TABLES`` states the tables' widths, for the byte counts and for the check
that holds the program's tables to them.

A layer is one ``einsum`` over a block of ``DENSE_BLOCK`` examples; its pair
tensor (``H_{k-1} * m * D`` floats an example, 1.31 GB a block at the paper's
sizes) is what the backward would keep for every layer at once, so each layer
is wrapped in ``jax.checkpoint``: the same mathematics, its pairs multiplied
again in the backward, so that the step fits beside a live trainer.

Departures from the paper, the program's (``xflow_tpu/models/xdeepfm.py``)
and this file's alike:

* the sparse linear term ``sum_i w_i x_i`` is over the hashed table ``w``;
* FTRL for the tables and plain SGD for the dense arrays, where the paper
  runs Adam at 1e-3 and batch 4096;
* no L2 penalty (the paper: 1e-4) and no dropout;
* the 13 integer fields are bucketed and embedded like the 26 categorical
  ones (the wires ship binary values);
* ``num_fields`` may count a bucket more than the rows have fields (40 for
  39): that row of ``X^0`` is zero, and no gradient reaches its weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.wide_deep import relu, tower

EMB_DIM = 10  # the paper's D on Criteo
TABLES = {"w": 1, "emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def depth(dense: dict, prefix: str) -> int:
    """How many arrays ``<prefix>1 .. <prefix>n`` the pytree holds."""
    n = 0
    while f"{prefix}{n + 1}" in dense:
        n += 1
    return n


@jax.checkpoint
def cin_layer(w, xk, x0):
    """w [H', H, m], xk [B, H, D], x0 [B, m, D] -> [B, H', D]."""
    return jnp.einsum("hij,bid,bjd->bhd", w, xk, x0)


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["w"] [B, K, 1], rows["emb"] [B, K, D] gathered rows -> [B]."""
    wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
    flat = tower(rows["emb"], x, slots, num_fields)  # [B, m * D]
    x0 = flat.reshape(x.shape[0], num_fields, -1)
    xk, pooled = x0, []
    for k in range(1, depth(dense, "cin_w") + 1):
        xk = cin_layer(dense[f"cin_w{k}"], xk, x0)
        pooled.append(jnp.sum(xk, axis=-1))
    h = flat
    for n in range(1, depth(dense, "w") + 1):
        h = relu(h @ dense[f"w{n}"] + dense[f"b{n}"])
    out = jnp.concatenate([*pooled, h], axis=-1) @ dense["w_out"] + dense["b_out"]
    return wide + out[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The products of one forward pass, from the dense arrays' shapes.  The
    protocol is ``(k, n)`` of a ``[B, k] x [k, n]`` product; a CIN layer is
    ``[B * D, H_{k-1} * m] x [H_{k-1} * m, H_k]``, ``B * D`` rows, so it is
    declared as ``(D * H_{k-1} * m, H_k)``: the same ``2 B k n``.  D is the
    first hidden layer's fan-in over m."""
    cin = [shapes[f"cin_w{k}"] for k in range(1, depth(shapes, "cin_w") + 1)]
    d = shapes["w1"][0] // cin[0][2]
    hidden = [tuple(shapes[f"w{n}"]) for n in range(1, depth(shapes, "w") + 1)]
    return (
        [(d * h_in * m, h_out) for h_out, h_in, m in cin]
        + hidden + [tuple(shapes["w_out"])]
    )

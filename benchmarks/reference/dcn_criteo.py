"""Deep & Cross Network as its paper runs it on the Criteo Display Ads data
(Wang, Fu, Fu, Wang, "Deep & Cross Network for Ad Click Predictions", ADKDD
2017, arXiv:1708.05123, section 4): an embedding tower under a stack of cross
layers beside a stack of ReLU layers, the two concatenated into one logit.

    x_0     = flatten(tower)                            [P = F * E]
    x_{l+1} = x_0 (x_l . w_l) + b_l + x_l               l = 0 .. L - 1
    h_k     = ReLU(h_{k-1} W_k + b_k),  h_0 = x_0       k = 1 .. n
    logit   = sum_i w_i x_i  +  [x_L ; h_n] W_out + b_out

The cross layer is the paper's vector form (its equation 1 with the weight a
vector, so ``x_l . w_l`` is a scalar a row).  The paper's optimum on Criteo is
L = 6 beside n = 2 layers of 1024 over 1026 inputs; the benchmark's
configuration (``configs/dcn_ftrl_criteo_tb.json``) has P = 40 x 26 = 1040.

``w`` and ``emb`` are rows of hashed tables under FTRL; ``cross_w [L, P],
cross_b [L, P], w1 [P, H], b1, ... wn [H, H], bn, w_out [P + H, 1], b_out``
are dense replicated parameters under plain SGD (``reference/ftrl.py``: the
``DENSE`` protocol, gradients by ``jax.vjp`` of this definition).  Depth and
the dense widths are read off the arrays; ``TABLES`` states the tables'
widths, for the byte counts and for the check that holds the program's
tables to them.

Departures from the paper, the program's (``xflow_tpu/models/dcn.py``) and
this file's alike:

* a sparse linear term ``sum_i w_i x_i`` over a table ``w``: the program's
  family carries it, the paper's model has none;
* no batch normalisation in the deep half and no gradient clipping: the dense
  pytree holds SGD parameters only, no running statistics;
* FTRL for the tables and plain SGD for the dense parameters, where the paper
  runs Adam at batch 512;
* the 13 integer fields are bucketed and embedded like the 26 categorical
  ones (the wires ship binary values), where the paper feeds them as
  log-transformed reals beside the embeddings;
* one embedding width for every field, where the paper gives a field of
  cardinality c a width of 6 c^(1/4).
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.reference.wide_deep import relu, tower

EMB_DIM = 26  # 39 fields x 26 = 1014 of the paper's 1026 inputs
TABLES = {"w": 1, "emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def deep_layers(dense: dict) -> int:
    """How many hidden layers ``w1 .. wn`` the pytree holds."""
    n = 0
    while f"w{n + 1}" in dense:
        n += 1
    return n


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["w"] [B, K, 1], rows["emb"] [B, K, E] gathered rows -> [B]."""
    wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
    x0 = tower(rows["emb"], x, slots, num_fields)
    xl = x0
    for w_l, b_l in zip(dense["cross_w"], dense["cross_b"]):
        xl = x0 * jnp.sum(xl * w_l, axis=-1, keepdims=True) + b_l + xl
    h = x0
    for k in range(1, deep_layers(dense) + 1):
        h = relu(h @ dense[f"w{k}"] + dense[f"b{k}"])
    out = jnp.concatenate([xl, h], axis=-1) @ dense["w_out"] + dense["b_out"]
    return wide + out[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The ``[B, k] x [k, n]`` products of one forward pass, from the dense
    arrays' shapes: every hidden layer, the output, and a dot with ``w_l`` a
    cross layer."""
    layers, p = shapes["cross_w"]
    hidden = [tuple(shapes[f"w{k}"]) for k in range(1, deep_layers(shapes) + 1)]
    return hidden + [tuple(shapes["w_out"])] + [(p, 1)] * layers

"""FiBiNET as its paper runs it on the Criteo Display Ads data (Huang, Zhang,
Zhang, "FiBiNET: Combining Feature Importance and Bilinear feature Interaction
for Click-Through Rate Prediction", RecSys 2019, arXiv:1905.09433, sections
3.2-3.5 and 4.1.4): a Squeeze-and-Excitation gate a field, a bilinear product
a pair of fields on the embeddings and on the gated embeddings, and a stack of
ReLU layers over the two.  With ``e_i [D]`` the sum of field i's embeddings, m
fields, r the reduction ratio:

    z_i   = mean_d e_i[d]                                   (squeeze, mean pooling)
    a     = ReLU(ReLU(z S1) S2)      S1 [m, m // r], S2 [m // r, m], no bias
    v_i   = a_i e_i                                         (re-weight)
    p_ij  = (e_i P_ij) * e_j ,  q_ij = (v_i Q_ij) * v_j     i < j,  P_ij, Q_ij [D, D]
    c     = [p_ij ; q_ij]  over all pairs                   [2 m (m - 1) / 2 D]
    h_n   = ReLU(h_{n-1} W_n + b_n),  h_0 = c
    logit = sum_k w_k x_k  +  h_n w_out + b_out

(the paper's equations 5-9: Field-Interaction, its third bilinear type, on
both towers).  The paper's Criteo setting is D = 10, r = 3 and n = 3 layers of
400; the benchmark's configuration (``configs/fibinet_ftrl_criteo_tb.json``)
has m = 40 for the rows' 39 fields: 780 pairs, c 15 600 wide.

``w`` and ``emb`` are rows of hashed tables under FTRL; ``senet_w1 [m, m //
r]``, ``senet_w2 [m // r, m]``, ``bil_p`` and ``bil_q`` (``[P, D, D]``, the
pairs ``i < j`` in lexicographic order: (0, 1), (0, 2) .. (m - 2, m - 1)),
``w1 [2 P D, H], b1, .. wn [H, H], bn``, ``w_out [H, 1]`` and ``b_out`` are
dense replicated parameters under plain SGD (``reference/ftrl.py``: the
``DENSE`` protocol, gradients by ``jax.vjp`` of this definition).  Depth and
the widths are read off the arrays; ``TABLES`` states the tables' widths.

A tower's pairs are one ``einsum`` over a block of ``DENSE_BLOCK`` examples,
wrapped in ``jax.checkpoint`` (the same mathematics, a pair's two picked
fields made again in the backward) so that the step fits beside a live
trainer.  Every ReLU, the excitation's two as well, is
``reference/wide_deep.py::relu`` and stands outside that wrapper.

Departures from the paper, the program's (``xflow_tpu/models/fibinet.py``) and
this file's alike:

* the sparse linear term ``sum_k w_k x_k`` is over the hashed table ``w``;
* FTRL for the tables and plain SGD for the dense arrays, where the paper runs
  Adam at 1e-4 and batch 1 000; no dropout (the paper: 0.5);
* the 13 integer fields are bucketed and embedded like the 26 categorical
  ones (the wires ship binary values);
* ``num_fields`` may count a bucket more than the rows have fields (40 for
  39);
* a field a row has no entry of (that bucket always; a field whose entry was
  dropped, value 0) is a zero vector: its squeeze is 0, its gate multiplies
  nothing and its pairs are 0 on both towers, so no gradient reaches their
  matrices or their rows of ``w1``, and no presence mask enters.  On a row
  with every field this is the paper's layer exactly;
* which combination of bilinear types the paper's Criteo table found best is
  not recalled: Field-Interaction on both towers is the public
  implementations' default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.wide_deep import relu, tower

EMB_DIM = 10  # the paper's embedding size on Criteo
TABLES = {"w": 1, "emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def depth(dense: dict) -> int:
    """How many hidden layers ``w1 .. wn`` the pytree holds."""
    n = 0
    while f"w{n + 1}" in dense:
        n += 1
    return n


@jax.checkpoint
def pairs(w, e):
    """w [P, D, D], e [B, m, D] -> [B, P * D]: ``(e_i W_ij) * e_j`` for every
    ``i < j``, lexicographic."""
    i, j = np.triu_indices(e.shape[1], 1)
    return (jnp.einsum("bpd,pde->bpe", e[:, i], w) * e[:, j]).reshape(e.shape[0], -1)


def gates(s1, s2, e):
    """e [B, m, D] -> [B, m]: the squeeze and the excitation."""
    return relu(relu(jnp.mean(e, axis=-1) @ s1) @ s2)


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["w"] [B, K, 1], rows["emb"] [B, K, D] gathered rows -> [B]."""
    wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
    e = tower(rows["emb"], x, slots, num_fields).reshape(x.shape[0], num_fields, -1)
    v = gates(dense["senet_w1"], dense["senet_w2"], e)[..., None] * e
    h = jnp.concatenate([pairs(dense["bil_p"], e), pairs(dense["bil_q"], v)], axis=-1)
    for n in range(1, depth(dense) + 1):
        h = relu(h @ dense[f"w{n}"] + dense[f"b{n}"])
    return wide + (h @ dense["w_out"] + dense["b_out"])[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The ``[B, k] x [k, n]`` products of one forward pass that the roofline's
    operation count takes from here: the hidden layers and the output product.
    The block's own products (a pair's ``[D] x [D, D]`` on two towers, the
    excitation's two: 157 040 multiply-adds an example at the paper's sizes
    beside these 6 560 400) are counted where their scope is read
    (``layer_metrics/bilinear_mxu_roofline.py``)."""
    hidden = [tuple(shapes[f"w{n}"]) for n in range(1, depth(shapes) + 1)]
    return hidden + [tuple(shapes["w_out"])]

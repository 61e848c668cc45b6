"""DLRM in its Criteo-Terabyte setting (Naumov et al., "Deep Learning
Recommendation Model for Personalization and Recommendation Systems",
arXiv:1906.00091; ``facebookresearch/dlrm`` ``bench/dlrm_s_criteo_terabyte.sh``:
``--arch-sparse-feature-size=128 --arch-mlp-bot=13-512-256-128
--arch-mlp-top=1024-1024-512-256-1``, interaction ``dot`` without
self-interaction, ``--loss-function=bce``; the network of MLPerf Training's
recommendation benchmark).  With ``x_f`` the value of numeric field f = 1..13
(the script's ``log(1 + count)``, here the value the row's token holds), ``E``
the table and m = 26 categorical fields:

    h_0 = x ;  h_l = ReLU(h_{l-1} A_l + a_l)        13 -> 512 -> 256 -> 128 ;  z = h_3
    e_j = sum_{k in field j} E[key_k] x_k            j = 1..26, e_j in R^128
    T   = [z ; e_1 ; ... ; e_26]  in R^{27 x 128} ;  Z = T T^T
    r   = [z ; Z_ij for i > j]  in R^{128 + 351}
    g_0 = r ;  g_l = ReLU(g_{l-1} C_l + c_l)        479 -> 1024 -> 1024 -> 512 -> 256
    logit = g_4 c_out + b_out

(ReLU on the bottom stack's last layer too, as the script's.)

``emb`` is rows of ONE hashed table under FTRL; ``bot_w1, bot_b1 ..``, ``top_w1,
top_b1 ..``, ``w_out``, ``b_out`` are dense replicated parameters under plain SGD
(``reference/ftrl.py``: the ``DENSE`` protocol, gradients by ``jax.vjp`` of this
definition).  Depths and widths are read off the arrays: the numeric fields
are ``bot_w1``'s rows (ids ``0 .. 12``), the interacting vectors ``num_fields``
less them (z, which stands as the last field ``num_fields - 1``, and the
categorical fields ``13 .. num_fields - 2``; the benchmark's configuration has
``num_fields`` 40 for the rows' 39 fields).  ``TABLES`` states the table's width.

An entry of a numeric field is a table entry like any other: its row is
among the gathered rows and takes gradient 0 here as in the program, and its
VALUE is what the model reads.  A row without a numeric field reads 0 there;
a categorical field a row has no entry of is a zero vector and its dots are 0.

Every ReLU is ``reference/wide_deep.py::relu``, in no ``jax.checkpoint``.

Departures from the script, the program's (``xflow_tpu/models/dlrm.py``) and
this file's alike:

* FTRL for the table where the script runs SGD; plain SGD for the dense
  arrays IS the script's optimizer;
* ONE hashed table for the script's 26;
* a field's vector is the sum of its entries' rows times their values (the
  script's bag sum; its data has one id a field);
* the values arrive transformed: the script applies ``log(1 + x)`` itself;
* ``num_fields`` counts one bucket more than the rows have fields, which z
  takes; an entry of that field or beyond is ignored.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.reference.wide_deep import relu, tower

EMB_DIM = 128  # the script's --arch-sparse-feature-size
TABLES = {"emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def depth(dense: dict, prefix: str) -> int:
    """How many layers ``<prefix>w1 .. <prefix>wL`` the pytree holds."""
    n = 0
    while f"{prefix}w{n + 1}" in dense:
        n += 1
    return n


def stack(dense: dict, prefix: str, h):
    """``h <- ReLU(h W_l + b_l)`` over the layers of ``prefix``."""
    for n in range(1, depth(dense, prefix) + 1):
        h = relu(h @ dense[f"{prefix}w{n}"] + dense[f"{prefix}b{n}"])
    return h


def numeric_values(x, slots, fields: int):
    """x [B, K], slots [B, K] -> [B, fields]: the value of each row's entry
    of the numeric fields ``0 .. fields - 1`` (summed where a row has more
    than one; 0 where it has none)."""
    inside = (slots >= 0) & (slots < fields)
    row = jnp.arange(x.shape[0])[:, None]
    sums = jnp.zeros((x.shape[0], fields), x.dtype)
    return sums.at[row, jnp.where(inside, slots, 0)].add(jnp.where(inside, x, 0.0))


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["emb"] [B, K, 128] gathered rows -> [B]."""
    numeric = dense["bot_w1"].shape[0]
    z = stack(dense, "bot_", numeric_values(x, slots, numeric))
    e = tower(rows["emb"], x, slots, num_fields).reshape(x.shape[0], num_fields, -1)
    t = jnp.concatenate([z[:, None, :], e[:, numeric:num_fields - 1]], axis=1)
    products = jnp.einsum("bid,bjd->bij", t, t)
    i, j = np.tril_indices(t.shape[1], -1)  # the pairs i > j, row by row
    g = stack(dense, "top_", jnp.concatenate([z, products[:, i, j]], axis=-1))
    return (g @ dense["w_out"] + dense["b_out"])[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The ``[B, k] x [k, n]`` products of one forward pass with a dense
    parameter, from the arrays' shapes: the bottom stack's, the top stack's
    and the output's.  The pairs' dots, products of two activations of one
    example, are counted by ``layer_metrics/interact_mxu_roofline.py``."""
    return [
        tuple(shapes[f"{prefix}w{n}"])
        for prefix in ("bot_", "top_")
        for n in range(1, depth(shapes, prefix) + 1)
    ] + [tuple(shapes["w_out"])]

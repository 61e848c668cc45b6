"""AutoInt as its paper runs it on the Criteo Display Ads data (Song, Shi,
Xiao, Duan, Xu, Zhang, Tang, "AutoInt: Automatic Feature Interaction Learning
via Self-Attentive Neural Networks", CIKM 2019, arXiv:1810.11921, sections
4.3-4.5 and 5.1.4): a stack of interacting layers, multi-head self-attention
over the fields of one row, and a linear output over the fields' vectors.
With ``e_m`` the m-th field's vector entering layer l (``d_1 = 16``, ``d_l = H
d'`` after), heads h = 1..H:

    psi^h(m, k)   = <W_Q^h e_m, W_K^h e_k>                        (eq. 5: no 1 / sqrt(d'))
    alpha^h(m, k) = exp psi^h(m, k) / sum_{l present} exp psi^h(m, l)
    e~_m^h        = sum_{k present} alpha^h(m, k) W_V^h e_k
    e_m^Res       = ReLU([e~_m^1 ; ... ; e~_m^H] + W_Res e_m)
    logit         = w_out^T [e_1^Res ; ... ; e_M^Res] + b_out      after L layers

The paper's Criteo setting is d = 16, L = 3, H = 2 heads of d' = 32; the
benchmark's configuration (``configs/autoint_ftrl_criteo_tb.json``) has M = 40
for the rows' 39 fields.  This is the plain AutoInt, not AutoInt+.

``emb`` is rows of ONE hashed table under FTRL (no first-order term);
``attn_q1 .. attn_qL``, ``attn_k*``, ``attn_v*``, ``attn_r*`` (``[d_l, H d']``,
head h the columns ``h d' .. (h + 1) d'``), ``w_out [M H d', 1]`` and ``b_out``
are dense replicated parameters under plain SGD (``reference/ftrl.py``: the
``DENSE`` protocol, gradients by ``jax.vjp`` of this definition).  Depth and
the widths are read off the arrays; ``HEADS`` is the paper's, as ``TABLES``
states the table's width: a ``[d_l, H d']`` array does not say where a head
ends.

A layer runs over a block of ``DENSE_BLOCK`` examples and is wrapped in
``jax.checkpoint`` (the same mathematics, its projections and scores computed
again in the backward) so that the step fits beside a live trainer.  The
wrapper ends at the ReLU's argument: ``relu`` itself stays outside it, because
what it hands the check (``wide_deep.relu_arguments``) has to leave the trace
it was made in, and a ``[block, M, H d']`` argument kept for the backward is
42 MB a layer.

Departures from the paper, the program's (``xflow_tpu/models/autoint.py``) and
this file's alike:

* FTRL for the table and plain SGD for the dense arrays, where the paper runs
  Adam; no dropout (the paper uses none on Criteo);
* the 13 integer fields are bucketed and embedded like the 26 categorical ones
  (the paper multiplies a field's vector by the logged value; the wires ship
  binary values);
* ``num_fields`` may count a bucket more than the rows have fields (40 for 39);
* a field a row has no entry of (that bucket always; a field whose entry was
  dropped, value 0) is neither attended to nor attending: the softmax runs over
  the present fields and the absent field's ``e^Res`` is 0, so its slice of
  ``w_out`` sees no gradient.  On a row with every field this is the paper's
  layer exactly;
* the paper's equations, not its released code (which, from memory, puts a ReLU
  on the projections).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.wide_deep import relu, tower

EMB_DIM = 16  # the paper's d on Criteo
HEADS = 2  # the paper's H on Criteo
TABLES = {"emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def depth(dense: dict) -> int:
    """How many layers ``attn_q1 .. attn_qL`` the pytree holds."""
    n = 0
    while f"attn_q{n + 1}" in dense:
        n += 1
    return n


def presence(x, slots, num_fields: int):
    """Bool [B, F]: the row has an entry of the field (value not 0, field id
    inside ``[0, num_fields)``)."""
    inside = (slots >= 0) & (slots < num_fields) & (x != 0)
    row = jnp.arange(x.shape[0])[:, None]
    have = jnp.zeros((x.shape[0], num_fields), bool)
    return have.at[row, jnp.where(inside, slots, 0)].max(inside)


@jax.checkpoint
def mixed_fields(wq, wk, wv, wr, e, present):
    """e [B, M, d_l], present bool [B, M] -> [B, M, H d'], the weighted sums
    plus the residual: the ReLU's argument."""
    b, m, _ = e.shape

    def heads(a):
        return a.reshape(b, m, HEADS, -1)

    q, k, v = heads(e @ wq), heads(e @ wk), heads(e @ wv)
    psi = jnp.einsum("bmhc,bkhc->bhmk", q, k)
    alpha = jax.nn.softmax(psi, axis=-1, where=present[:, None, None, :])
    mixed = jnp.einsum("bhmk,bkhc->bmhc", alpha, v).reshape(b, m, -1)
    return mixed + e @ wr


def layer(wq, wk, wv, wr, e, present):
    """e [B, M, d_l], present bool [B, M] -> [B, M, H d']."""
    return relu(mixed_fields(wq, wk, wv, wr, e, present), present[..., None])


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["emb"] [B, K, d] gathered rows -> [B]."""
    e = tower(rows["emb"], x, slots, num_fields).reshape(x.shape[0], num_fields, -1)
    present = presence(x, slots, num_fields)
    for n in range(1, depth(dense) + 1):
        e = layer(*(dense[f"attn_{p}{n}"] for p in "qkvr"), e, present)
    out = e.reshape(x.shape[0], -1) @ dense["w_out"] + dense["b_out"]
    return out[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The products of one forward pass, from the dense arrays' shapes.  The
    protocol is ``(k, n)`` of a ``[B, k] x [k, n]`` product, ``2 B k n``
    operations.  A projection is ``[B M, d_l] x [d_l, H d']``, declared ``(M
    d_l, H d')``; the scores ``[M, d'] x [d', M]`` and the weighted sum ``[M,
    M] x [M, d']`` a head are products of two ACTIVATIONS of one example,
    declared ``(H M d', M)`` and ``(H M M, d')``: unlike a one-hot field
    contraction they multiply, so they count.  M is the output's fan-in over
    ``H d'``."""
    width = shapes["attn_q1"][1]
    m, head = shapes["w_out"][0] // width, width // HEADS
    out = []
    for n in range(1, depth(shapes) + 1):
        d_in = shapes[f"attn_q{n}"][0]
        out += [(m * d_in, width)] * 4 + [(HEADS * m * head, m), (HEADS * m * m, head)]
    return out + [tuple(shapes["w_out"])]

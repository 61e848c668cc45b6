"""Field-aware factorization machine as libffm defines it (Juan, Zhuang,
Chin, Lin, "Field-aware Factorization Machines for CTR Prediction", RecSys
2016; https://github.com/ycjuan/libffm): every feature keeps one latent
vector PER FIELD, and a pair of entries meets through the vector each keeps
for the other's field.

    logit = sum_i w_i x_i
          + sum_{i<j} < v[k_i, f_j, :], v[k_j, f_i, :] > x_i x_j

written out as that sum over pairs: nothing here aggregates by field, which
is the identity the system computes with (``blocks.ffm_field_interaction``).
A row of ``v`` is ``num_fields * V_DIM`` columns, field-major.  An entry whose
field is outside ``[0, num_fields)`` adds nothing to the pair term and has
gradient 0 there (its linear term stays, as in the system).  The gradient is
``jax.grad`` of the definition.

Three departures from libffm, all the system's own and stated by it:

* the linear table ``w``: libffm has the pair term alone; xLearn's FFM and
  the system's carry the linear term;
* no instance-wise normalisation (libffm scales a row by 1 / |x|^2): the
  compact and dictionary wires ship binary values (ROADMAP B-M);
* FTRL at ``ftrl.h``'s constants where libffm runs AdaGrad: the system's
  optimizers are FTRL and SGD, and ``reference/ftrl.py`` is the recurrence
  every family here is held to.

A ``[B, K, K, V_DIM]`` tensor of pairs is 26 M pairs of 4 numbers at the
benchmark's cell (B = 16384, K = 40), and the TPU pads a minor axis of 4 to
128: 13 GB.  So the pair sum, and its gradient, run over blocks of ``BLOCK``
rows (``jax.lax.map``; a row's logit reads that row's entries alone, so the
gradient of a block's summed logits is each row's own): the reference's step
then fits beside the live trainer (its peak is in PERF.md section 6, PR 34).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

V_DIM = 4  # libffm's -k default, and the paper's Criteo setting
TABLES = {"w": 1, "v": 160}  # 40 fields (39, rounded up to 8) x V_DIM
USES_FIELDS = True  # logit and grad_logit take (slots, num_fields)
BLOCK = 256  # rows whose pairs are held at once


def _pair_term(v, x, slots, num_fields: int):
    """v [R, K, F * V_DIM], x [R, K], slots [R, K] -> [R]: the sum over the
    pairs i < j of the row, both fields inside."""
    rows, k = x.shape
    inside = (slots >= 0) & (slots < num_fields)
    field = jnp.where(inside, slots, 0)
    v4 = v.reshape(rows, k, num_fields, V_DIM)
    # met[r, i, j, :] = v[k_i, f_j, :]: the vector entry i keeps for j's field
    met = jnp.take_along_axis(v4, field[:, None, :, None], axis=2)
    dots = jnp.sum(met * jnp.swapaxes(met, 1, 2), axis=-1)  # [R, K, K]
    first = jnp.arange(k)[:, None] < jnp.arange(k)[None, :]
    live = inside[:, :, None] & inside[:, None, :] & first
    return jnp.sum(
        jnp.where(live, dots * x[:, :, None] * x[:, None, :], 0.0), axis=(1, 2)
    )


def _blocks(*arrays):
    """Each [B, ...] array as [B / block, block, ...], the block the largest
    divisor of B that BLOCK allows."""
    block = math.gcd(arrays[0].shape[0], BLOCK)
    return tuple(a.reshape(-1, block, *a.shape[1:]) for a in arrays)


def logit(rows: dict, x, slots, num_fields: int):
    """rows["w"] [B, K, 1], rows["v"] [B, K, 160] gathered rows, x [B, K]
    values, slots [B, K] field ids -> [B]."""
    assert num_fields * V_DIM == TABLES["v"] == rows["v"].shape[-1]
    pair = jax.lax.map(
        lambda blk: _pair_term(*blk, num_fields), _blocks(rows["v"], x, slots)
    )
    return jnp.sum(rows["w"][..., 0] * x, axis=-1) + pair.reshape(-1)


def grad_logit(rows: dict, x, slots, num_fields: int) -> dict:
    """d logit / d each gathered entry: {"w": [B, K, 1], "v": [B, K, 160]}."""
    assert num_fields * V_DIM == TABLES["v"] == rows["v"].shape[-1]
    grad_v = jax.lax.map(
        lambda blk: jax.grad(
            lambda v: jnp.sum(_pair_term(v, *blk[1:], num_fields))
        )(blk[0]),
        _blocks(rows["v"], x, slots),
    )
    return {"w": x[..., None], "v": grad_v.reshape(rows["v"].shape)}

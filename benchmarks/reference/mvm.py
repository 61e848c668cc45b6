"""Multi-View Machine (reference ``src/model/mvm/mvm_worker.cc``): one table
``v``; a row's entries are grouped by field (view), and the logit is the sum
over factors of the product over fields of one plus the field's sum.

    s_fd   = sum over the entries i of the row with field f:  v_id * x_i
    logit  = sum_d [ prod_f (1 + s_fd)  -  1 ]
    d logit / d v_id = x_i * prod_f (1 + s_fd) / (1 + s_{f(i),d}),
                       0 where |1 + s_{f(i),d}| < 1e-12

An entry whose field is outside ``[0, num_fields)`` adds nothing to the logit
and has gradient 0 (upstream sizes its field arrays from the largest id it
sees, mvm_worker.cc:225-243; the system counts ``max_fields`` of them).

Two departures from upstream, both the system's own and stated by it:

* mvm_worker.cc:67-95 multiplies the BARE field sum in the forward, while
  :155-156 divides the product by ``1 + sum`` in the backward (and gives 0
  where that is 0).  The system and this reference use ``1 + sum`` on both
  sides: the MVM paper's constant-1 feature in every view, under which a
  field with no entry is a neutral factor 1.
* the ``- 1`` per factor, which upstream has not: without it the logit of
  freshly drawn rows is ``+V_DIM``.  It is a constant, so the gradients are
  those of the uncentred form.
"""

from __future__ import annotations

import jax.numpy as jnp

V_DIM = 10  # ftrl.h:16
TABLES = {"v": V_DIM}
USES_FIELDS = True  # logit and grad_logit take (slots, num_fields)
GUARD = 1e-12


def _by_field(rows: dict, x, slots, num_fields: int):
    """``inside`` [B, K]: the entry's field is one of the F; ``field`` [B, K]:
    that field (0 where it is none); ``one_plus`` [B, F, D]: 1 + s_fd, each
    inside entry's v_id * x_i added to its row's and field's sum."""
    inside = (slots >= 0) & (slots < num_fields)
    field = jnp.where(inside, slots, 0)
    vx = jnp.where(inside[..., None], rows["v"] * x[..., None], 0.0)  # [B, K, D]
    row = jnp.arange(x.shape[0])[:, None]
    sums = jnp.zeros((x.shape[0], num_fields, vx.shape[-1]), vx.dtype)
    return inside, field, 1.0 + sums.at[row, field].add(vx)


def logit(rows: dict, x, slots, num_fields: int):
    """rows["v"] [B, K, D] gathered rows, x [B, K] values, slots [B, K]
    field ids -> [B]."""
    _, _, one_plus = _by_field(rows, x, slots, num_fields)
    return jnp.sum(jnp.prod(one_plus, axis=1) - 1.0, axis=-1)


def grad_logit(rows: dict, x, slots, num_fields: int) -> dict:
    """d logit / d each gathered entry, [B, K, D]."""
    inside, field, one_plus = _by_field(rows, x, slots, num_fields)
    prod = jnp.prod(one_plus, axis=1)  # [B, D]
    own = jnp.take_along_axis(one_plus, field[..., None], axis=1)  # [B, K, D]
    live = inside[..., None] & (jnp.abs(own) >= GUARD)
    grad = jnp.where(live, prod[:, None, :] / jnp.where(live, own, 1.0), 0.0)
    return {"v": grad * x[..., None]}

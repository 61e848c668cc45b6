"""Static-shape sparse primitives: per-unique-key gradient consolidation
and gather/update/scatter row application.

This module is the TPU replacement for the ps-lite Push path.  In the
reference, a worker thread sorts the minibatch's (sid, fid) pairs,
uniques the keys (lr_worker.cc:147-166), pushes per-unique-key summed
gradients, and the server applies the optimizer recurrence per key
inside the request handler (ftrl.h:54-79).  Here the same dataflow runs
inside one XLA program with static shapes:

* ``consolidate`` replaces sort+unique: argsort the M flattened keys,
  mark segment starts, segment-sum gradients.  The output is M slots of
  which only the first U (U = number of unique keys) are real; the rest
  carry an out-of-range sentinel key so downstream scatters drop them.
* ``gather_rows`` / ``scatter_rows`` replace Pull / the server-side
  state mutation: gather optimizer state rows at the unique keys, apply
  the pure update, scatter the new rows back.  Out-of-range sentinel
  scatters are dropped (XLA scatter ``mode=drop``), so padding never
  touches the table.

Padding safety argument: a padded consolidation slot carries g=0 and a
sentinel key.  Its gathered row (clamped by XLA gather semantics) is
updated with g=0 — for FTRL that recomputes w from unchanged (z, n),
which is exactly what the reference server does on a zero-gradient push
(ftrl.h:58-74 runs unconditionally) — and then the write is dropped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def PAD_SENTINEL_FOR(table_size: int) -> int:
    """Key value used for padding entries: one past the last row, so
    gathers clamp and scatters drop."""
    return table_size


def consolidate(
    keys: jax.Array, grads: jax.Array, table_size: int
) -> tuple[jax.Array, jax.Array]:
    """Sum gradient contributions per unique key, statically shaped.

    Args:
      keys: int32 [M]; padding entries must already carry the sentinel
        ``table_size``.
      grads: float [M, D] per-occurrence gradients (0 for padding).
      table_size: number of real table rows.

    Returns:
      (ukeys [M] int32, gsum [M, D]): slot i holds the i-th unique key in
      sorted order with its summed gradient; unused slots hold the
      sentinel key and g=0.
    """
    order, seg, ukeys = consolidate_plan(keys, table_size)
    # Sentinel inputs (padding) form the last segment(s); their ukey is the
    # sentinel itself, so they stay inert.
    return ukeys, consolidate_apply(grads, order, seg)


def consolidate_plan(
    keys: jax.Array, table_size: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The key-only half of ``consolidate``, computed ONCE per batch and
    shared across a model's tables (they index with the same keys):
    returns (order [M], seg [M], ukeys [M]).  Apply per table with
    ``consolidate_apply``.

    For the touched-rows updates (TrainStep._sparse_update, the
    sequential-hot sparse window end, store/hot.py), which must apply
    the optimizer once per unique row.  Ahead of the dense step's
    scatter-add it loses: the chip pays a scatter per slot, live or
    dropped (docs/PERF.md "Cold consolidation")."""
    m = keys.shape[0]
    order = jnp.argsort(keys)
    sk = jnp.take(keys, order)
    is_start = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sk[1:] != sk[:-1]]
    )
    seg = jnp.cumsum(is_start) - 1
    sentinel = jnp.int32(table_size)
    ukeys = jnp.full((m,), sentinel, dtype=jnp.int32).at[seg].set(
        sk, mode="drop"
    )
    return order, seg, ukeys


def consolidate_apply(
    grads: jax.Array, order: jax.Array, seg: jax.Array
) -> jax.Array:
    """Per-table half of the shared consolidation: permute [M, D]
    gradients into key-sorted order and segment-sum; slot i of the
    result pairs with ``ukeys[i]`` from the plan (sentinel slots get
    g=0 because padding gradients are 0 and duplicates collapse into
    their segment head)."""
    sg = jnp.take(grads, order, axis=0)
    return jax.ops.segment_sum(sg, seg, num_segments=order.shape[0])


def gather_rows(table: jax.Array, ukeys: jax.Array) -> jax.Array:
    """Gather [U, D] state rows; sentinel keys clamp to the last row
    (their updates are dropped on scatter, see module docstring)."""
    return table.at[ukeys].get(mode="clip")


def scatter_rows(table: jax.Array, ukeys: jax.Array, rows: jax.Array) -> jax.Array:
    """Write updated rows back; sentinel (out-of-range) keys are dropped."""
    return table.at[ukeys].set(rows, mode="drop")

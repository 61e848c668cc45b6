"""Monotone window gathers: ``src[idx]`` without per-element addressing.

An XLA gather on this TPU generation costs a DMA descriptor per *slice*
(8.6 ns for one element of a stream, 8-15 ns for a 128-wide row: PERF.md
section 6, ROADMAP A4), so a gather of M one-element slices pays a row's
price for every element.  The
dictionary-wire decode (parallel/step.py::expand_dict_wire) only ever
gathers with indices that are running counts: non-decreasing, and
consecutive indices differ by 0 or 1.  For such an index stream the 128
outputs of one lane row read from ONE window of the source: positions
``[idx[0], idx[0] + 127]``.  So:

* view the outputs as rows of 128 lanes; row ``q`` is anchored at source
  row ``idx[128 q] // 128`` and needs that row and the next one;
* fetch the two rows as ONE slice of a ``[rows, 256]`` view of the source
  in which row ``r`` holds source rows ``r`` and ``r + 1`` (a row gather:
  one descriptor per 128 outputs instead of 128);
* pick each output inside its 256-wide window by ``idx - 128 * anchor``
  (``lane_select``): a within-row lane shuffle.

``lane_select`` is the only platform-sensitive piece.  On the TPU it is a
Pallas kernel around Mosaic's ``dynamic_gather`` (a one-vreg lane
shuffle: two shuffles and a select per 128 outputs); everywhere else it
is ``take_along_axis`` on the minor axis, which the CPU backend runs as
the plain gather it is good at.  PERF.md section 6 (PR 25) has the v5e
times of each form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def lane_select_xla(win: jax.Array, local: jax.Array) -> jax.Array:
    """``win[q, local[q, l]]``: [Q, 256] windows, [Q, 128] in-window
    positions in [0, 256)."""
    return jnp.take_along_axis(win, local, axis=1, mode="clip")


def _lane_select_kernel(win_ref, local_ref, out_ref):
    local = local_ref[...]
    lane = local & (LANES - 1)
    lo = jnp.take_along_axis(win_ref[:, :LANES], lane, axis=1)
    hi = jnp.take_along_axis(win_ref[:, LANES:], lane, axis=1)
    out_ref[...] = jnp.where(local < LANES, lo, hi)


def lane_select_tpu(
    win: jax.Array, local: jax.Array, block_rows: int = 512
) -> jax.Array:
    """lane_select_xla as a Mosaic kernel: each 128-lane half of the
    window is one vreg-wide ``dynamic_gather`` source."""
    from jax.experimental import pallas as pl

    q = win.shape[0]
    rows = min(block_rows, q)
    return pl.pallas_call(
        _lane_select_kernel,
        grid=(pl.cdiv(q, rows),),
        in_specs=[
            pl.BlockSpec((rows, 2 * LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, LANES), win.dtype),
    )(win, local)


def window_rows(src: jax.Array) -> jax.Array:
    """[n] -> [n // 128 + 1, 256]: row ``r`` is ``src[128 r : 128 r + 256]``
    (zeros past the end), so any window that starts in source row ``r``
    and is at most 129 long lies inside row ``r`` of the view."""
    n = src.shape[0]
    rows = n // LANES + 2
    two = jnp.pad(src, (0, rows * LANES - n)).reshape(rows, LANES)
    return jnp.concatenate([two[:-1], two[1:]], axis=1)


def monotone_take(
    idx: jax.Array, src: jax.Array, lane_select=lane_select_xla
) -> jax.Array:
    """``src[idx]`` for a non-decreasing int32 ``idx`` [M] whose
    consecutive entries differ by 0 or 1, with ``0 <= idx <= len(src)``
    (reading one past the end gives 0: a running count at a position
    that no entry occupies).  ``src`` is 1-D int32.  Two takes with one
    ``idx`` share its window arithmetic once compiled."""
    m = idx.shape[0]
    if src.shape[0] == 0:
        return jnp.zeros(m, src.dtype)
    q = -(-m // LANES)
    # repeating the last index keeps the stream monotone
    idx = jnp.pad(idx, (0, q * LANES - m), mode="edge").reshape(q, LANES)
    anchor = idx[:, 0] >> 7
    local = idx - (anchor << 7)[:, None]
    win = jnp.take(window_rows(src), anchor, axis=0, mode="clip")
    return lane_select(win, local).reshape(-1)[:m]


def wide_take(src: jax.Array, idx: jax.Array) -> jax.Array:
    """``src[idx]`` along axis 0 for any int32 ``idx`` into a
    batch-sized source ``[n]`` or ``[n, D]``, never as a gather of
    one-element slices: a single column rides beside a second one.
    On the v5e an index whose slice is ONE element pays 8.6 ns, one
    whose slice is a row of two to eleven 32-bit words 2.5-2.7 (PERF.md
    section 6, PR 30: 1 228 800 indices into 53 248 rows).  The
    compiler folds a column pick back into the gather, and a gather
    beside a constant column back into the element gather: so the
    second column counts the rows, and a barrier stands before the
    pick.  Out-of-range indices clip; an empty source gives zeros
    (like monotone_take: an index plane whose dictionary is empty has
    no real entry)."""
    if src.shape[0] == 0:
        return jnp.zeros(idx.shape + src.shape[1:], src.dtype)
    if src.ndim == 1:
        return wide_take(src[:, None], idx)[:, 0]
    if src.shape[1] > 1:
        return jnp.take(src, idx, axis=0, mode="clip")
    rows = jnp.arange(src.shape[0], dtype=src.dtype)[:, None]
    two = jnp.take(
        jnp.concatenate([src, rows], axis=1), idx, axis=0, mode="clip"
    )
    return jax.lax.optimization_barrier(two)[:, :1]

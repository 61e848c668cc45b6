"""The frequency-hot table head: rows [0, H) gathered and scattered apart
from the [T, D] table, by two-level one-hot matmuls on the MXU or, where
that is cheaper, by plain indexing of the [H, D] slice.

XLA TPU gather/scatter cost is per *slice* (~8-37 ns of DMA descriptor
issue each INTO THE WHOLE TABLE, independent of slice width —
docs/PERF.md), so a step over M = B*nnz feature occurrences pays that
per occurrence no matter what.  CTR key distributions are zipfian: after
the frequency remap (io/freq.py) the head of the distribution lives in
table rows [0, H).  Those occurrences never index the table: the callers
hand this module the head's own ``[H, D]`` slice.  Two exact forms read
and sum it:

    key = hi * h2 + lo            (H = h1 * h2)
    gather:  rows = ((onehot_hi @ W) . reshape  *  onehot_lo) sum over lo
    scatter: W'   = onehot_hi^T @ (g * onehot_lo)

the one-hot scans ("mxu"), and plain indexing of the slice ("seg"):
``w_hot[keys]`` and a scatter-add.  Which one runs:

  * ``hot_scatter``: a sum, so the forms differ in summation order
    and the price AND the benchmark's row check decide
    (``scatter_form``).  The scan costs 0.7-1.5 ns a slot and column
    (its product is at 84 % of its three-pass MXU bound at D = 26), the
    plain scatter-add 7.4-9.2 ns a slot at any width, one slot after
    the other.  The scan is the form of every D = 1 head and of D = 10
    (MVM's, FM's, xDeepFM's: a tie); from ``PLAIN_SCATTER_MIN_COLUMNS``
    columns up (AutoInt's 16, DCN's 26) ``"auto"`` adds plainly, a
    piece of the slots at a time.  Under the benchmark's own keys (at
    most one add a row and example) the plain sums stand 1.5e-6-4.2e-6
    of the largest from float64 where the scan's stand 0.5e-6-1.9e-6
    (scripts/probe_hot2.py; under a flat zipf draw, 10^6 adds into one
    row, ten times).  "seg" is also the CPU's form.
  * ``hot_gather``: a selection, bit for bit the same in both forms, so
    the price decides (``gather_form``): the scan costs 0.7 ns a slot
    AND COLUMN, a row out of the small slice 2-4.4 ns at any width.
    The scan is the D = 1 form (LR's, FM's and FFM's ``w``, the serving
    program); from ``PLAIN_GATHER_MIN_COLUMNS`` columns up ``"auto"``
    indexes the slice.  The rounds that built the scan compared it with
    slices of the WHOLE table; against its own slice it lost at D = 10
    and 26 (PERF.md section 6, PR 44-45).

The scans: traffic is M*(h1 + h2*D) one-hot elements instead of M DMA
descriptors (docs/PERF.md "The win" has the v5e rates of the rounds that
built them).  One-hot intermediates are built in chunks under
``lax.scan`` so the [C, D*h2] temporaries stay within a few MiB
regardless of M or D.

ONE flattened order for both directions: the level-2 axis is
``[h1, D * h2]``, column ``d * h2 + lo``.  The gather flattens the head
that way once, outside its scan; the scatter accumulates in it and
reorders once, after its scan.  Why: the TPU computes the product
``[C, D * h2]`` with the chunk's C slots on the lanes and the columns on
the sublanes, in tiles of 8.  At the benchmark's heads h2 = 128 is
sixteen whole tiles, so ``[C, D * h2] -> [C, D, h2]`` moves nothing (the
compiler makes it a bitcast), whereas with D on the minor side
(``[C, h2 * D] -> [C, h2, D]``) rows of D = 10 or 26 straddle the tiles
and the view is a shuffle of the whole product in every chunk: ``reshape
f32[1024,128,10]`` was 8.0 ms of mvm_tb.train_packed's 271.7 ms step and
``f32[512,128,26]`` 9.1 of dcn_tb.train_packed's 322.0 (ledger, PR 43).
For the same reason a chunk of the gather leaves the scan as ``[D, C]``:
one contiguous piece of the stacked ``[M/C, D, C]``, transposed once
after the loop; a ``[C, D]`` chunk goes into D planes of the stacked
result 16 MiB apart, piece by piece (10.6 and 11.7 ms of those steps).
At D = 1 the two orders are the same array.  tests/test_tpu_compile.py
holds the bitcast.

Numerics: the gather is *exact* (each one-hot row selects a single W
element; no accumulation), and the scatter differs from ``.at[].add``
only in summation order — but only because every contraction below
asks for ``Precision.HIGHEST``.  The TPU's default
precision for a float32 dot rounds both operands to bfloat16 on the way
into the MXU: measured on a v5e (PR 21, H=4096, N(0,1) weights) the
default-precision gather was off by up to 7.7e-3 absolute and the
scatter by 1.7e-3 relative, while HIGHEST is bitwise equal to
``w_hot[keys]`` and within 2.6e-7 of the segment-sum.  A CPU dot is
exact either way, so tests pin the argument (tests/test_hot.py)
and ``chip_smoke.py`` Phase 2 pins the effect.

Sentinel behavior: any key outside [0, H) produces an all-zero onehot_hi
row, so out-of-range/padding keys gather a zero row and scatter nothing
— mirroring the drop/clip semantics of ops/sparse.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint


def hot_factors(hot_size: int) -> tuple[int, int]:
    """Split H = h1 * h2 with h1 >= h2, both powers of two.

    h1 is the matmul contraction width for level 1 (oh_hi @ W) and h2
    the lane-select width for level 2; near-square minimizes
    h1 + h2*D traffic per occurrence.
    """
    log2 = hot_size.bit_length() - 1
    if hot_size != 1 << log2:
        raise ValueError(f"hot_size must be a power of two, got {hot_size}")
    h1 = 1 << ((log2 + 1) // 2)
    return h1, hot_size // h1


# Every contraction's precision: a default-precision float32 dot on the
# v5e rounds its operands to bfloat16 in the MXU (module docstring,
# PR 21).
_PRECISION = jax.lax.Precision.HIGHEST


def _chunk(h1: int, h2: int, d: int, m: int) -> int:
    """Rows per scan chunk: bound the [C, max(h1, D*h2)] temporaries to
    ~2^21 f32 elements (8 MiB), and never pad a small M (e.g. an online-
    inference batch) up to a huge chunk."""
    width = max(h1, h2 * d)
    c = max(256, (1 << 21) // width)
    c = 1 << (c.bit_length() - 1)  # round down to a power of two
    m_pow2 = 1 << max(m - 1, 1).bit_length()  # round M up to a power of two
    return min(c, m_pow2)


def _pad_to(x: jax.Array, m_pad: int, fill) -> jax.Array:
    m = x.shape[0]
    if m_pad == m:
        return x
    pad_shape = (m_pad - m,) + x.shape[1:]
    return jnp.concatenate([x, jnp.full(pad_shape, fill, x.dtype)])


# Columns of a head from which ``hot_gather``'s "auto" indexes the
# [H, D] slice and stops scanning it.  Two laws, read on a v5e alone at
# H = 16384 over 4 194 304 slots (scripts/probe_hot2.py; PERF.md section
# 6, PR 45).  The scan's product is 2 M h1 D h2 operations in three MXU
# passes: 2.7 ms at D = 1, 6.6 at 2, 11.7 at 4, 22.6 at 8, 30.9 at 10
# (and 91 at 16, 134 at 32).  A row of the slice is one descriptor
# whatever its width: 8.3 ms at D = 2, 8.4 at 4 and 8, 10.9 at 10, 10.8
# at 32, a piece at a time (2.0-2.6 ns a slot; 4.2 gathered whole); one
# COLUMN is a gather of single elements, 30.3 ms.  They cross between
# D = 2, where the scan wins, and D = 4, the narrowest the plain form
# was seen to win at.
PLAIN_GATHER_MIN_COLUMNS = 4


def gather_form(d: int, impl: str = "auto") -> str:
    """The form ``hot_gather`` runs for a head of ``d`` columns: ``impl``
    itself where it names one ("mxu", "seg"), and for "auto" the cheaper
    one on the TPU at that width."""
    if impl != "auto":
        return impl
    return "seg" if d >= PLAIN_GATHER_MIN_COLUMNS else "mxu"


# Slots the plain gather reads at a time.  The TPU writes a gathered row
# of D < 128 columns into a 128-lane row of its own (f32[M, D] in (8,128)
# tiles: 512 B a slot whatever D), so mvm_tb.train_packed's 4 194 304
# slots at once were a 2 GiB temporary and 1 GiB more of program peak
# (compiled for a described v5e, PR 45).  A piece is 16 MiB of that,
# which the compiler keeps in VMEM (the gather then costs 1.5-2.6 ns a
# slot, not 4.2), and leaves the loop as [D, C], the slots on the lanes,
# as a chunk of the scan does (module docstring): the stacked result is
# the scan's, and so is everything after it.  Alone, pieces of 4 096 to
# 65 536 slots cost the same to 0.1 ms; 262 144 leave VMEM (+3 ms at
# D = 10).
_PLAIN_GATHER_SLOTS = 1 << 15


def _plain_gather(w_hot: jax.Array, keys: jax.Array) -> jax.Array:
    """``w_hot[keys]`` with zero rows for keys outside [0, H), a piece of
    ``_PLAIN_GATHER_SLOTS`` slots at a time."""
    h, d = w_hot.shape

    def piece(k):  # [C] -> [C, D]
        rows = w_hot[jnp.clip(k, 0, h - 1)]
        ok = (k >= 0) & (k < h)
        return jnp.where(ok[:, None], rows, 0.0).astype(jnp.float32)

    m, c = keys.shape[0], _PLAIN_GATHER_SLOTS
    if m <= c:
        return piece(keys)
    m_pad = ((m + c - 1) // c) * c
    lanes = Layout(major_to_minor=(0, 1))  # of [D, C]: the slots minor
    out = jax.lax.map(
        lambda k: with_layout_constraint(piece(k).T, lanes),
        _pad_to(keys, m_pad, h).reshape(-1, c),
    )  # [M/C, D, C]
    return out.transpose(0, 2, 1).reshape(m_pad, d)[:m]


@jax.named_scope("xf.gather")
def hot_gather(
    w_hot: jax.Array,
    keys: jax.Array,
    *,
    impl: str = "mxu",
) -> jax.Array:
    """Gather rows of the hot table's [H, D] slice.

    Args:
      w_hot: [H, D] hot-table rows (H a power of two).
      keys: int32 [M]; entries outside [0, H) yield zero rows.
      impl: "mxu" — the two-level one-hot scan; "seg" — a plain
        clip-gather of the slice with zero fill; "auto" — by the
        slice's width (``gather_form``).  Same contract, exact every
        way.  "seg" is also the CPU-fast form (one-hot matmuls are an
        MXU trick — measured 3.3x slower than the gather on the CPU
        backend, docs/PERF.md "Wire format and compaction").
        TrainStep picks per platform via Config.hot_impl: "auto" on
        the TPU, "seg" elsewhere.

    Returns: [M, D] gathered rows, float32.
    """
    h, d = w_hot.shape
    if gather_form(d, impl) == "seg":
        return _plain_gather(w_hot, keys)
    h1, h2 = hot_factors(h)
    m = keys.shape[0]
    c = _chunk(h1, h2, d, m)
    m_pad = ((m + c - 1) // c) * c
    kp = _pad_to(keys, m_pad, h)  # sentinel: all-zero one-hot
    # [h1, (d, h2)]: the module's one flattened order (docstring)
    wr = w_hot.reshape(h1, h2, d).transpose(0, 2, 1).reshape(h1, d * h2)
    ar1 = jnp.arange(h1, dtype=kp.dtype)
    ar2 = jnp.arange(h2, dtype=kp.dtype)

    def body(_, k):
        hi = k // h2
        lo = k % h2
        oh_hi = (hi[:, None] == ar1[None, :]).astype(jnp.float32)  # [C, h1]
        rows = jnp.dot(
            oh_hi, wr, precision=_PRECISION,
            preferred_element_type=jnp.float32,
        ).reshape(c, d, h2)
        oh_lo = (lo[:, None] == ar2[None, :]).astype(jnp.float32)  # [C, h2]
        # one selected element plus exact zeros: no rounding
        return None, (rows * oh_lo[:, None, :]).sum(-1).T  # [D, C]

    _, out = jax.lax.scan(body, None, kp.reshape(-1, c))  # [M/C, D, C]
    return out.transpose(0, 2, 1).reshape(m_pad, d)[:m]


# Columns of a head from which ``hot_scatter``'s "auto" adds the slots'
# gradients into the [H, D] slice by plain indexing and stops scanning.
# Two laws again, read on a v5e alone at H = 16384 under the benchmark
# cells' OWN keys (scripts/probe_hot2.py --scatter; PERF.md section 6,
# PR 49).  The scan's product is 2 M h1 D h2 operations in three MXU
# passes and its price grows with D, in steps (the chunk halves as
# D h2 doubles): over 2 097 152 slots 18.5 ms at D = 4, 23.4 at 8, 14.9
# at 10, 39.0 at 16, 44.1 at 26, 102 at 32, 204 at 64.  The plain
# scatter-add takes the slots one after the other, 7.4-9.2 ns a slot at
# any width: 15.6 ms at D = 4 and 8, 16.3 at 10, 17.3 at 16, 19.4 at 26,
# 19.0 at 32, 23.4 at 64 (twice each over 4 194 304 slots).  At D = 10
# (MVM's, FM's and xDeepFM's heads) the scan wins or ties (14.9 / 16.3;
# 33.5 / 34.0 over MVM's slots; 3.8 / 4.1 over xDeepFM's), at D = 16
# (AutoInt's) it loses (9.2 / 4.1 over 524 288 slots): 16 is the
# narrowest width above 10 the plain form was seen to win at.  (It also
# won at D = 4 and 8, by 3-8 ms; no head is that wide, and one
# threshold cannot leave 10 out.)
PLAIN_SCATTER_MIN_COLUMNS = 16


def scatter_form(d: int, impl: str = "auto") -> str:
    """The form ``hot_scatter`` runs for a head of ``d`` columns: ``impl``
    itself where it names one ("mxu", "seg"), and for "auto" the cheaper
    one on the TPU at that width."""
    if impl != "auto":
        return impl
    return "seg" if d >= PLAIN_SCATTER_MIN_COLUMNS else "mxu"


# Slots the plain scatter adds at a time.  The TPU takes the updates of a
# scatter as rows, D < 128 columns in a 128-lane row of their own: whole,
# dcn_tb.train_packed's [2097152, 26] gradients would be a 1 GiB
# temporary for 218 MB of numbers.  So the slots reach the loop as the
# scan's chunks do, on the lanes ([M/C, D, C]), and a piece is turned to
# rows inside it, 16 MiB that the compiler keeps in VMEM beside the
# [H, D] accumulator.  The piece buys memory, not time: the adds go one
# after the other whatever the piece (alone at D = 26: 21.3 ms at 4 096
# slots, 19.4 at 16 384 to 65 536, 19.9 at 262 144; 16.4 written whole,
# the turn costs 3 ms; 17.9 in dcn_tb.train_packed's step, where the
# scan read 43.9), and in order, so the sums are those of the plain
# scatter-add written whole, bit for bit, at every piece.
_PLAIN_SCATTER_SLOTS = 1 << 15


def _plain_scatter(
    keys: jax.Array, grads: jax.Array, hot_size: int
) -> jax.Array:
    """``zeros([H, D]).at[keys].add(grads)`` with keys outside [0, H)
    dropped, a piece of ``_PLAIN_SCATTER_SLOTS`` slots at a time."""
    m, d = grads.shape
    grads = grads.astype(jnp.float32)
    zeros = jnp.zeros((hot_size, d), jnp.float32)

    def add(acc, k, g):  # [H, D] += [C, D] at [C]
        # a negative index would count from the end: every key outside
        # the head goes to H, which "drop" drops
        k = jnp.where((k >= 0) & (k < hot_size), k, hot_size)
        return acc.at[k].add(g, mode="drop")

    c = _PLAIN_SCATTER_SLOTS
    if m <= c:
        return add(zeros, keys, grads)
    m_pad = ((m + c - 1) // c) * c
    lanes = Layout(major_to_minor=(0, 1, 2))  # of [M/C, D, C]: slots minor
    pieces = with_layout_constraint(
        _pad_to(grads, m_pad, 0).reshape(-1, c, d).transpose(0, 2, 1), lanes
    )
    acc, _ = jax.lax.scan(
        lambda acc, xs: (add(acc, xs[0], xs[1].T), None),
        zeros,
        (_pad_to(keys, m_pad, hot_size).reshape(-1, c), pieces),
    )
    return acc


@jax.named_scope("xf.scatter")
def hot_scatter(
    keys: jax.Array,
    grads: jax.Array,
    hot_size: int,
    *,
    impl: str = "mxu",
) -> jax.Array:
    """Sum per-occurrence gradients into a dense [H, D] buffer (the
    head's ``zeros([H, D]).at[keys].add(grads)``).

    Args:
      keys: int32 [M]; entries outside [0, H) are dropped.
      grads: float [M, D].
      hot_size: H (power of two).
      impl: "mxu" — the two-level one-hot scan; "seg" — a plain
        scatter-add into the [H, D] buffer, a piece of the slots at a
        time (also the CPU-fast form); "auto" — by the buffer's width
        (``scatter_form``).  Same sums; the summation order differs.

    Returns: [H, D] float32 gradient sums.
    """
    m, d = grads.shape
    if scatter_form(d, impl) == "seg":
        return _plain_scatter(keys, grads, hot_size)
    h1, h2 = hot_factors(hot_size)
    c = _chunk(h1, h2, d, m)
    m_pad = ((m + c - 1) // c) * c
    kp = _pad_to(keys, m_pad, hot_size)
    gp = _pad_to(grads, m_pad, 0)
    ar1 = jnp.arange(h1, dtype=kp.dtype)
    ar2 = jnp.arange(h2, dtype=kp.dtype)

    def body(acc, xs):
        k, g = xs
        hi = k // h2
        lo = k % h2
        oh_hi = (hi[:, None] == ar1[None, :]).astype(jnp.float32)  # [C, h1]
        oh_lo = (lo[:, None] == ar2[None, :]).astype(g.dtype)  # [C, h2]
        glo = (g[:, :, None] * oh_lo[:, None, :]).reshape(c, d * h2)
        acc = acc + jnp.dot(
            oh_hi.T, glo, precision=_PRECISION,
            preferred_element_type=jnp.float32,
        )
        return acc, None

    acc0 = jnp.zeros((h1, d * h2), jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0, (kp.reshape(-1, c), gp.reshape(-1, c, d))
    )
    # acc is [h1, (d, h2)], the module's one flattened order; reorder to
    # [h1, h2, d] so row hi*h2+lo lands at table row `key`.
    return acc.reshape(h1, d, h2).transpose(0, 2, 1).reshape(h1 * h2, d)

"""Two-level one-hot MXU gather/scatter for the frequency-hot table head.

XLA TPU gather/scatter cost is per *slice* (~8-14 ns of DMA descriptor
issue each, independent of slice width — docs/PERF.md), so a step over
M = B*nnz feature occurrences pays ~18 ns/occurrence of round-trip DMA
no matter what.  CTR key distributions are zipfian: after the frequency
remap (io/freq.py) the head of the distribution lives in table rows
[0, H).  For those occurrences we replace per-slice DMA with two-level
one-hot matmuls that ride the MXU:

    key = hi * h2 + lo            (H = h1 * h2)
    gather:  rows = ((onehot_hi @ W) . reshape  *  onehot_lo) sum over lo
    scatter: W'   = onehot_hi^T @ (g * onehot_lo)

Traffic is M*(h1 + h2*D) one-hot elements instead of M DMA descriptors
(docs/PERF.md "The win" has the v5e rates of the rounds that built it).

One-hot intermediates are built in chunks under ``lax.scan`` so the
[C, D*h2] temporaries stay within a few MiB regardless of M or D.

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


@jax.named_scope("xf.gather")
def hot_gather(
    w_hot: jax.Array,
    keys: jax.Array,
    *,
    impl: str = "mxu",
) -> jax.Array:
    """Gather rows of the hot table via two-level one-hot matmuls.

    Args:
      w_hot: [H, D] hot-table rows (H a power of two).
      keys: int32 [M]; entries outside [0, H) yield zero rows.
      impl: "mxu" — the one-hot matmul path (the TPU win this module
        exists for); "seg" — a plain clip-gather with zero fill.  Same
        contract, exact either way; "seg" is the CPU-fast form (one-hot
        matmuls are an MXU trick — measured 3.3x slower than the gather
        on the CPU backend, docs/PERF.md "Wire format and compaction").
        TrainStep picks per platform via Config.hot_impl.

    Returns: [M, D] gathered rows, float32.
    """
    h, d = w_hot.shape
    if impl == "seg":
        rows = w_hot[jnp.clip(keys, 0, h - 1)]
        ok = (keys >= 0) & (keys < h)
        return jnp.where(ok[:, None], rows, 0.0).astype(jnp.float32)
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


@jax.named_scope("xf.scatter")
def hot_scatter(
    keys: jax.Array,
    grads: jax.Array,
    hot_size: int,
    *,
    impl: str = "mxu",
) -> jax.Array:
    """Sum per-occurrence gradients into a dense [H, D] buffer via
    two-level one-hot matmuls (the MXU replacement for
    ``zeros([H, D]).at[keys].add(grads)``).

    Args:
      keys: int32 [M]; entries outside [0, H) are dropped.
      grads: float [M, D].
      hot_size: H (power of two).
      impl: "mxu" (one-hot matmuls) or "seg" (segment-sum into the
        [H, D] buffer — the CPU-fast form; same sums, summation order
        differs like the MXU path differs from ``.at[].add``).

    Returns: [H, D] float32 gradient sums.
    """
    m, d = grads.shape
    if impl == "seg":
        seg = jnp.where(
            (keys >= 0) & (keys < hot_size), keys, jnp.int32(hot_size)
        )
        return jax.ops.segment_sum(
            grads.astype(jnp.float32), seg, num_segments=hot_size + 1
        )[:hot_size]
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

"""Unit tests for the hot-table head (ops/hot.py): the two-level one-hot
MXU scans, and the plain indexing of the head's slice that the gather
takes from hot.PLAIN_GATHER_MIN_COLUMNS columns up and the scatter from
hot.PLAIN_SCATTER_MIN_COLUMNS.

Correctness spec: hot_gather(W, k) == W[k] (zero row for k outside
[0, H)) and hot_scatter(k, g, H) == zeros([H, D]).at[k].add(g) (dropping
out-of-range keys) — i.e. exact drop/clip parity with the DMA path of
ops/sparse.py, up to summation order in the scatter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.ops.hot import (
    PLAIN_GATHER_MIN_COLUMNS,
    PLAIN_SCATTER_MIN_COLUMNS,
    gather_form,
    hot_factors,
    hot_gather,
    hot_scatter,
    scatter_form,
)


def dma_gather(w, keys):
    h = w.shape[0]
    rows = w[jnp.clip(keys, 0, h - 1)]
    return jnp.where((keys >= 0)[:, None] & (keys < h)[:, None], rows, 0.0)


def dma_scatter(keys, grads, h):
    return jnp.zeros((h, grads.shape[1]), jnp.float32).at[keys].add(
        grads, mode="drop"
    )


@pytest.mark.parametrize("h", [256, 4096, 8192])
def test_factors(h):
    h1, h2 = hot_factors(h)
    assert h1 * h2 == h
    assert h1 >= h2
    assert h1 & (h1 - 1) == 0 and h2 & (h2 - 1) == 0


def test_factors_rejects_non_pow2():
    with pytest.raises(ValueError):
        hot_factors(1000)


# the last two: the heads of the benchmark's mvm_tb / dcn_tb cells (hot
# 2^14 at D = 10 and 26), M not a multiple of the scan's chunk
@pytest.mark.parametrize("h,d,m", [
    (256, 1, 1000), (1024, 10, 4097), (4096, 1, 300),
    (16384, 10, 5000), (16384, 26, 3000),
])
def test_gather_matches_dma(h, d, m):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    # include keys outside [0, H) on both sides (the padding convention:
    # the sentinel is H; a negative key selects nothing either)
    keys = rng.integers(-(h // 8), h + h // 4, size=m).astype(np.int32)
    got = np.asarray(jax.jit(hot_gather)(w, jnp.asarray(keys)))
    want = np.asarray(dma_gather(w, jnp.asarray(keys)))
    assert got.shape == (m, d) and (keys < 0).any() and (keys >= h).any()
    assert (got == want).all()  # a selection: bit for bit, not approximately


# LR's width, MVM's / FM's / xDeepFM's, DCN's; the plain form in one piece
# and in pieces that do not divide M
@pytest.mark.parametrize("piece", [None, 256])
@pytest.mark.parametrize("d", [1, 10, 26])
def test_gather_forms_agree_bit_for_bit(monkeypatch, d, piece):
    """One contract, two exact implementations: the scan ("mxu") and the
    plain clip-gather of the slice ("seg", which reads
    hot._PLAIN_GATHER_SLOTS slots at a time) return the same bits, zero
    rows for keys below 0, at H and beyond it included; "auto" is one of
    the two."""
    from xflow_tpu.ops import hot

    h, m = 4096, 3001
    if piece:
        monkeypatch.setattr(hot, "_PLAIN_GATHER_SLOTS", piece)
    assert (m > hot._PLAIN_GATHER_SLOTS) == bool(piece)
    rng = np.random.default_rng(d)
    w = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
    keys = rng.integers(0, h, size=m).astype(np.int32)
    keys[:6] = [-1, -h, h, h + 1, 2 * h, np.iinfo(np.int32).max]
    keys[-1] = h  # in the last, short piece too
    got = {
        impl: np.asarray(
            jax.jit(lambda w, k, impl=impl: hot_gather(w, k, impl=impl))(
                w, jnp.asarray(keys)
            )
        ).view(np.uint32)
        for impl in ("mxu", "seg", "auto")
    }
    assert got["seg"].shape == (m, d)
    assert (got["mxu"] == got["seg"]).all() and (got["auto"] == got["seg"]).all()
    assert not got["seg"][:6].any() and not got["seg"][-1].any()
    assert got["seg"][6:-1].any()


def _primitives(jaxpr) -> set[str]:
    return {eqn.primitive.name for eqn in jaxpr.eqns}


def test_auto_gathers_by_the_scan_at_one_column_and_by_indexing_when_wide():
    """ops/hot.py::gather_form: "auto" is the scan below
    PLAIN_GATHER_MIN_COLUMNS columns (D = 1: LR's, FM's and FFM's w, the
    serving program) and plain indexing of the [H, D] slice from there up
    (D = 10: MVM, FM's v, xDeepFM; D = 26: DCN); an explicit "mxu" or
    "seg" is taken at any width.  And hot_gather runs what the rule says:
    the scan is a ``scan`` and no ``gather``, the plain form a ``gather``
    and no ``scan``."""
    assert 1 < PLAIN_GATHER_MIN_COLUMNS <= 10
    assert [gather_form(d) for d in (1, 10, 26)] == ["mxu", "seg", "seg"]
    assert gather_form(PLAIN_GATHER_MIN_COLUMNS - 1, "auto") == "mxu"
    assert gather_form(PLAIN_GATHER_MIN_COLUMNS, "auto") == "seg"
    for d in (1, 10, 26):
        assert gather_form(d, "mxu") == "mxu" and gather_form(d, "seg") == "seg"
    keys = jnp.zeros((100,), jnp.int32)
    for d, impl, scans in [
        (1, "auto", True), (10, "auto", False), (26, "auto", False),
        (1, "seg", False), (10, "mxu", True), (26, "mxu", True),
    ]:
        w = jnp.zeros((256, d), jnp.float32)
        found = _primitives(
            jax.make_jaxpr(lambda w, k: hot_gather(w, k, impl=impl))(w, keys).jaxpr
        )
        assert ("scan" in found, "gather" in found) == (scans, not scans), (
            d, impl, found
        )


@pytest.mark.parametrize("h,d,m", [(256, 1, 1000), (1024, 10, 4097), (4096, 1, 300)])
def test_scatter_matches_dma(h, d, m):
    rng = np.random.default_rng(1)
    # zipf-ish duplicates so real accumulation happens
    keys = (rng.zipf(1.3, size=m) - 1).clip(0, h + 10).astype(np.int32)
    grads = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    got = hot_scatter(jnp.asarray(keys), grads, h)
    want = dma_scatter(jnp.asarray(keys), grads, h)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def _float64_sums(keys, grads, h):
    """zeros([H, D]).at[keys].add(grads) in float64 on the host, keys
    outside [0, H) dropped."""
    live = (keys >= 0) & (keys < h)
    return np.stack([
        np.bincount(keys[live], weights=grads[live, j].astype(np.float64),
                    minlength=h)
        for j in range(grads.shape[1])
    ], axis=1)


# (piece, M): one piece (the slots fit it), pieces that divide M, pieces
# that do not
@pytest.mark.parametrize("piece,m", [(1 << 15, 3000), (512, 4096), (512, 3001)])
@pytest.mark.parametrize("d", [1, 10, 16, 26])
def test_scatter_forms_agree_with_float64_sums(monkeypatch, d, piece, m):
    """ops/hot.py::hot_scatter: the one-hot scan ("mxu") and the plain
    scatter-add a piece at a time ("seg") both stand within 1e-5 of the
    largest sum from the sums in float64, at the widths of the
    benchmark's heads, sentinel (H), beyond-the-head and NEGATIVE keys
    dropped (a negative index would count from the end of the slice),
    whether the slots fit one piece, fill whole pieces or leave a rest."""
    import xflow_tpu.ops.hot as hot

    monkeypatch.setattr(hot, "_PLAIN_SCATTER_SLOTS", piece)
    h = 1024
    rng = np.random.default_rng(d)
    keys = (rng.zipf(1.3, size=m) - 1).clip(0, h + 10).astype(np.int32)
    keys[::7] = h  # the sentinel of a padded slot
    keys[3::11] = -1 - (keys[3::11] % 5)  # negative keys
    grads = rng.normal(size=(m, d)).astype(np.float32)
    want = _float64_sums(keys, grads, h)
    for impl in ("mxu", "seg"):
        got = np.asarray(
            jax.jit(lambda k, g, impl=impl: hot_scatter(k, g, h, impl=impl))(
                jnp.asarray(keys), jnp.asarray(grads)
            )
        )
        assert got.shape == (h, d) and got.dtype == np.float32
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max(), impl
    # a negative key wraps nowhere
    dropped = np.asarray(hot_scatter(
        jnp.asarray(np.full(8, -1, np.int32)), jnp.ones((8, d)), h, impl="seg"
    ))
    assert not dropped.any()


@pytest.mark.parametrize("impl", ["mxu", "seg", "auto"])
@pytest.mark.parametrize("d", [1, 26])
def test_scatter_of_an_empty_head_plane_is_zeros(impl, d):
    """A batch with no hot slot (hot_nnz 0 rows, or every slot the
    sentinel) sums to an all-zero [H, D] buffer in every form."""
    h = 256
    for keys in (np.zeros((0,), np.int32), np.full((40,), h, np.int32)):
        got = np.asarray(hot_scatter(
            jnp.asarray(keys), jnp.ones((len(keys), d), jnp.float32), h,
            impl=impl,
        ))
        assert got.shape == (h, d) and not got.any()


def test_scatter_form_is_chosen_from_the_width():
    """ops/hot.py::scatter_form: "auto" is the scan below
    PLAIN_SCATTER_MIN_COLUMNS columns (every D = 1 head, and the widths
    the probe saw no win at) and the plain scatter-add from there up
    (DCN's emb at D = 26); an explicit "mxu" or "seg" is taken at any
    width.  The constant lies between 10 and 26 (ISSUE 49) and is not
    the gather's: the two directions cross at different widths.  And
    hot_scatter runs what the rule says: a scan with a dot_general in
    it, or a scatter-add and no dot."""
    assert 10 <= PLAIN_SCATTER_MIN_COLUMNS <= 26
    assert PLAIN_SCATTER_MIN_COLUMNS > PLAIN_GATHER_MIN_COLUMNS
    assert scatter_form(1) == "mxu" and scatter_form(26) == "seg"
    assert scatter_form(PLAIN_SCATTER_MIN_COLUMNS - 1, "auto") == "mxu"
    assert scatter_form(PLAIN_SCATTER_MIN_COLUMNS, "auto") == "seg"
    for d in (1, PLAIN_SCATTER_MIN_COLUMNS - 1, PLAIN_SCATTER_MIN_COLUMNS, 64):
        assert scatter_form(d, "mxu") == "mxu" and scatter_form(d, "seg") == "seg"
    keys = jnp.zeros((100,), jnp.int32)
    for d, impl, plain in [
        (1, "auto", False), (PLAIN_SCATTER_MIN_COLUMNS - 1, "auto", False),
        (PLAIN_SCATTER_MIN_COLUMNS, "auto", True), (26, "auto", True),
        (26, "mxu", False), (1, "seg", True),
    ]:
        grads = jnp.zeros((100, d), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda k, g: hot_scatter(k, g, 256, impl=impl)
        )(keys, grads).jaxpr
        found = {eqn.primitive.name for eqn in jaxpr.eqns}
        assert ("scatter-add" in found, bool(_dot_precisions(jaxpr))) == (
            plain, not plain
        ), (d, impl, found)


def test_gather_f32_is_exact_selection():
    # one-hot selection in f32 must be bit-exact, not approximately equal
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(512, 3)).astype(np.float32) * 1e-4)
    keys = jnp.asarray(rng.integers(0, 512, size=700).astype(np.int32))
    got = np.asarray(hot_gather(w, keys))
    want = np.asarray(w)[np.asarray(keys)]
    assert (got == want).all()


def test_jit_and_grad_flow():
    # the ops must be jittable and differentiable (autodiff models route
    # gradients through hot_gather)
    w = jnp.ones((256, 2))
    keys = jnp.asarray(np.arange(100, dtype=np.int32))

    @jax.jit
    def f(w):
        return hot_gather(w, keys).sum()

    g = jax.grad(f)(w)
    assert float(g.sum()) == 200.0  # each of 100 keys contributes d=2 ones


def _dot_precisions(jaxpr):
    """``precision`` of every dot_general in a jaxpr, scan bodies
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if hasattr(sub, "jaxpr"):
                    out += _dot_precisions(sub.jaxpr)
                elif hasattr(sub, "eqns"):
                    out += _dot_precisions(sub)
    return out


def test_float32_contractions_ask_for_highest_precision():
    """A TPU's default-precision float32 dot rounds its operands to
    bfloat16 (measured on a v5e: gather off by 7.7e-3 — ops/hot.py
    docstring).  A CPU dot is exact either way, so this pins the
    ARGUMENT: every contraction carries Precision.HIGHEST.
    chip_smoke.py Phase 2 checks the effect on the chip."""
    want = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    w = jnp.zeros((256, 10), jnp.float32)
    keys = jnp.zeros((100,), jnp.int32)
    grads = jnp.zeros((100, 10), jnp.float32)
    gather = jax.make_jaxpr(hot_gather)(w, keys)
    scatter = jax.make_jaxpr(lambda k, g: hot_scatter(k, g, 256))(
        keys, grads
    )
    for jaxpr in (gather.jaxpr, scatter.jaxpr):
        precisions = _dot_precisions(jaxpr)
        assert precisions, "no dot_general found — did the lowering change?"
        assert all(p == want for p in precisions), precisions

"""Unit tests for the two-level one-hot MXU hot-table path (ops/hot.py).

Correctness spec: hot_gather(W, k) == W[k] (zero row for k outside
[0, H)) and hot_scatter(k, g, H) == zeros([H, D]).at[k].add(g) (dropping
out-of-range keys) — i.e. exact drop/clip parity with the DMA path of
ops/sparse.py, up to summation order in the scatter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xflow_tpu.ops.hot import hot_factors, hot_gather, hot_scatter


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

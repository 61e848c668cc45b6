"""Consolidation (sort+segment-sum unique) vs a dense numpy oracle."""

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.ops.sparse import consolidate, gather_rows, scatter_rows

TABLE = 64


def oracle_sums(keys, grads, table):
    dense = np.zeros((table, grads.shape[1]), dtype=np.float64)
    for k, g in zip(keys, grads):
        if k < table:
            dense[k] += g
    return dense


def test_consolidate_matches_oracle():
    rng = np.random.default_rng(0)
    m, d = 256, 3
    keys = rng.integers(0, TABLE, size=m).astype(np.int32)
    # sprinkle sentinel padding
    keys[rng.random(m) < 0.2] = TABLE
    grads = rng.normal(size=(m, d)).astype(np.float32)
    grads[keys == TABLE] = 0.0

    ukeys, gsum = jax.jit(consolidate, static_argnums=2)(
        jnp.asarray(keys), jnp.asarray(grads), TABLE
    )
    ukeys, gsum = np.asarray(ukeys), np.asarray(gsum)

    dense = np.zeros((TABLE, d))
    for k, g in zip(ukeys, gsum):
        if k < TABLE:
            dense[k] += g
    np.testing.assert_allclose(dense, oracle_sums(keys, grads, TABLE), atol=1e-4)
    # real unique keys appear exactly once
    real = ukeys[ukeys < TABLE]
    assert len(real) == len(set(real.tolist()))
    assert set(real.tolist()) == set(keys[keys < TABLE].tolist())


def test_consolidate_all_padding():
    keys = jnp.full((16,), TABLE, jnp.int32)
    grads = jnp.zeros((16, 1))
    ukeys, gsum = consolidate(keys, grads, TABLE)
    assert np.all(np.asarray(ukeys) == TABLE)
    np.testing.assert_array_equal(np.asarray(gsum), 0.0)


def test_consolidate_single_unique_key():
    """Every real slot carries the same key: one live segment, all
    gradients summed into it, every other slot sentinel/zero."""
    m, d = 32, 2
    keys = np.full(m, 7, np.int32)
    keys[-4:] = TABLE  # a little padding
    grads = np.ones((m, d), np.float32)
    grads[-4:] = 0.0
    ukeys, gsum = consolidate(
        jnp.asarray(keys), jnp.asarray(grads), TABLE
    )
    ukeys, gsum = np.asarray(ukeys), np.asarray(gsum)
    real = ukeys < TABLE
    assert real.sum() == 1
    np.testing.assert_allclose(gsum[real][0], np.full(d, m - 4.0))
    np.testing.assert_array_equal(gsum[~real], 0.0)


def test_gather_scatter_sentinel_dropped():
    table = jnp.arange(TABLE, dtype=jnp.float32)[:, None]
    ukeys = jnp.asarray([3, TABLE, 5], jnp.int32)
    rows = gather_rows(table, ukeys)
    # sentinel gather clamps to last row
    np.testing.assert_allclose(np.asarray(rows)[:, 0], [3.0, TABLE - 1, 5.0])
    new = scatter_rows(table, ukeys, rows * 10.0)
    out = np.asarray(new)[:, 0]
    assert out[3] == 30.0 and out[5] == 50.0
    # last row untouched: sentinel write dropped
    assert out[TABLE - 1] == TABLE - 1

"""The plain references, and the check that holds the system to them."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import refcheck
from benchmarks.reference import fm, ftrl, lr

HYPER = {"alpha": 5e-2, "beta": 1.0, "lambda1": 5e-5, "lambda2": 10.0}


def test_ftrl_update_is_the_recurrence_of_ftrl_h():
    """ftrl.h:58-74, one key at a time, in Python floats."""
    rng = np.random.default_rng(0)
    w, n, z, g = (rng.normal(0, 1, 50) for _ in range(4))
    n = n * n
    z[:5] = 1e-5  # inside the L1 ball after a tiny push
    g[:5] = 1e-6
    got = ftrl.ftrl_update(
        {"param": jnp.asarray(w, jnp.float32), "n": jnp.asarray(n, jnp.float32),
         "z": jnp.asarray(z, jnp.float32)},
        jnp.asarray(g, jnp.float32), HYPER,
    )
    for i in range(50):
        n1 = n[i] + g[i] ** 2
        z1 = z[i] + g[i] - (np.sqrt(n1) - np.sqrt(n[i])) / HYPER["alpha"] * w[i]
        w1 = 0.0 if abs(z1) <= HYPER["lambda1"] else (
            (np.sign(z1) * HYPER["lambda1"] - z1)
            / ((HYPER["beta"] + np.sqrt(n1)) / HYPER["alpha"] + HYPER["lambda2"])
        )
        assert float(got["n"][i]) == pytest.approx(n1, rel=1e-5)
        assert float(got["z"][i]) == pytest.approx(z1, rel=1e-5, abs=1e-7)
        assert float(got["param"][i]) == pytest.approx(w1, rel=1e-4, abs=1e-7)
    assert float(got["param"][0]) == 0.0


def test_an_entry_no_gradient_has_reached_keeps_its_drawn_value():
    row = {"param": jnp.asarray([0.3]), "n": jnp.zeros(1), "z": jnp.zeros(1)}
    assert float(ftrl.ftrl_update(row, jnp.zeros(1), HYPER)["param"][0]) == pytest.approx(0.3)


def test_fm_forward_has_no_half_and_its_backward_is_of_the_halved_form():
    rng = np.random.default_rng(1)
    rows = {
        "w": jnp.asarray(rng.normal(0, 1, (4, 6, 1)), jnp.float32),
        "v": jnp.asarray(rng.normal(0, 1, (4, 6, fm.V_DIM)), jnp.float32),
    }
    x = jnp.asarray(rng.integers(0, 2, (4, 6)), jnp.float32)
    w, v, xs = (np.asarray(a, np.float64) for a in (rows["w"], rows["v"], x))
    pairs = np.zeros(4)
    for b in range(4):
        for i in range(6):
            for j in range(6):
                if i != j:
                    pairs[b] += (v[b, i] * v[b, j]).sum() * xs[b, i] * xs[b, j]
    # sum over ORDERED pairs = (sum)^2 - sum of squares: twice the usual FM term
    want = (w[..., 0] * xs).sum(1) + pairs
    assert np.allclose(fm.logit(rows, x), want, rtol=1e-4, atol=1e-4)

    def halved(rows_):
        vx = rows_["v"] * x[..., None]
        pair = jnp.sum(vx, 1) ** 2 - jnp.sum(vx * vx, 1)
        return jnp.sum(jnp.sum(rows_["w"][..., 0] * x, -1) + 0.5 * jnp.sum(pair, -1))

    auto = jax.grad(halved)(rows)
    explicit = fm.grad_logit(rows, x)
    assert np.allclose(explicit["w"], auto["w"], atol=1e-5)
    assert np.allclose(explicit["v"], auto["v"], rtol=1e-4, atol=1e-5)


def _system(model: str, hot_log2: int):
    from xflow_tpu.config import Config
    from xflow_tpu.io.batch import make_batch
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    cfg = Config(
        model=model, optimizer="ftrl", table_size_log2=12, batch_size=64,
        max_nnz=6, hot_size_log2=hot_log2, hot_nnz=6, num_devices=1, seed=3,
    )
    mesh = make_mesh(1)
    mdl, opt = make_model(cfg), make_optimizer(cfg)
    system = types.SimpleNamespace(  # what the check uses of a Trainer
        step=TrainStep(mdl, opt, cfg, mesh), state=init_state(mdl, opt, cfg, mesh)
    )
    rng = np.random.default_rng(5)
    k = cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
    batches = []
    for _ in range(3):
        keys = rng.integers(0, cfg.table_size, (64, k))
        keys = np.where(rng.random(keys.shape) < 0.5, rng.integers(0, 40, keys.shape), keys)
        mask = (rng.random(keys.shape) < 0.7).astype(np.float32)
        weights = np.ones(64, np.float32)
        weights[-5:] = 0.0  # padding examples
        batches.append(make_batch(
            keys.astype(np.int32), np.zeros(keys.shape, np.int32), mask.copy(),
            mask, rng.integers(0, 2, 64).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return system, batches, cfg


@pytest.mark.parametrize("model, family", [("lr", lr), ("fm", fm)])
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_system_step_agrees_with_the_reference(model, family, hot_log2):
    """xflow_tpu's train step — wire, hot/cold split, dense FTRL pass —
    against the plain reference, three steps running (the second and third
    from a state that is no longer zero)."""
    system, batches, cfg = _system(model, hot_log2)
    got = refcheck.check_train_steps(system, family, batches, cfg)
    assert got["ok"], got
    assert all(s["touched_rows"] > 100 for s in got["steps"])
    assert got["steps"][0]["logloss"] == pytest.approx(np.log(2), abs=2e-3)


def test_the_check_can_fail():
    """A reference at another learning rate (0.1 % off) is outside the
    tolerance: the check is tight enough to see it."""
    system, batches, cfg = _system("lr", 5)
    off = cfg.replace(alpha=cfg.alpha * 1.001)
    got = refcheck.check_train_steps(system, lr, batches, off)
    assert not got["ok"]
    assert max(got["steps"][-1]["rows_rel_err"].values()) > refcheck.ROWS_RTOL

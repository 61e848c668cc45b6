"""Model forward/gradient math vs independent numpy oracles, including
the reference's FM forward/backward quirk (fm_worker.cc:82 vs :140-142)
and MVM's fixed consistent 1+sum form (checked against autodiff) —
plus the models/blocks.py refactor's no-regression contract: every
incumbent family's predict output bitwise-identical to a frozen copy
of the pre-refactor implementation (tests/_legacy_models.py) on a
fixed seeded batch, in dense, MXU-hot, and tiered store modes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._legacy_models import legacy_model_for
from xflow_tpu.models.fm import FMModel
from xflow_tpu.models.lr import LRModel
from xflow_tpu.models.mvm import MVMModel

B, K, D, S = 4, 6, 5, 4


def random_batch(seed=0, binary=True):
    rng = np.random.default_rng(seed)
    mask = (rng.random((B, K)) < 0.8).astype(np.float32)
    return {
        "keys": jnp.asarray(rng.integers(0, 100, (B, K)), jnp.int32),
        "slots": jnp.asarray(rng.integers(0, S, (B, K)), jnp.int32),
        "vals": jnp.asarray(
            np.ones((B, K), np.float32)
            if binary
            else rng.normal(1, 0.3, (B, K)).astype(np.float32)
        ),
        "mask": jnp.asarray(mask),
        "labels": jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
        "weights": jnp.ones(B, jnp.float32),
    }


def test_lr_logit_oracle():
    model = LRModel()
    batch = random_batch(binary=False)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(B, K, 1)), jnp.float32)
    got = np.asarray(model.logit({"w": w}, batch))
    x = np.asarray(batch["vals"]) * np.asarray(batch["mask"])
    want = (np.asarray(w)[..., 0] * x).sum(-1)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    g = np.asarray(model.grad_logit({"w": w}, batch)["w"])
    np.testing.assert_allclose(g[..., 0], x, rtol=1e-6)


def test_fm_forward_has_no_half_factor():
    """logit = w·x + [(Σvx)² − Σ(vx)²] — no ½ (fm_worker.cc:82,86)."""
    model = FMModel(v_dim=D)
    batch = random_batch()
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(B, K, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, K, D)), jnp.float32)
    got = np.asarray(model.logit({"w": w, "v": v}, batch))
    x = np.asarray(batch["mask"])  # vals are 1
    vx = np.asarray(v) * x[..., None]
    inter = (vx.sum(1) ** 2 - (vx**2).sum(1)).sum(-1)
    want = (np.asarray(w)[..., 0] * x).sum(-1) + inter
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_fm_gradient_is_half_scaled_reference_form():
    """grad_v = (Σ v x − v x)·x — the ½-scaled gradient the reference
    pushes (fm_worker.cc:140-142), which is NOT the autodiff gradient of
    the no-½ forward (would be twice this)."""
    model = FMModel(v_dim=D)
    batch = random_batch()
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(B, K, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, K, D)), jnp.float32)
    rows = {"w": w, "v": v}
    g = model.grad_logit(rows, batch)
    x = np.asarray(batch["mask"])
    vx = np.asarray(v) * x[..., None]
    want_v = (vx.sum(1, keepdims=True) - vx) * x[..., None]
    np.testing.assert_allclose(np.asarray(g["v"]), want_v, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g["w"])[..., 0], x, rtol=1e-6)

    # autodiff of the forward is exactly 2x on the interaction term
    auto = jax.grad(lambda vv: model.logit({"w": w, "v": vv}, batch).sum())(v)
    interaction_auto = np.asarray(auto) - 0.0  # w part not in v grad
    np.testing.assert_allclose(interaction_auto, 2.0 * want_v, rtol=2e-4, atol=1e-5)


def test_mvm_consistent_with_autodiff():
    """MVM uses the fixed 1+Σ form on both sides, so explicit grads must
    equal autodiff of the forward."""
    model = MVMModel(v_dim=D, max_fields=S)
    batch = random_batch(seed=5, binary=False)
    rng = np.random.default_rng(6)
    v = jnp.asarray(rng.normal(0, 0.5, size=(B, K, D)), jnp.float32)
    explicit = np.asarray(model.grad_logit({"v": v}, batch)["v"])
    auto = np.asarray(
        jax.grad(lambda vv: model.logit({"v": vv}, batch).sum())(v)
    )
    np.testing.assert_allclose(explicit, auto, rtol=1e-4, atol=1e-5)


def test_mvm_forward_oracle():
    model = MVMModel(v_dim=D, max_fields=S)
    batch = random_batch(seed=8, binary=False)
    rng = np.random.default_rng(9)
    v = np.asarray(rng.normal(0, 0.5, size=(B, K, D)), np.float32)
    got = np.asarray(model.logit({"v": jnp.asarray(v)}, batch))
    x = np.asarray(batch["vals"]) * np.asarray(batch["mask"])
    slots = np.asarray(batch["slots"])
    want = np.zeros(B)
    for b in range(B):
        total = 0.0
        for d in range(D):
            prod = 1.0
            for s in range(S):
                ssum = sum(
                    v[b, k, d] * x[b, k] for k in range(K) if slots[b, k] == s
                )
                prod *= 1.0 + ssum
            total += prod - 1.0  # centered form (models/mvm.py docstring)
        want[b] = total
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_mvm_ignores_out_of_range_fields():
    model = MVMModel(v_dim=D, max_fields=2)
    batch = random_batch(seed=10)
    batch["slots"] = jnp.full((B, K), 5, jnp.int32)  # all fields out of range
    v = jnp.asarray(np.random.default_rng(11).normal(size=(B, K, D)), jnp.float32)
    # every slot empty → product 1 per factor, centered to logit 0
    np.testing.assert_allclose(np.asarray(model.logit({"v": v}, batch)), 0.0)
    np.testing.assert_array_equal(
        np.asarray(model.grad_logit({"v": v}, batch)["v"]), 0.0
    )


# -- MVM's backward picks by contraction: bit for bit the gather it replaced ---
#
# models/mvm.py::grad_logit selects each entry's own ``1 + slotsum`` by
# contracting the forward's one-hot with it (blocks.field_pick), where
# the frozen copy (tests/_legacy_models.py) gathers it by index
# (take_along_axis).  A 0/1 operand makes the contraction a selection:
# the same float32 number, so the same gradient, bit for bit.

_PICK_B, _PICK_K = 48, 8 + 32  # the benchmark cell's 8 cold + 32 hot entries
_PICK_CASES = ("in_range", "outside", "masked", "guard", "mixed")


def _pick_case(case: str, fields: int):
    rng = np.random.default_rng([fields, _PICK_CASES.index(case)])
    slots = rng.integers(0, fields, (_PICK_B, _PICK_K)).astype(np.int32)
    mask = np.ones((_PICK_B, _PICK_K), np.float32)
    vals = rng.normal(1, 0.3, (_PICK_B, _PICK_K)).astype(np.float32)
    v = rng.normal(0, 0.5, (_PICK_B, _PICK_K, 10)).astype(np.float32)
    if case in ("outside", "mixed"):
        outside = rng.random(slots.shape) < 0.3
        slots[outside] = rng.choice(
            [-7, -1, fields, fields + 1, 255], int(outside.sum())
        )
        assert (slots < 0).any() and (slots >= fields).any()
    if case in ("masked", "mixed"):
        mask = (rng.random(slots.shape) < 0.7).astype(np.float32)
    if case in ("guard", "mixed"):
        # a field whose only entry is v = -1 at x = 1: 1 + s is 0, under
        # the 1e-12 guard, in every other row's first five factors
        slots[slots == 3] = 4
        slots[:, 0], vals[:, 0], mask[:, 0] = 3, 1.0, 1.0
        v[::2, 0, :5] = -1.0
    batch = {
        "slots": jnp.asarray(slots), "vals": jnp.asarray(vals),
        "mask": jnp.asarray(mask),
    }
    return batch, jnp.asarray(v)


@pytest.mark.parametrize("fields", (32, 40))
@pytest.mark.parametrize("case", _PICK_CASES)
def test_mvm_grad_picks_by_contraction_bitwise(case, fields):
    from tests._legacy_models import LegacyMVMModel

    batch, v = _pick_case(case, fields)
    new = MVMModel(v_dim=10, max_fields=fields)
    old = LegacyMVMModel(v_dim=10, max_fields=fields)
    want = np.asarray(jax.jit(old.grad_logit)({"v": v}, batch)["v"])
    got = np.asarray(jax.jit(new.grad_logit)({"v": v}, batch)["v"])
    bits = lambda a: np.asarray(a).view(np.uint32)  # noqa: E731
    np.testing.assert_array_equal(bits(got), bits(want))
    np.testing.assert_array_equal(
        bits(new.grad_logit({"v": v}, batch)["v"]), bits(want)  # op by op
    )
    assert np.isfinite(want).all() and (want != 0).mean() > 0.3
    slots = np.asarray(batch["slots"])
    gone = (slots < 0) | (slots >= fields) | (np.asarray(batch["mask"]) == 0)
    assert (got[gone] == 0).all()
    if case in ("guard", "mixed"):
        assert (got[::2, 0, :5] == 0).all()  # the guarded entry
        assert (got[1::2, 0, :] != 0).all() and (got[::2, 0, 5:] != 0).all()
        one_plus = np.asarray(new._slot_terms({"v": v}, batch)[0])
        assert (np.abs(one_plus[::2, 3, :5]) < 1e-12).all()


@pytest.mark.parametrize("fields", (32, 40))
def test_field_pick_is_the_gather(fields):
    """blocks.field_pick alone: the row of each entry's own field, 0 for
    an all-zero one-hot row (a field id outside [0, F))."""
    from xflow_tpu.models.blocks import field_pick

    rng = np.random.default_rng(fields)
    slots = rng.integers(-2, fields + 2, (_PICK_B, _PICK_K)).astype(np.int32)
    per_field = (1.0 + rng.normal(0, 2e-2, (_PICK_B, fields, 10))).astype(
        np.float32
    )
    onehot = jax.nn.one_hot(jnp.asarray(slots), fields, dtype=jnp.float32)
    got = np.asarray(jax.jit(field_pick)(onehot, jnp.asarray(per_field)))
    inside = (slots >= 0) & (slots < fields)
    want = np.take_along_axis(
        per_field, np.clip(slots, 0, fields - 1)[:, :, None], axis=1
    )
    np.testing.assert_array_equal(got[inside], want[inside])
    assert (got[~inside] == 0).all() and (~inside).any()


# -- blocks refactor: bitwise no-regression vs the frozen legacy oracles ------
#
# The refactor's contract (docs/SERVING.md cascade PR): expressing the
# five incumbent families through models/blocks.py changes NOTHING —
# not "close", bitwise.  Each family runs the full TrainStep predict
# machinery twice on one fixed seeded batch and identical state: once
# with the refactored model, once with the frozen pre-refactor copy
# (tests/_legacy_models.py), and the pctr arrays must be equal bit for
# bit, in every parameter-residency mode the step supports.

_NR_FAMILIES = ("lr", "fm", "mvm", "ffm", "wide_deep")


def _nr_cfg(name, **over):
    from xflow_tpu.config import Config

    base = dict(
        model=name,
        table_size_log2=10,
        batch_size=8,
        max_nnz=6,
        max_fields=S,
        num_devices=1,
    )
    base.update(over)
    return Config(**base)


def _nr_batch(cfg, seed=11):
    from xflow_tpu.io.batch import make_batch

    rng = np.random.default_rng(seed)
    b, k = cfg.batch_size, cfg.max_nnz
    keys = rng.integers(0, cfg.table_size, (b, k)).astype(np.int32)
    slots = rng.integers(0, cfg.max_fields, (b, k)).astype(np.int32)
    vals = np.ones((b, k), np.float32)
    mask = (rng.random((b, k)) < 0.9).astype(np.float32)
    labels = rng.integers(0, 2, b).astype(np.float32)
    weights = np.ones(b, np.float32)
    return make_batch(
        keys, slots, vals, mask, labels, weights,
        cfg.hot_size, cfg.hot_nnz,
    )


def _nr_predict(model, cfg, batch, state=None):
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    mesh = make_mesh(1)
    opt = make_optimizer(cfg)
    step = TrainStep(model, opt, cfg, mesh)
    if step.store is not None:
        state = step.store.init_device_state()
    elif state is None:
        state = init_state(model, opt, cfg, mesh)
    arrays = step.put_batch(batch, predict=True)
    return np.asarray(jax.device_get(step.predict(state, arrays))), state


@pytest.mark.parametrize("name", _NR_FAMILIES)
def test_blocks_refactor_bitwise_dense(name):
    from xflow_tpu.models import make_model

    cfg = _nr_cfg(name)
    batch = _nr_batch(cfg)
    got, state = _nr_predict(make_model(cfg), cfg, batch)
    want, _ = _nr_predict(legacy_model_for(cfg), cfg, batch, state=state)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", _NR_FAMILIES)
def test_blocks_refactor_bitwise_hot(name):
    """MXU-hot mode: frequency-head steering + the hot gather path
    (seg impl on CPU — gather-exact either way)."""
    from xflow_tpu.models import make_model

    cfg = _nr_cfg(name, hot_size_log2=6, hot_nnz=4)
    batch = _nr_batch(cfg)
    got, state = _nr_predict(make_model(cfg), cfg, batch)
    want, _ = _nr_predict(legacy_model_for(cfg), cfg, batch, state=state)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", _NR_FAMILIES)
def test_blocks_refactor_bitwise_tiered(name):
    """Tiered store mode: the hot+miss predict jit (store/hot.py) over
    a lazily materialized cold store — two independent TieredStores
    built from the same cfg/seed are deterministic, so the refactored
    and legacy models must still agree bitwise."""
    from xflow_tpu.models import make_model

    cfg = _nr_cfg(name, store_mode="tiered", hot_capacity_log2=5)
    batch = _nr_batch(cfg)
    got, _ = _nr_predict(make_model(cfg), cfg, batch)
    want, _ = _nr_predict(legacy_model_for(cfg), cfg, batch)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ("lr", "fm", "mvm"))
def test_blocks_refactor_bitwise_grads(name):
    """Explicit-gradient families: grad_logit through blocks is
    bitwise the pre-refactor gradient (the FM reference-quirk ½-scaled
    form must survive the refactor exactly)."""
    from xflow_tpu.models import make_model

    cfg = _nr_cfg(name)
    new = make_model(cfg)
    old = legacy_model_for(cfg)
    rng = np.random.default_rng(5)
    batch = {
        "keys": jnp.asarray(rng.integers(0, 100, (B, K)), jnp.int32),
        "slots": jnp.asarray(rng.integers(0, S, (B, K)), jnp.int32),
        "vals": jnp.asarray(np.ones((B, K), np.float32)),
        "mask": jnp.asarray((rng.random((B, K)) < 0.8).astype(np.float32)),
        "labels": jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
        "weights": jnp.ones(B, jnp.float32),
    }
    rows = {
        spec.name: jnp.asarray(
            rng.normal(size=(B, K, spec.dim)).astype(np.float32)
        )
        for spec in new.tables()
    }
    g_new = new.grad_logit(rows, batch)
    g_old = old.grad_logit(rows, batch)
    assert set(g_new) == set(g_old)
    for t in g_new:
        np.testing.assert_array_equal(
            np.asarray(g_new[t]), np.asarray(g_old[t])
        )

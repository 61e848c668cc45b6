"""A family whose forward reads WHICH FIELD an entry belongs to, held to
the plain reference: the program's MVM train step (models/mvm.py through
models/blocks.py::mvm_slot_terms; wire, hot/cold split, FTRL pass) against
benchmarks/reference/mvm.py + ftrl.py, and its FFM step (models/ffm.py
through blocks.ffm_field_interaction, the autodiff backward, ``v`` out of the
MXU head) against benchmarks/reference/ffm.py, through the check that
decides a benchmark cell's ``correct`` (benchmarks/harness/refcheck.py), at
a small size on the CPU.  On the CPU a float32 contraction is float32
whatever precision it asks for; that the field contraction asks for it on a
TPU is pinned in tests/test_tpu_compile.py."""

import types

import numpy as np
import pytest

from benchmarks.harness import refcheck
from benchmarks.reference import ffm, mvm
from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, init_state

MAX_FIELDS = 4  # a dozen entries a row over four fields: every field sum has terms
FFM_FIELDS = 40  # the benchmark's: a row of v is 40 x ffm_v_dim = 160 columns
BATCH = 64
FAMILIES = {"mvm": (mvm, MAX_FIELDS), "ffm": (ffm, FFM_FIELDS)}


def _system(hot_log2, impl="auto", model="mvm"):
    cfg = Config(
        model=model, optimizer="ftrl", table_size_log2=12, batch_size=BATCH,
        max_nnz=6, hot_size_log2=hot_log2, hot_nnz=6, num_devices=1, seed=3,
        max_fields=FAMILIES[model][1], ffm_v_dim=ffm.V_DIM, hot_impl=impl,
    )
    mesh = make_mesh(1)
    mdl, opt = make_model(cfg), make_optimizer(cfg)
    system = types.SimpleNamespace(  # what refcheck uses of a Trainer
        step=TrainStep(mdl, opt, cfg, mesh), state=init_state(mdl, opt, cfg, mesh)
    )
    rng = np.random.default_rng(5)
    k = cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
    batches = []
    for _ in range(3):
        keys = rng.integers(0, cfg.table_size, (BATCH, k))
        keys = np.where(rng.random(keys.shape) < 0.5, rng.integers(0, 40, keys.shape), keys)
        mask = (rng.random(keys.shape) < 0.7).astype(np.float32)
        # field ids in [0, max_fields), one in ten outside it, on both sides
        slots = rng.integers(0, cfg.max_fields, keys.shape)
        outside = rng.choice(
            [-1, cfg.max_fields, cfg.max_fields + 3], keys.shape
        )
        slots = np.where(rng.random(keys.shape) < 0.1, outside, slots)
        weights = np.ones(BATCH, np.float32)
        weights[-5:] = 0.0  # padding examples
        batches.append(make_batch(
            keys.astype(np.int32), slots.astype(np.int32), mask.copy(),
            mask, rng.integers(0, 2, BATCH).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return system, batches, cfg


# "seg" is what hot_impl=auto picks on a CPU, "mxu" what the chip runs
@pytest.mark.parametrize("impl", ["seg", "mxu"])
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_mvm_step_agrees_with_the_reference(hot_log2, impl):
    """Three steps running (the second and third from a state that is no
    longer the drawn one), within refcheck's ROWS_RTOL and LOGLOSS_ATOL."""
    system, batches, cfg = _system(hot_log2, impl)
    assert system.step._ship_slots and system.step.wire_format == "dict"
    got = refcheck.check_train_steps(system, mvm, batches, cfg)
    assert got["ok"], got
    assert all(s["touched_rows"] > 100 for s in got["steps"])
    # MVM's logit is of first order in its drawn rows: ~12 entries x 10
    # factors of N(0, 1e-2), so the first logloss lies within 2.2e-2 of ln 2
    assert got["steps"][0]["logloss"] == pytest.approx(np.log(2), abs=2.2e-2)


@pytest.mark.parametrize("impl", ["seg", "mxu"])
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_ffm_step_agrees_with_the_reference(hot_log2, impl):
    """FFM at the benchmark's widths (40 fields x 4 factors: 160 columns a
    row of v) over three steps.  With a hot table, ``w`` rides the head
    (under both of its forms) and ``v``, which opts out of it
    (TableSpec.hot=False), takes its hot occurrences as plain table rows:
    both routes end in the one gradient buffer the reference is compared
    with.  The backward is the step's autodiff arm."""
    system, batches, cfg = _system(hot_log2, impl, "ffm")
    assert system.step._ship_slots and system.step.wire_format == "dict"
    assert system.step._mxu_hot == {"w": True, "v": False}
    assert system.state["tables"]["v"]["param"].shape[1] == ffm.TABLES["v"]
    got = refcheck.check_train_steps(system, ffm, batches, cfg)
    assert got["ok"], got
    assert all(s["touched_rows"] > 100 for s in got["steps"])
    # w starts at 0 and the pair term is of second order in rows of N(0, 1e-2)
    assert got["steps"][0]["logloss"] == pytest.approx(np.log(2), abs=1e-3)


@pytest.mark.parametrize("model", ["mvm", "ffm"])
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_the_check_fails_on_field_ids_shifted_by_one(hot_log2, model, monkeypatch):
    """The reference handed every field id plus one (so field 0's entries
    read as field 1's and the last field's fall outside), and all else as
    the loader steered it: not ``ok``.  The check above cannot pass blind
    to which field an entry belongs to."""
    system, batches, cfg = _system(hot_log2, model=model)
    entries = refcheck.entries

    def shifted(batch):
        keys, x, slots = entries(batch)
        return keys, x, slots + 1

    monkeypatch.setattr(refcheck, "entries", shifted)
    got = refcheck.check_train_steps(system, FAMILIES[model][0], batches, cfg)
    assert not got["ok"]
    assert max(got["steps"][0]["rows_rel_err"].values()) > 100 * refcheck.ROWS_RTOL


def test_the_reference_is_handed_the_field_ids_hot_section_first():
    _, batches, cfg = _system(5)
    keys, x, slots = refcheck.entries(batches[0])
    assert keys.shape == x.shape == slots.shape == (BATCH, cfg.hot_nnz + cfg.max_nnz)
    assert (slots[:, : cfg.hot_nnz] == batches[0].hot_slots).all()
    assert (slots[:, cfg.hot_nnz :] == batches[0].slots).all()
    live = slots[x != 0]
    assert ((live < 0) | (live >= cfg.max_fields)).any()  # some outside, kept as drawn


def _toy_rows(case: str):
    """Six rows of eight entries over five fields, v of 5 x 4 columns."""
    rng = np.random.default_rng(11)
    b, k, f = 6, 8, 5
    slots = rng.integers(0, f, (b, k))
    mask = np.ones((b, k), np.float32)
    if case == "negative":
        slots[:, ::3] = -1 - rng.integers(0, 3, slots[:, ::3].shape)
    elif case == "beyond":
        slots[:, 1::3] = f + rng.integers(0, 3, slots[:, 1::3].shape)
    elif case == "repeated":
        slots[:, :5] = 2  # five entries of one field a row
    elif case == "masked":
        mask[:, 2::3] = 0.0
        mask[0] = 0.0  # a row with no entry at all
    elif case == "mixed":
        slots = rng.integers(-2, f + 2, (b, k))
        mask = (rng.random((b, k)) < 0.7).astype(np.float32)
    rows = {
        "w": rng.normal(size=(b, k, 1)).astype(np.float32),
        "v": rng.normal(size=(b, k, f * ffm.V_DIM)).astype(np.float32),
    }
    batch = {
        "keys": np.zeros((b, k), np.int32), "slots": slots.astype(np.int32),
        "vals": rng.uniform(0.5, 2.0, (b, k)).astype(np.float32), "mask": mask,
    }
    return rows, batch, f


@pytest.fixture
def five_fields(monkeypatch):
    monkeypatch.setitem(ffm.TABLES, "v", 5 * ffm.V_DIM)


@pytest.mark.parametrize(
    "case", ["plain", "negative", "beyond", "repeated", "masked", "mixed"]
)
def test_the_reference_ffm_is_the_sum_over_pairs(case, five_fields):
    """reference/ffm.py's sum over the pairs i < j equals the program's own
    naive oracle (FFMModel.logit_pairwise) and its field-aggregated form
    (blocks.ffm_field_interaction through FFMModel.logit), with field ids
    negative, >= max_fields, repeated within a row, and entries masked: an
    entry outside [0, max_fields) keeps its linear term and meets no other;
    and the gradient the reference hands the FTRL recurrence is that of the
    aggregated form."""
    import jax
    import jax.numpy as jnp

    from xflow_tpu.models.ffm import FFMModel

    rows, batch, f = _toy_rows(case)
    model = FFMModel(v_dim=ffm.V_DIM, max_fields=f)
    x = batch["vals"] * batch["mask"]
    want = ffm.logit(rows, x, batch["slots"], f)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    for got in (model.logit_pairwise(rows, batch), model.logit(rows, batch)):
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * scale)
    grads = ffm.grad_logit(rows, x, batch["slots"], f)
    auto = jax.grad(lambda r: jnp.sum(model.logit(r, batch)))(
        jax.tree.map(jnp.asarray, rows)
    )
    for name in ("w", "v"):
        top = float(jnp.max(jnp.abs(auto[name]))) + 1e-30
        assert float(jnp.max(jnp.abs(grads[name] - auto[name]))) <= 2e-6 * top
    outside = (batch["slots"] < 0) | (batch["slots"] >= f)
    assert not np.asarray(grads["v"])[outside | (batch["mask"] == 0)].any()

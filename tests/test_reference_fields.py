"""A family whose forward reads WHICH FIELD an entry belongs to, held to
the plain reference: the program's MVM train step (models/mvm.py through
models/blocks.py::mvm_slot_terms; wire, hot/cold split, FTRL pass) against
benchmarks/reference/mvm.py + ftrl.py through the check that decides a
benchmark cell's ``correct`` (benchmarks/harness/refcheck.py), at a small
size on the CPU.  On the CPU a float32 contraction is float32 whatever
precision it asks for; that the field contraction asks for it on a TPU is
pinned in tests/test_tpu_compile.py."""

import types

import numpy as np
import pytest

from benchmarks.harness import refcheck
from benchmarks.reference import mvm
from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, init_state

MAX_FIELDS = 4  # a dozen entries a row over four fields: every field sum has terms
BATCH = 64


def _system(hot_log2, impl="auto"):
    cfg = Config(
        model="mvm", optimizer="ftrl", table_size_log2=12, batch_size=BATCH,
        max_nnz=6, hot_size_log2=hot_log2, hot_nnz=6, num_devices=1, seed=3,
        max_fields=MAX_FIELDS, hot_impl=impl,
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
        # field ids in [0, MAX_FIELDS), one in ten outside it, on both sides
        slots = rng.integers(0, MAX_FIELDS, keys.shape)
        outside = rng.choice([-1, MAX_FIELDS, MAX_FIELDS + 3], keys.shape)
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


@pytest.mark.parametrize("hot_log2", [0, 5])
def test_the_check_fails_on_field_ids_shifted_by_one(hot_log2, monkeypatch):
    """The reference handed every field id plus one (so field 0's entries
    read as field 1's and the last field's fall outside), and all else as
    the loader steered it: not ``ok``.  The check above cannot pass blind
    to which field an entry belongs to."""
    system, batches, cfg = _system(hot_log2)
    entries = refcheck.entries

    def shifted(batch):
        keys, x, slots = entries(batch)
        return keys, x, slots + 1

    monkeypatch.setattr(refcheck, "entries", shifted)
    got = refcheck.check_train_steps(system, mvm, batches, cfg)
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

"""The program's train step against the benchmark's plain reference for the
families that own dense replicated parameters, on the CPU at toy size: the
tier-1 guard of what decides ``correct`` in ``dcn_tb.train_packed``
(benchmarks/harness/refcheck.py: rows under ROWS_RTOL, logloss under
LOGLOSS_ATOL, every dense array under DENSE_RTOL).  The CPU computes a
float32 dot in float32 whatever precision is asked, so what holds the MLP's
matmuls to float32 ON THE TPU is tests/test_tpu_compile.py (the lowered
step asks for HIGHEST) and the cell's own check on the chip; here the
mathematics is held: depth, which arrays move, which field an entry is in.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import refcheck
from benchmarks.reference import dcn_criteo, wide_deep
from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import blocks, make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, init_state

MAX_FIELDS = 4  # a dozen entries a row over four fields: every field sum has terms
DCN = {"model": "dcn", "emb_dim": dcn_criteo.EMB_DIM, "hidden_dim": 16, "cross_layers": 3}


def _system(**fields):
    """What ``refcheck.check_train_steps`` uses of a Trainer, at toy size,
    and three batches with a hot section, padding entries and examples, and
    one field id in ten outside ``[0, MAX_FIELDS)``."""
    cfg = Config(**{
        "optimizer": "ftrl", "table_size_log2": 12, "batch_size": 64,
        "max_nnz": 6, "hot_size_log2": 5, "hot_nnz": 6, "num_devices": 1,
        "seed": 3, "max_fields": MAX_FIELDS, **fields,
    })
    mesh = make_mesh(1)
    model, opt = make_model(cfg), make_optimizer(cfg)
    system = types.SimpleNamespace(
        step=TrainStep(model, opt, cfg, mesh),
        state=init_state(model, opt, cfg, mesh),
    )
    rng = np.random.default_rng(5)
    shape = (cfg.batch_size, cfg.max_nnz + cfg.hot_nnz)
    batches = []
    for _ in range(3):
        keys = rng.integers(0, cfg.table_size, shape)
        keys = np.where(rng.random(shape) < 0.5, rng.integers(0, 40, shape), keys)
        mask = (rng.random(shape) < 0.7).astype(np.float32)
        slots = rng.integers(0, MAX_FIELDS, shape)
        outside = rng.choice([-1, MAX_FIELDS, MAX_FIELDS + 3], shape)
        slots = np.where(rng.random(shape) < 0.1, outside, slots)
        weights = np.ones(cfg.batch_size, np.float32)
        weights[-5:] = 0.0  # padding examples
        batches.append(make_batch(
            keys.astype(np.int32), slots.astype(np.int32), mask.copy(), mask,
            rng.integers(0, 2, cfg.batch_size).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return system, batches, cfg


def _off(step: dict) -> set[str]:
    """The dense arrays of a checked step that are outside DENSE_RTOL."""
    return {a for a, d in step["dense"].items() if d["rel_err"] > refcheck.DENSE_RTOL}


@pytest.mark.parametrize("fields, family", [
    ({**DCN, "deep_layers": 1, "hot_impl": "seg"}, dcn_criteo),
    ({**DCN, "deep_layers": 1, "hot_impl": "mxu"}, dcn_criteo),
    ({**DCN, "deep_layers": 2, "hot_impl": "seg"}, dcn_criteo),
    ({**DCN, "deep_layers": 2, "hot_impl": "mxu"}, dcn_criteo),
    ({**DCN, "deep_layers": 3, "hot_impl": "mxu"}, dcn_criteo),
    ({"model": "wide_deep", "hot_impl": "mxu"}, wide_deep),
], ids=lambda v: "-".join(
    str(v[k]) for k in ("model", "deep_layers", "hot_impl") if k in v
) if isinstance(v, dict) else v.__name__.rsplit(".", 1)[-1])
def test_program_step_agrees_with_the_dense_reference(fields, family):
    """Three steps running, the second and third from a state that is no
    longer the drawn one: logloss, every touched row of ``w`` and ``emb`` and
    every dense array (the stack's ``w2`` / ``w3`` where it has them,
    ``cross_w``, the biases) as the reference leaves them."""
    system, batches, cfg = _system(**fields)
    got = refcheck.check_train_steps(system, family, batches, cfg)
    assert got["ok"], got
    arrays = set(system.state["dense"])
    if cfg.model == "dcn":
        stack = {f"{p}{k}" for k in range(1, cfg.deep_layers + 1) for p in "wb"}
        assert arrays == stack | {"cross_w", "cross_b", "w_out", "b_out"}
        assert family.matmuls(got["dense_shapes"]) == system.step.model.dense_matmuls()
    for step in got["steps"]:
        assert step["logloss_err"] <= refcheck.LOGLOSS_ATOL
        assert max(step["rows_rel_err"].values()) <= refcheck.ROWS_RTOL
        assert set(step["dense"]) == arrays and not _off(step)
        assert max(d["update"] for d in step["dense"].values()) > 0.0


def _freeze(system, array: str) -> None:
    """The program's step with ONE dense array left as it was."""
    real = system.step.train

    def train(state, arrays):
        before = jnp.array(state["dense"][array])  # the step donates its state
        new, metrics = real(state, arrays)
        return {**new, "dense": {**new["dense"], array: before}}, metrics

    system.step.train = train


@pytest.mark.parametrize("array", ["w1", "w2", "b2", "cross_w", "w_out"])
def test_a_dense_array_left_as_it_was_fails_by_that_array(array):
    """A step that does not move one array of the two-layer program (an
    optimizer that skips it, a gradient that never reaches it) reads exactly
    1 there in its first step and fails.  (From the second step on the arrays
    downstream of a frozen one see other gradients too.)"""
    system, batches, cfg = _system(**DCN, deep_layers=2)
    _freeze(system, array)
    got = refcheck.check_train_steps(system, dcn_criteo, batches, cfg)
    assert not got["ok"] and not any(s["ok"] for s in got["steps"])
    first = got["steps"][0]
    assert _off(first) == {array} and first["dense"][array]["rel_err"] == 1.0
    assert max(first["rows_rel_err"].values()) <= refcheck.ROWS_RTOL


def test_a_stack_without_its_second_layer_fails():
    """A reference whose deep half stops after ``w1`` (the one-layer program
    this family was until PR 39) against the two-layer program: the logit is
    another one, and ``w2`` and ``b2``, which that reference never reaches,
    stand still on its side."""
    class OneLayer:  # a reference family: hashable, as a module is
        TABLES, USES_FIELDS, DENSE = dcn_criteo.TABLES, True, True

        @staticmethod
        def logit(rows, x, slots, num_fields, dense):
            shallow = {k: v for k, v in dense.items() if k not in ("w2", "b2")}
            unused = 0.0 * (jnp.sum(dense["w2"]) + jnp.sum(dense["b2"]))
            return dcn_criteo.logit(rows, x, slots, num_fields, shallow) + unused

    one_layer = OneLayer()
    system, batches, cfg = _system(**DCN, deep_layers=2)
    got = refcheck.check_train_steps(system, one_layer, batches, cfg)
    assert not got["ok"] and not any(s["ok"] for s in got["steps"])
    first = got["steps"][0]
    assert {"w2", "b2"} <= _off(first)
    assert max(first["rows_rel_err"].values()) > refcheck.ROWS_RTOL


def test_field_ids_shifted_by_one_fail(monkeypatch):
    """The reference handed each row's field ids shifted by one place: the
    tower's sums land in other buckets, so the rows and the first layer miss."""
    system, batches, cfg = _system(**DCN, deep_layers=2)
    entries = refcheck.entries

    def shifted(batch):
        keys, x, slots = entries(batch)
        return keys, x, np.roll(slots, 1, axis=1)

    monkeypatch.setattr(refcheck, "entries", shifted)
    got = refcheck.check_train_steps(system, dcn_criteo, batches, cfg)
    assert not got["ok"]
    first = got["steps"][0]
    assert max(first["rows_rel_err"].values()) > 100 * refcheck.ROWS_RTOL
    assert "w1" in _off(first)


def _toy_dense_batch(seed: int = 0, b: int = 7, k: int = 5, f: int = 3, e: int = 4):
    rng = np.random.default_rng(seed)
    rows = {
        "w": jnp.asarray(rng.normal(0, 1, (b, k, 1)), jnp.float32),
        "emb": jnp.asarray(rng.normal(0, 1, (b, k, e)), jnp.float32),
    }
    batch = {
        "vals": jnp.ones((b, k), jnp.float32),
        "mask": jnp.asarray(rng.random((b, k)) < 0.8, jnp.float32),
        "slots": jnp.asarray(rng.integers(-1, f + 1, (b, k)), jnp.int32),
    }
    return rows, batch


def test_one_layer_stack_is_bitwise_the_head_it_came_from():
    """``mlp_head`` through ``mlp_stack`` and ``dense_dot`` against the
    expression it was until PR 39, and the draw of its parameters against
    the draw it was: bit for bit (on the CPU precision changes nothing)."""
    rng = jax.random.PRNGKey(11)
    dense = blocks.mlp_head_init(rng, 12, 8)
    k1, k2 = jax.random.split(rng)
    was = {
        "w1": jax.random.normal(k1, (12, 8), jnp.float32) * jnp.sqrt(2.0 / 12),
        "b1": jnp.zeros((8,), jnp.float32),
        "w2": jax.random.normal(k2, (8, 1), jnp.float32) * jnp.sqrt(1.0 / 8),
        "b2": jnp.zeros((1,), jnp.float32),
    }
    assert list(dense) == list(was)
    jax.tree.map(np.testing.assert_array_equal, dense, was)
    dense = jax.tree.map(lambda a: a + 0.25, dense)  # biases off zero
    h = jax.random.normal(jax.random.PRNGKey(12), (9, 12), jnp.float32)
    hidden = jax.nn.relu(h @ dense["w1"] + dense["b1"])
    np.testing.assert_array_equal(
        blocks.mlp_head(dense, h), (hidden @ dense["w2"] + dense["b2"])[:, 0]
    )
    np.testing.assert_array_equal(blocks.mlp_stack(dense, h, 1), hidden)


def test_dcn_at_one_deep_layer_is_bitwise_the_family_it_was():
    """``deep_layers`` 1: the dense pytree's keys and drawn values, and the
    logit, are those of the family before it had a stack (its ``dense_init``
    and ``logit`` as PR 38's tree wrote them)."""
    from xflow_tpu.models.dcn import DCNModel

    model = DCNModel(emb_dim=4, hidden=8, cross_layers=2, max_fields=3)
    rng = jax.random.PRNGKey(5)
    dense = model.dense_init(rng)
    kc, k1, ko = jax.random.split(rng, 3)
    p = 3 * 4
    was = {
        "cross_w": jax.random.normal(kc, (2, p), jnp.float32) * jnp.sqrt(1.0 / p),
        "cross_b": jnp.zeros((2, p), jnp.float32),
        "w1": jax.random.normal(k1, (p, 8), jnp.float32) * jnp.sqrt(2.0 / p),
        "b1": jnp.zeros((8,), jnp.float32),
        "w_out": jax.random.normal(ko, (p + 8, 1), jnp.float32) * jnp.sqrt(1.0 / (p + 8)),
        "b_out": jnp.zeros((1,), jnp.float32),
    }
    assert sorted(dense) == sorted(was)
    jax.tree.map(np.testing.assert_array_equal, dense, was)
    dense = jax.tree.map(lambda a: a + 0.125, dense)
    rows, batch = _toy_dense_batch()
    x = blocks.masked_x(batch)
    x0 = blocks.flatten_tower(
        blocks.field_sum_tower(rows["emb"], x, batch["slots"], 3)
    )
    xc = blocks.cross_network(x0, dense["cross_w"], dense["cross_b"])
    h = jax.nn.relu(x0 @ dense["w1"] + dense["b1"])
    out = (jnp.concatenate([xc, h], axis=-1) @ dense["w_out"] + dense["b_out"])[:, 0]
    np.testing.assert_array_equal(
        model.logit(rows, batch, dense), blocks.linear_term(rows["w"], x) + out
    )
    # the second layer draws beside the first, which keeps its draw
    deeper = DCNModel(
        emb_dim=4, hidden=8, cross_layers=2, deep_layers=2, max_fields=3
    ).dense_init(rng)
    assert sorted(deeper) == sorted([*was, "w2", "b2"])
    np.testing.assert_array_equal(deeper["w1"], was["w1"])
    assert deeper["w2"].shape == (8, 8) and float(jnp.std(deeper["w2"])) > 0.2


def test_config_refuses_a_stack_of_no_layers():
    with pytest.raises(ValueError, match="deep_layers"):
        Config(model="dcn", deep_layers=0)


def test_a_two_layer_stack_survives_checkpoint_and_artifact(toy_dataset, tmp_path):
    """utils/checkpoint.py and serve/artifact.py carry ``state["dense"]`` as
    a pytree, whatever its keys: a DCN of two hidden layers restores bit for
    bit from its checkpoint, and the engine loaded from its exported artifact
    scores a raw batch as the trainer does.  Neither file knows of
    ``deep_layers``."""
    from xflow_tpu.io.loader import ShardLoader
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        train_path=toy_dataset.train_prefix, test_path=toy_dataset.test_prefix,
        model="dcn", emb_dim=4, hidden_dim=8, cross_layers=2, deep_layers=2,
        epochs=2, batch_size=64, table_size_log2=14, max_nnz=24,
        max_fields=12, num_devices=1, checkpoint_dir=str(tmp_path / "ck"),
    )
    with Trainer(cfg) as trainer:
        trainer.train()
        before = jax.device_get(trainer.state["dense"])
        assert {"w1", "b1", "w2", "b2"} <= set(before)
        assert float(np.abs(before["b2"]).max()) > 0.0  # the layer trained
        with Trainer(cfg) as again:
            assert again.restore() is not None
            jax.tree.map(
                np.testing.assert_array_equal, before,
                jax.device_get(again.state["dense"]),
            )
        art = str(tmp_path / "artifact")
        export_artifact(trainer, art)
        engine = PredictEngine.load(art, buckets=(64,), warm=True)
        loader = ShardLoader(
            cfg.test_path + "-00000", batch_size=cfg.batch_size,
            max_nnz=cfg.max_nnz, table_size=cfg.table_size,
            parse_fn=trainer._parse_fn(),
        )
        batch, _ = next(iter(loader.iter_batches()))
        want = np.asarray(jax.device_get(trainer.step.predict(
            trainer.state, trainer.step.put_batch(trainer.prepare_batch(batch))
        )))
        np.testing.assert_allclose(engine.predict(batch), want, atol=1e-6)

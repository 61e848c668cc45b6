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
from benchmarks.reference import (
    autoint_criteo, dcn_criteo, fibinet_criteo, fm, wide_deep, xdeepfm_criteo,
)
from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import blocks, make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, init_state

MAX_FIELDS = 4  # a dozen entries a row over four fields: every field sum has terms
DCN = {"model": "dcn", "emb_dim": dcn_criteo.EMB_DIM, "hidden_dim": 16, "cross_layers": 3}
# rows drawn at 0.3: the CIN's gradients are of second to fourth order in the
# embeddings, and at 0.01 an update of ``cin_w2`` / ``cin_w3`` is under one
# float32 step of the weight, which the dense measure cannot see
XDEEPFM = {
    "model": "xdeepfm", "emb_dim": xdeepfm_criteo.EMB_DIM, "hidden_dim": 16,
    "cross_layers": 3, "cin_maps": 8, "deep_layers": 2, "v_init_scale": 0.3,
    "sgd_lr": 0.05,
}
# rows drawn at 0.3 for xDeepFM's reason: the gradient of ``attn_q`` /
# ``attn_k`` is of third order in the embeddings.  Eight fields, of which the
# batches fill four and, as out-of-range ids of the other families, two more:
# fields 5 and 6 are absent from every row
AUTOINT = {
    "model": "autoint", "emb_dim": autoint_criteo.EMB_DIM,
    "attn_heads": autoint_criteo.HEADS, "attn_dim": 4, "cross_layers": 2,
    "max_fields": 8, "v_init_scale": 0.3, "sgd_lr": 0.05,
}
# rows drawn at 0.3 for xDeepFM's reason: every path from ``emb`` to FiBiNET's
# logit is a product of two embeddings.  Eight fields (fields 4 to 7 absent
# from every row, as the benchmark's 40th bucket) and a reduction of 1: the
# excitation keeps eight hidden units, where the 2 that r = 3 leaves of 8
# would all be dead (every gate's argument exactly 0: a tie, counted) in a
# quarter of the examples, over ``TIE_SHARE_MAX``; r = 3 runs in the cases
# below that call the model alone
FIBINET = {
    "model": "fibinet", "emb_dim": fibinet_criteo.EMB_DIM, "hidden_dim": 16,
    "deep_layers": 3, "senet_reduction": 1, "max_fields": 8, "v_init_scale": 0.3,
    "sgd_lr": 0.05,
}


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
    ({**XDEEPFM, "hot_impl": "seg"}, xdeepfm_criteo),
    ({**XDEEPFM, "hot_impl": "mxu"}, xdeepfm_criteo),
    ({**AUTOINT, "hot_impl": "seg"}, autoint_criteo),
    ({**AUTOINT, "hot_impl": "mxu"}, autoint_criteo),
    ({**FIBINET, "hot_impl": "seg"}, fibinet_criteo),
    ({**FIBINET, "hot_impl": "mxu"}, fibinet_criteo),
    ({**FIBINET, "hot_impl": "mxu", "wire_dedup": "off"}, fibinet_criteo),
], ids=lambda v: "-".join(
    str(v[k]) for k in ("model", "deep_layers", "hot_impl", "wire_dedup") if k in v
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
    if cfg.model == "xdeepfm":
        stack = {f"{p}{k}" for k in range(1, cfg.deep_layers + 1) for p in "wb"}
        cin = {f"cin_w{k}" for k in range(1, cfg.cross_layers + 1)}
        assert arrays == stack | cin | {"w_out", "b_out"}
        assert system.state["dense"]["cin_w2"].shape == (8, 8, MAX_FIELDS)
        # every CIN array moves by enough of its own float32 steps to be seen
        assert all(s["dense"][a]["update_ulps"] > 50 for s in got["steps"] for a in cin)
    if cfg.model == "autoint":
        layers = range(1, cfg.cross_layers + 1)
        attn = {f"attn_{p}{k}" for k in layers for p in "qkvr"}
        assert arrays == attn | {"w_out", "b_out"}
        assert system.state["dense"]["attn_q1"].shape == (autoint_criteo.EMB_DIM, 8)
        assert system.state["dense"]["attn_k2"].shape == (8, 8)
        assert system.state["dense"]["w_out"].shape == (8 * 8, 1)
        # every projection moves by enough of its own float32 steps to be seen
        assert all(s["dense"][a]["update_ulps"] > 1000 for s in got["steps"] for a in attn)
    if cfg.model == "fibinet":
        stack = {f"{p}{k}" for k in range(1, cfg.deep_layers + 1) for p in "wb"}
        block = {"senet_w1", "senet_w2", "bil_p", "bil_q"}
        assert arrays == stack | block | {"w_out", "b_out"}
        # the dictionary wire by default, the compact wire where it is off
        want = "compact" if cfg.wire_dedup == "off" else "dict"
        assert system.step.wire_format == want
        assert system.state["dense"]["bil_q"].shape == (28, 10, 10)
        assert system.state["dense"]["w1"].shape == (2 * 28 * 10, 16)
        # every array of the block moves in every step
        assert all(s["dense"][a]["update"] > 0.0 for s in got["steps"] for a in block)
    if cfg.model in ("dcn", "xdeepfm", "autoint", "fibinet"):
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


@pytest.mark.parametrize("array, fields, family", [
    *((a, {**DCN, "deep_layers": 2}, dcn_criteo) for a in ["w1", "w2", "b2", "cross_w", "w_out"]),
    *((a, XDEEPFM, xdeepfm_criteo) for a in ["cin_w1", "cin_w2", "cin_w3"]),
    *((a, AUTOINT, autoint_criteo) for a in ["attn_q2", "attn_k1", "attn_v2", "attn_r1"]),
    *((a, FIBINET, fibinet_criteo) for a in ["senet_w1", "senet_w2", "bil_p", "bil_q"]),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_dense_array_left_as_it_was_fails_by_that_array(array, fields, family):
    """A step that does not move one array of the two-layer program (an
    optimizer that skips it, a gradient that never reaches it) reads exactly
    1 there in its first step and fails: DCN's arrays, each of xDeepFM's
    three-dimensional CIN weights, a query, a key, a value and a residual
    projection of AutoInt's, and FiBiNET's two excitation matrices and two
    towers of pair matrices.  (From the second step on the arrays
    downstream of a frozen one see other gradients too.)"""
    system, batches, cfg = _system(**fields)
    _freeze(system, array)
    got = refcheck.check_train_steps(system, family, batches, cfg)
    assert not got["ok"] and not any(s["ok"] for s in got["steps"])
    first = got["steps"][0]
    assert _off(first) == {array} and first["dense"][array]["rel_err"] == 1.0
    assert max(first["rows_rel_err"].values()) <= refcheck.ROWS_RTOL


def test_the_cells_own_constants_let_the_check_see_the_cin(capsys):
    """``xdeepfm_tb.train_packed``'s own configuration (its FTRL constants,
    ``sgd_lr`` and init: what the toy cases above replace by rows drawn at
    0.3), rehearsed at the paper's widths: after two epochs every ``cin_w``
    moves by thousands of its own float32 steps a step, and the control
    (``benchmarks/control.py``: the reference with its operands rounded to
    bfloat16) is out of ``DENSE_RTOL`` in EVERY one of them.  Under the other
    configurations' ``beta`` 1 / ``lambda2`` 10 the embeddings stayed at 1e-7
    - 1e-4, ``cin_w2`` / ``cin_w3`` moved by 1 and 0 steps, and a CIN at
    default precision passed the cell's check on the chip (PERF.md section 2)."""
    import json

    from benchmarks import control
    from benchmarks.harness import manifest

    fields = manifest.config_file("benchmarks/configs/xdeepfm_ftrl_criteo_tb.json")
    assert fields["beta"] * fields["batch_size"] == 1.0
    assert fields["lambda2"] * fields["batch_size"] == 10.0
    argv = ["--workload", "xdeepfm_tb.train_packed", "--seed", "7",
            "--seconds", "0.5", "--rehearsal"]
    assert control.main(argv) == 0  # the control failed, as it must
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["checks_failed"] == ["steps_match_reference"]
    compared = line["compared"]
    for k in (1, 2, 3):
        assert compared[f"dense_update_ulps.cin_w{k}"]["value"] >= 1000
        assert compared[f"dense_rel_err.cin_w{k}"]["value"] > 4 * refcheck.DENSE_RTOL


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


def _cin_case(b: int = 13, m: int = 8, d: int = 3, maps: int = 5, empty=()):
    """A tower [b, m, d] (the fields of ``empty`` all zero, as a bucket no
    row fills) and three layers' weights; values of order 1."""
    rng = np.random.default_rng(7)
    tower = rng.normal(0, 0.5, (b, m, d)).astype(np.float32)
    tower[:, list(empty), :] = 0.0
    weights = [
        jnp.asarray(rng.normal(0, 0.3, (maps, h, m)), jnp.float32)
        for h in (m, maps, maps)
    ]
    return weights, jnp.asarray(tower)


def _plain_cin(weights, tower):
    """The CIN as its equation: one einsum a layer, every layer pooled."""
    xk, pooled = tower, []
    for w in weights:
        xk = jnp.einsum("hij,bid,bjd->bhd", w, xk, tower)
        pooled.append(jnp.sum(xk, axis=-1))
    return jnp.concatenate(pooled, axis=-1)


@pytest.mark.parametrize("slice_rows", [1, 4, 13], ids=["one-row", "uneven", "whole"])
def test_sliced_cin_equals_the_plain_einsum_in_value_and_gradients(slice_rows):
    """``blocks.cin_stack`` (slices of the batch through ``lax.map``, pairs
    along the lanes, each slice's backward rematerialised) against one plain
    einsum a layer: the pooled maps, and the gradient of every layer's
    weights and of the tower, for a slice of one row, a slice that does not
    divide the batch (the last is padded) and the whole batch."""
    weights, tower = _cin_case()
    want = _plain_cin(weights, tower)
    got = blocks.cin_stack(weights, tower, slice_rows)
    assert got.shape == want.shape == (13, 15)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    mix = jnp.asarray(np.random.default_rng(8).normal(size=want.shape), jnp.float32)
    grads = [
        jax.grad(lambda w, t: jnp.sum(f(w, t) * mix), (0, 1))(weights, tower)
        for f in (lambda w, t: blocks.cin_stack(w, t, slice_rows), _plain_cin)
    ]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=0)


def test_an_empty_field_leaves_its_cin_weights_unmoved():
    """max_fields counts a bucket more than the rows have fields (40 for
    39): that row of X^0 is zero, so no gradient reaches the weights that
    multiply it, on either side: ``w[:, :, j]`` of every layer and
    ``w1[:, j, :]`` of the first (whose maps ARE the fields)."""
    weights, tower = _cin_case(empty=(7,))
    grads = jax.grad(
        lambda w: jnp.sum(jnp.sin(blocks.cin_stack(w, tower, 4)))
    )(weights)
    for g in grads:
        assert float(jnp.abs(g).max()) > 0.0
        np.testing.assert_array_equal(g[:, :, 7], 0.0)
    np.testing.assert_array_equal(grads[0][:, 7, :], 0.0)


def test_cin_slice_is_sized_from_shapes_in_whole_lane_widths():
    """``blocks.cin_slice_rows``: at the paper's Criteo sizes a slice of 128
    examples (a 41 MB pair tensor under the 64 MiB the block allows), a
    small batch whole, a narrow layer more lane widths."""
    assert blocks.cin_slice_rows(16384, 10, 40, 200) == 128
    assert 4 * 128 * 200 * 40 * 10 <= blocks.CIN_PAIR_BYTES
    assert blocks.cin_slice_rows(64, 10, 4, 8) == 64
    assert blocks.cin_slice_rows(16384, 10, 40, 100) == 384
    model = make_model(Config(
        model="xdeepfm", emb_dim=10, max_fields=40, cin_maps=200, cross_layers=3
    ))
    assert model.cin_slice_rows(16384) == 128
    assert model.cin_widths() == [(40, 200), (200, 200), (200, 200)]


def test_config_refuses_a_cin_without_maps():
    with pytest.raises(ValueError, match="cin_maps"):
        Config(model="xdeepfm", cin_maps=0)


def test_xdeepfm_survives_checkpoint_and_artifact_and_serves_the_reference(
    toy_dataset, tmp_path
):
    """utils/checkpoint.py, serve/artifact.py and serve/engine.py carry
    ``state["dense"]`` as a pytree whatever its arrays' ranks: xDeepFM's
    three-dimensional ``cin_w`` restore bit for bit from the checkpoint, and
    the engine loaded from the exported artifact scores a raw batch as the
    trainer does AND as the benchmark's reference's ``logit`` does from the
    trained state (through train.py's own entry, ``xflow_tpu.train.main``)."""
    from xflow_tpu import train
    from xflow_tpu.io.loader import ShardLoader
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.trainer import Trainer

    fields = dict(
        train_path=toy_dataset.train_prefix, test_path=toy_dataset.test_prefix,
        model="xdeepfm", emb_dim=xdeepfm_criteo.EMB_DIM, hidden_dim=8,
        cross_layers=2, cin_maps=6, deep_layers=2, v_init_scale=0.3,
        sgd_lr=0.05, epochs=2, batch_size=64, table_size_log2=14, max_nnz=24,
        max_fields=12, num_devices=1, checkpoint_dir=str(tmp_path / "ck"),
    )
    cfg = Config(**fields)
    with Trainer(cfg) as trainer:
        drawn = jax.device_get(trainer.state["dense"])
        trainer.train()
        before = jax.device_get(trainer.state["dense"])
        assert before["cin_w2"].shape == (6, 6, 12)
        for k in (1, 2):  # the CIN trained
            assert float(np.abs(before[f"cin_w{k}"] - drawn[f"cin_w{k}"]).max()) > 0.0
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
        got = engine.predict(batch)
        np.testing.assert_allclose(got, want, atol=1e-6)
        # the reference's logit over the same rows of the trained tables
        keys, x, slots = refcheck.entries(trainer.prepare_batch(batch))
        tables = jax.device_get(trainer.state["tables"])
        rows = {t: jnp.asarray(tables[t]["param"][keys]) for t in tables}
        ref = xdeepfm_criteo.logit(
            rows, jnp.asarray(x), jnp.asarray(slots), cfg.max_fields, before
        )
        real = batch.weights > 0
        np.testing.assert_allclose(
            got[real], np.asarray(jax.nn.sigmoid(ref))[real], atol=2e-6
        )
    # the CLI's own entry reaches the family and its one new field
    args = train.build_parser().parse_args([
        "--model", "xdeepfm", "--cin-maps", "6", "--cross-layers", "2",
        "--train", toy_dataset.train_prefix,
    ])
    assert (args.model, args.cin_maps, args.cross_layers) == ("xdeepfm", 6, 2)


# -- AutoInt: field self-attention, a presence mask, a sliced stack -----------


def _attention_case(
    b: int = 13, m: int = 8, d: int = 4, absent=(7,), head: int = 4,
    layers: int = 2, empty=(),
):
    """A tower [b, m, d] and its presence [b, m] (the fields of ``absent``
    lacking from every row, two more lacking from a row each, as a dropped
    entry leaves them, and the rows of ``empty`` lacking EVERY field), and
    ``layers`` layers of two heads of ``head``; values of order 1."""
    rng = np.random.default_rng(7)
    present = np.ones((b, m), np.float32)
    present[:, list(absent)] = 0.0
    present[2, 1] = present[5, 3] = 0.0
    present[list(empty)] = 0.0
    tower = rng.normal(0, 0.5, (b, m, d)).astype(np.float32) * present[..., None]
    width = 2 * head
    weights = [
        tuple(
            jnp.asarray(rng.normal(0, 0.4 * np.sqrt(8 / width), (d_in, width)), jnp.float32)
            for _ in "qkvr"
        )
        for d_in in [d] + [width] * (layers - 1)
    ]
    return weights, jnp.asarray(tower), jnp.asarray(present)


def _plain_attention(weights, tower, present, heads: int = 2):
    """The interacting layers as their equations, the whole batch at once,
    through the REFERENCE's layer (benchmarks/reference/autoint_criteo.py)."""
    assert heads == autoint_criteo.HEADS
    e = tower
    for layer in weights:
        e = autoint_criteo.layer(*layer, e, present > 0)
    return e


# the paper's Criteo shape of a slice (40 fields, 2 heads of 32, d = 16):
# the lane form at its real tile.  A projection's gradient there is a
# float32 sum over 10^4 (example, field) pairs, taken in another order on
# each side: 3e-6 of the largest where the toy cases hold 1e-6
_PAPER_SLICE = {"m": 40, "d": 16, "absent": (39,), "head": 32, "layers": 3}


@pytest.mark.parametrize("slice_rows,case,tol", [
    (1, {}, 1e-6), (4, {}, 1e-6), (13, {}, 1e-6),
    (128, {"b": 256, **_PAPER_SLICE}, 3e-6),
    (256, {"b": 384, **_PAPER_SLICE}, 3e-6),
    (72, {"b": 200, "empty": (9, 150), **_PAPER_SLICE}, 3e-6),
    (4, {"empty": (6,)}, 1e-6),
], ids=[
    "one-row", "uneven", "whole", "lanes-128", "lanes-256", "off-lanes",
    "no-field",
])
def test_sliced_attention_equals_the_plain_layers_in_value_and_gradients(
    slice_rows, case, tol
):
    """``blocks.field_attention_stack`` (slices of the batch through
    ``lax.map``, the slice's examples along the lanes, each slice's
    backward rematerialised) against the plain layers over the whole
    batch: the fields' vectors, and the gradient of every projection and
    of the tower, within ``tol`` of the largest, for a slice of one row, a
    slice that does not divide the batch (the last is padded with rows
    that have no field) and the whole batch; at the paper's Criteo shape
    for a slice of one lane width, of two (the last padded) and of 72
    examples (no whole lane width); and with rows that have NO present
    field, which give zeros and no NaN, and neither take a gradient nor
    add to one: the plain layers never see them (their softmax has
    nothing to run over), and the projections' gradients are those of
    the batch without them."""
    weights, tower, present = _attention_case(**case)
    empty = np.array(case.get("empty", ()), int)
    full = np.setdiff1d(np.arange(tower.shape[0]), empty)
    want = _plain_attention(weights, tower[full], present[full])
    got = blocks.field_attention_stack(weights, tower, present, 2, slice_rows)
    assert got.shape == (tower.shape[0], *want.shape[1:])
    np.testing.assert_allclose(
        got[full], want, rtol=0, atol=tol * float(jnp.abs(want).max())
    )
    np.testing.assert_array_equal(got[empty], 0.0)
    mix = jnp.asarray(np.random.default_rng(8).normal(size=got.shape), jnp.float32)
    d_w, d_tower = jax.grad(
        lambda w, t: jnp.sum(
            blocks.field_attention_stack(w, t, present, 2, slice_rows) * mix
        ), (0, 1),
    )(weights, tower)
    want_w, want_tower = jax.grad(
        lambda w, t: jnp.sum(_plain_attention(w, t, present[full]) * mix[full]),
        (0, 1),
    )(weights, tower[full])
    np.testing.assert_array_equal(d_tower[empty], 0.0)
    for a, b in zip(
        jax.tree.leaves((d_w, d_tower[full])), jax.tree.leaves((want_w, want_tower))
    ):
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.abs(b).max()), rtol=0)


@pytest.mark.parametrize("h,r,g,a,s", [
    (2, 32, 40, 40, 128), (2, 40, 32, 40, 256), (2, 4, 8, 8, 13), (1, 3, 11, 5, 72),
], ids=["scores", "mix-two-tiles", "toy", "ragged"])
def test_the_lane_contraction_kernel_is_the_multiply_and_sum(h, r, g, a, s):
    """``blocks._lane_contract``'s Mosaic kernel (what the TPU runs; here
    under the Pallas TPU interpreter) against XLA's multiply and sum (what
    every other backend runs, and what the tests above hold to the plain
    layers): the scores' shape at the paper's sizes, the weighted sum's
    over two lane tiles, a toy slice of 13 examples (one block, no whole
    lane width) and a count of rows that is no multiple of the eight sums
    the kernel holds at a time; float32 sums in two orders."""
    from jax.experimental.pallas import tpu as pltpu

    rng = np.random.default_rng(9)
    rows = jnp.asarray(rng.normal(size=(h, r, g, s)), jnp.float32)
    tiles = jnp.asarray(rng.normal(size=(h, r, a, s)), jnp.float32)
    want = blocks._lane_contract_xla(rows, tiles)
    with pltpu.force_tpu_interpret_mode():
        got = blocks._lane_contract_tpu(rows, tiles)
    assert got.shape == want.shape == (h, g, a, s)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))


def _autoint_rows(model, rng, b: int = 6, k: int = 10):
    """Gathered rows and a batch for ``model``: every row has one entry of
    each of the first ``max_fields - 1`` fields (the last bucket is empty, as
    the benchmark's 40th), and row 0 one entry more of field 2."""
    m = model.max_fields
    slots = np.tile(np.arange(k) % (m - 1), (b, 1)).astype(np.int32)
    mask = np.ones((b, k), np.float32)
    mask[:, m - 1:] = 0.0
    mask[0, m - 1], slots[0, m - 1] = 1.0, 2
    batch = {
        "slots": jnp.asarray(slots), "vals": jnp.ones((b, k), jnp.float32),
        "mask": jnp.asarray(mask),
    }
    rows = {"emb": jnp.asarray(rng.normal(0, 0.5, (b, k, model.emb_dim)), jnp.float32)}
    return rows, batch


def _autoint_model(**fields):
    return make_model(Config(**{
        "model": "autoint", "emb_dim": autoint_criteo.EMB_DIM, "attn_heads": 2,
        "attn_dim": 4, "cross_layers": 2, "max_fields": 8, **fields,
    }))


def test_a_row_that_lacks_a_field_scores_as_the_reference_with_the_entry_deleted():
    """A row whose entry of field 4 was dropped (value 0: what the capacity
    rule leaves) scores what the reference scores for the row WITHOUT that
    entry: field 4 takes no attention weight and adds nothing.  Were its key
    left in (a zero vector at weight e^0) the logit would be another: the
    unmasked layer differs by far more than the tolerance."""
    model = _autoint_model()
    rng = np.random.default_rng(3)
    rows, batch = _autoint_rows(model, rng)
    dense = jax.tree.map(
        lambda a: a + 0.05, model.dense_init(jax.random.PRNGKey(2))
    )
    mask = np.array(batch["mask"])
    mask[1, 4] = 0.0  # row 1 loses its entry of field 4
    got = model.logit(rows, {**batch, "mask": jnp.asarray(mask)}, dense)
    keep = np.array([c for c in range(10) if c != 4])  # deleted, not zeroed
    x = np.array(batch["vals"] * batch["mask"])
    want = autoint_criteo.logit(
        {"emb": rows["emb"][1:2, keep]}, jnp.asarray(x[1:2, keep]),
        batch["slots"][1:2, keep], model.max_fields, dense,
    )
    np.testing.assert_allclose(got[1], want[0], rtol=0, atol=2e-6)
    whole = autoint_criteo.logit(
        rows, jnp.asarray(x), batch["slots"], model.max_fields, dense
    )
    others = np.array([0, 2, 3, 4, 5])
    np.testing.assert_allclose(got[others], whole[others], rtol=0, atol=2e-6)
    assert abs(float(whole[1] - want[0])) > 1e-3  # the field's presence matters

    # the mask left out: every key attended to, the empty bucket's too
    import unittest.mock

    masked = blocks.field_attention_layer

    def unmasked(wq, wk, wv, wr, e, present, heads):
        return masked(wq, wk, wv, wr, e, jnp.ones_like(present), heads)

    with unittest.mock.patch.object(blocks, "field_attention_layer", unmasked):
        off = model.logit(rows, batch, dense)
    assert float(jnp.abs(off - whole).max()) > 1e-3


def test_no_gradient_reaches_the_empty_buckets_slice_of_w_out():
    """``max_fields`` counts a bucket more than the rows have fields (40 for
    39): that field's ``e^Res`` is 0 on every row, so its slice of ``w_out``
    sees no gradient, while every other slice does; and nothing that field
    holds reaches the logit."""
    model = _autoint_model()
    rows, batch = _autoint_rows(model, np.random.default_rng(4))
    dense = model.dense_init(jax.random.PRNGKey(2))
    grads = jax.grad(lambda d: jnp.sum(jnp.sin(model.logit(rows, batch, d))))(dense)
    by_field = np.asarray(grads["w_out"]).reshape(model.max_fields, model.width)
    np.testing.assert_array_equal(by_field[-1], 0.0)
    assert (np.abs(by_field[:-1]).max(axis=1) > 0.0).all()
    assert all(float(jnp.abs(g).max()) > 0.0 for g in grads.values())


def test_a_row_with_no_field_at_all_stays_finite():
    """None in the benchmark's rows, possible in a user's (and what pads the
    last slice): every key of the row is absent, the softmax has nothing to
    normalise over, and the row's logit is ``b_out``; value and every
    gradient stay finite."""
    model = _autoint_model()
    rows, batch = _autoint_rows(model, np.random.default_rng(5))
    mask = np.array(batch["mask"])
    mask[3] = 0.0
    batch = {**batch, "mask": jnp.asarray(mask)}
    dense = {**model.dense_init(jax.random.PRNGKey(2)), "b_out": jnp.full((1,), 0.25)}
    logit = model.logit(rows, batch, dense)
    assert float(logit[3]) == 0.25 and bool(jnp.isfinite(logit).all())
    grads = jax.grad(
        lambda r, d: jnp.sum(jnp.sin(model.logit(r, batch, d))), (0, 1)
    )(rows, dense)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    np.testing.assert_array_equal(grads[0]["emb"][3], 0.0)


def test_attention_slice_is_sized_from_shapes_in_whole_lane_widths():
    """``blocks.attn_slice_rows``: at the paper's Criteo sizes a slice's
    activations (225 KiB an example over three layers) inside
    ``ATTN_SLICE_BYTES``, a power of two that divides B = 16384 (no padded
    slice, no copy of the stack's output); a small batch whole; the model
    hands the same number to the stack and to the step's counters."""
    rows = blocks.attn_slice_rows(16384, 40, 2, 32, 3)
    assert rows % 128 == 0 and 16384 % rows == 0
    assert rows * 4 * 3 * (5 * 40 * 64 + 2 * 2 * 40 * 40) <= blocks.ATTN_SLICE_BYTES
    assert blocks.attn_slice_rows(64, 8, 2, 4, 2) == 64
    assert blocks.attn_slice_rows(16384, 20, 2, 32, 3) == 2 * rows == 256  # fewer fields
    model = _autoint_model(emb_dim=16, attn_dim=32, cross_layers=3, max_fields=40)
    assert model.attn_slice_rows(16384) == rows
    assert model.layer_inputs() == [16, 64, 64]
    counters = model.dense_counters(16384)
    assert counters == {
        "dense.attn_flops": 6 * 16384 * 2088960,
        "dense.attn_score_bytes": 4 * 16384 * 3 * 2 * 2 * 40 * 40,
        "dense.attn_slice_rows": rows,
    }
    assert sum(k * n for k, n in model.dense_matmuls()) == 2091520
    shapes = jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
    assert len(shapes) == 14 and sum(a.size for a in shapes.values()) == 39425


def test_config_refuses_attention_without_heads_or_width():
    for field in ("attn_heads", "attn_dim"):
        with pytest.raises(ValueError, match="attn_heads and attn_dim"):
            Config(model="autoint", **{field: 0})


def test_the_cells_own_constants_let_the_check_see_the_attention(capsys):
    """``autoint_tb.train_packed``'s own configuration (its FTRL constants
    over the batch size as xDeepFM's, ``sgd_lr`` 0.1 and the init: what the
    toy cases above replace by rows drawn at 0.3), rehearsed at the paper's
    widths: after two toy epochs every query and key projection already
    moves by a thousand or more of its own float32 steps a step (on the chip,
    after a window's training, by 3.9e4 or more: PERF.md section 2; at the
    paper's 1e-3, Adam's there, by 5 to 90), and the control
    (``benchmarks/control.py``: the reference with its operands rounded to
    bfloat16) is out of ``DENSE_RTOL`` in EVERY one of the fourteen arrays."""
    import json

    from benchmarks import control
    from benchmarks.harness import manifest

    fields = manifest.config_file("benchmarks/configs/autoint_ftrl_criteo_tb.json")
    assert fields["beta"] * fields["batch_size"] == 1.0
    assert fields["lambda2"] * fields["batch_size"] == 10.0
    assert fields["sgd_lr"] == 0.1
    argv = ["--workload", "autoint_tb.train_packed", "--seed", "7",
            "--seconds", "0.5", "--rehearsal"]
    assert control.main(argv) == 0  # the control failed, as it must
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["checks_failed"] == ["steps_match_reference"]
    compared = line["compared"]
    arrays = [k.split(".", 1)[1] for k in compared if k.startswith("dense_rel_err.")]
    assert len(arrays) == 14
    for array in arrays:
        assert compared[f"dense_update_ulps.{array}"]["value"] >= 1000
        assert compared[f"dense_rel_err.{array}"]["value"] > 4 * refcheck.DENSE_RTOL


def test_autoint_survives_checkpoint_and_artifact_and_serves_the_reference(
    toy_dataset, tmp_path
):
    """The family through the rest of the system's normal path: it trains
    through ``Trainer.train``, its fourteen dense arrays restore bit for bit
    from the checkpoint, the engine loaded from the exported artifact (whose
    batches carry the field ids the presence mask reads) scores a raw batch
    as the trainer does AND as the benchmark's reference's ``logit`` does
    from the trained state, and train.py's CLI reaches the two new fields."""
    from xflow_tpu import train
    from xflow_tpu.io.loader import ShardLoader
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        train_path=toy_dataset.train_prefix, test_path=toy_dataset.test_prefix,
        model="autoint", emb_dim=autoint_criteo.EMB_DIM, attn_heads=2, attn_dim=4,
        cross_layers=3, v_init_scale=0.3, sgd_lr=0.05, epochs=2, batch_size=64,
        table_size_log2=14, max_nnz=24, max_fields=12, num_devices=1,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    with Trainer(cfg) as trainer:
        drawn = jax.device_get(trainer.state["dense"])
        trainer.train()
        before = jax.device_get(trainer.state["dense"])
        assert len(before) == 14 and before["attn_q3"].shape == (8, 8)
        for name in ("attn_q1", "attn_k2", "attn_v3", "attn_r1", "w_out"):  # it trained
            assert float(np.abs(before[name] - drawn[name]).max()) > 0.0
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
        got = engine.predict(batch)
        np.testing.assert_allclose(got, want, atol=1e-6)
        keys, x, slots = refcheck.entries(trainer.prepare_batch(batch))
        tables = jax.device_get(trainer.state["tables"])
        rows = {t: jnp.asarray(tables[t]["param"][keys]) for t in tables}
        ref = autoint_criteo.logit(
            rows, jnp.asarray(x), jnp.asarray(slots), cfg.max_fields, before
        )
        real = batch.weights > 0
        np.testing.assert_allclose(
            got[real], np.asarray(jax.nn.sigmoid(ref))[real], atol=2e-6
        )
    args = train.build_parser().parse_args([
        "--model", "autoint", "--attn-heads", "4", "--attn-dim", "16",
        "--cross-layers", "2", "--train", toy_dataset.train_prefix,
    ])
    assert (args.model, args.attn_heads, args.attn_dim) == ("autoint", 4, 16)


# -- FiBiNET: SENET gates, a matrix a field pair, a 2 P D-wide first layer ----


def _fibinet_model(**fields):
    """The issue's toy sizes: m = 8, D = 4, r = 3, three hidden layers of 16."""
    return make_model(Config(**{
        "model": "fibinet", "emb_dim": 4, "senet_reduction": 3, "hidden_dim": 16,
        "deep_layers": 3, "max_fields": 8, **fields,
    }))


def _fibinet_rows(model, rng, b: int = 6, k: int = 10):
    """Gathered rows and a batch for ``model``: every row has one entry of
    each of the first ``max_fields - 1`` fields (the last bucket is empty, as
    the benchmark's 40th), row 0 one entry more of field 2, and row 1 has lost
    its entry of field 4 (value 0: what the capacity rule leaves)."""
    rows, batch = _autoint_rows(model, rng, b, k)
    mask = np.array(batch["mask"])
    mask[1, 4] = 0.0
    rows["w"] = jnp.asarray(rng.normal(0, 0.5, (b, k, 1)), jnp.float32)
    return rows, {**batch, "mask": jnp.asarray(mask)}


def test_fibinet_logit_and_every_gradient_are_the_references():
    """The program's ``logit`` (models/fibinet.py over blocks.senet_gates and
    blocks.bilinear_pairs: a field's pairs side by side in one product)
    against the plain reference's (one einsum over picked pairs), on drawn
    weights with the biases off zero: the logit, and the gradient of every
    gathered row of both tables and of every dense array, within 2e-6 of the
    array's largest; with an absent field (the last bucket) and a dropped
    entry (row 1's field 4), which score as the row WITHOUT the entry does."""
    model = _fibinet_model()
    rows, batch = _fibinet_rows(model, np.random.default_rng(3))
    dense = jax.tree.map(lambda a: a + 0.05, model.dense_init(jax.random.PRNGKey(2)))
    assert dense["senet_w1"].shape == (8, 2) and dense["senet_w2"].shape == (2, 8)
    assert dense["bil_p"].shape == dense["bil_q"].shape == (28, 4, 4)
    assert dense["w1"].shape == (2 * 28 * 4, 16) and dense["w3"].shape == (16, 16)
    x = batch["vals"] * batch["mask"]

    def ours(r, d):
        return model.logit(r, batch, d)

    def theirs(r, d):
        return fibinet_criteo.logit(r, x, batch["slots"], model.max_fields, d)

    got, want = ours(rows, dense), theirs(rows, dense)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * float(jnp.abs(want).max()))
    mix = jnp.asarray(np.random.default_rng(8).normal(size=want.shape), jnp.float32)
    grads = [
        jax.grad(lambda r, d: jnp.sum(f(r, d) * mix), (0, 1))(rows, dense)
        for f in (ours, theirs)
    ]
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads[0]), jax.tree.leaves(grads[1])
    ):
        assert float(jnp.abs(b).max()) > 0.0, path
        np.testing.assert_allclose(
            a, b, rtol=0, atol=2e-6 * float(jnp.abs(b).max()), err_msg=str(path)
        )
    # row 1 without its dropped entry, deleted and not zeroed
    keep = np.array([c for c in range(10) if c != 4])
    alone = fibinet_criteo.logit(
        {t: r[1:2, keep] for t, r in rows.items()}, x[1:2, keep],
        batch["slots"][1:2, keep], model.max_fields, dense,
    )
    np.testing.assert_allclose(got[1], alone[0], rtol=0, atol=2e-6)
    # no gradient reaches the empty bucket's pairs, its rows of w1 or its gate
    i, j = np.triu_indices(model.max_fields, 1)
    empty = (i == model.max_fields - 1) | (j == model.max_fields - 1)
    of_dense = grads[0][1]
    for name in ("bil_p", "bil_q"):
        np.testing.assert_array_equal(of_dense[name][empty], 0.0)
    # every live pair's plain matrix sees a gradient (a gated one only where
    # both of its fields' gates are open)
    assert (np.abs(np.asarray(of_dense["bil_p"]))[~empty].max(axis=(1, 2)) > 0.0).all()
    by_pair = np.asarray(of_dense["w1"]).reshape(2, len(i), model.emb_dim, -1)
    np.testing.assert_array_equal(by_pair[:, empty], 0.0)
    np.testing.assert_array_equal(of_dense["senet_w1"][-1], 0.0)
    np.testing.assert_array_equal(of_dense["senet_w2"][:, -1], 0.0)


def _bilinear_case(b: int = 13, m: int = 8, d: int = 4, r: int = 3, empty=(7,)):
    """A tower [b, m, d] (the fields of ``empty`` all zero, one more zero in
    two rows each, as a dropped entry leaves it) and the block's four arrays;
    values of order 1."""
    rng = np.random.default_rng(7)
    tower = rng.normal(0, 0.7, (b, m, d)).astype(np.float32)
    tower[:, list(empty), :] = 0.0
    tower[2, 1] = tower[5, 3] = 0.0
    pairs = blocks.field_pairs(m)
    weights = (
        jnp.asarray(rng.normal(0, 0.5, (m, m // r)), jnp.float32),
        jnp.asarray(rng.normal(0, 0.5, (m // r, m)), jnp.float32),
        jnp.asarray(rng.normal(0, 0.5, (pairs, d, d)), jnp.float32),
        jnp.asarray(rng.normal(0, 0.5, (pairs, d, d)), jnp.float32),
    )
    return weights, jnp.asarray(tower)


def _plain_bilinear(weights, tower):
    """The block as its equations, through the REFERENCE's functions
    (benchmarks/reference/fibinet_criteo.py)."""
    s1, s2, wp, wq = weights
    v = fibinet_criteo.gates(s1, s2, tower)[..., None] * tower
    return jnp.concatenate(
        [fibinet_criteo.pairs(wp, tower), fibinet_criteo.pairs(wq, v)], axis=-1
    )


@pytest.mark.parametrize("slice_rows", [1, 4, 13], ids=["one-row", "uneven", "whole"])
def test_sliced_bilinear_block_equals_the_plain_pairs_in_value_and_gradients(slice_rows):
    """``blocks.senet_bilinear`` whole and in slices of the batch (through
    ``lax.map``, each slice's backward rematerialised; a slice of one row, a
    slice that does not divide the batch, the whole batch) against the plain
    pairs over the whole batch: the pair tensor, and the gradient of the four
    arrays and of the tower, within 2e-6 of the largest."""
    weights, tower = _bilinear_case()
    want = _plain_bilinear(weights, tower)
    got = blocks.senet_bilinear(*weights, tower, slice_rows)
    assert got.shape == want.shape == (13, 2 * 28 * 4)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * float(jnp.abs(want).max()))
    mix = jnp.asarray(np.random.default_rng(8).normal(size=want.shape), jnp.float32)
    grads = [
        jax.grad(lambda w, t: jnp.sum(f(w, t) * mix), (0, 1))(weights, tower)
        for f in (lambda w, t: blocks.senet_bilinear(*w, t, slice_rows), _plain_bilinear)
    ]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * float(jnp.abs(b).max()))


def test_identity_matrices_under_open_gates_sum_to_the_fm_second_order_term():
    """Ties the pair equations to code the suite already trusts: with every
    ``P_ij`` the identity, ``sum_{i<j, d} p_ij[d] = sum_{i<j} <e_i, e_j>``,
    which is half of the FM reference's second-order term over the fields'
    vectors (``reference/fm.py``: (sum v)^2 - sum v^2, no half); and with the
    excitation's weights such that every gate is exactly 1 the gated tower is
    the plain one."""
    _, tower = _bilinear_case(empty=())
    b, m, d = tower.shape
    eye = jnp.broadcast_to(jnp.eye(d, dtype=jnp.float32), (blocks.field_pairs(m), d, d))
    p = blocks.bilinear_pairs(eye, tower)
    fm_rows = {"w": jnp.zeros((b, m, 1), jnp.float32), "v": tower}
    second_order = fm.logit(fm_rows, jnp.ones((b, m), jnp.float32))
    np.testing.assert_allclose(
        2.0 * jnp.sum(p, axis=-1), second_order, rtol=0,
        atol=2e-6 * float(jnp.abs(second_order).max()),
    )
    # gates forced to 1: z >= 0 here, S1 = 0 would kill them, so force them
    # through the block's own seam
    import unittest.mock

    with unittest.mock.patch.object(
        blocks, "senet_gates", lambda s1, s2, e: jnp.ones(e.shape[:2], e.dtype)
    ):
        c = blocks.senet_bilinear(
            jnp.zeros((m, 2)), jnp.zeros((2, m)), eye, eye, tower, b
        )
    np.testing.assert_array_equal(c[:, : p.shape[1]], c[:, p.shape[1]:])
    np.testing.assert_array_equal(c[:, : p.shape[1]], p)


def test_a_zero_excitation_leaves_the_gated_tower_and_its_gradients_exactly_zero():
    """``S2`` zero: every gate is ReLU(0) = 0, the gated tower's pairs are
    exactly 0, and so are the gradients of ``bil_q``, of ``senet_w1`` and of
    the gated tower's rows of ``w1`` (a ReLU's gradient at 0 is 0 on both
    sides, so ``senet_w2``'s is 0 too); the plain tower's are not."""
    model = _fibinet_model()
    rows, batch = _fibinet_rows(model, np.random.default_rng(4))
    dense = model.dense_init(jax.random.PRNGKey(2))
    dense = {**dense, "senet_w2": jnp.zeros_like(dense["senet_w2"])}
    x = batch["vals"] * batch["mask"]
    tower = blocks.field_sum_tower(rows["emb"], x, batch["slots"], model.max_fields)
    c = blocks.senet_bilinear(
        dense["senet_w1"], dense["senet_w2"], dense["bil_p"], dense["bil_q"], tower, 6
    )
    half = c.shape[1] // 2
    np.testing.assert_array_equal(c[:, half:], 0.0)
    assert float(jnp.abs(c[:, :half]).max()) > 0.0
    for logit in (
        lambda d: model.logit(rows, batch, d),
        lambda d: fibinet_criteo.logit(rows, x, batch["slots"], model.max_fields, d),
    ):
        grads = jax.grad(lambda d: jnp.sum(jnp.sin(logit(d))))(dense)
        for name in ("bil_q", "senet_w1", "senet_w2"):
            np.testing.assert_array_equal(grads[name], 0.0)
        np.testing.assert_array_equal(grads["w1"][half:], 0.0)
        assert float(jnp.abs(grads["bil_p"]).max()) > 0.0
        assert float(jnp.abs(grads["w1"][:half]).max()) > 0.0


def test_bilinear_block_goes_whole_at_the_cells_batch_and_in_slices_beyond():
    """``blocks.bilinear_slice_rows``: at the paper's Criteo sizes the cell's
    batch whole (its 6 P D floats an example, 2.9 GiB, inside
    ``BILINEAR_WHOLE_BYTES``), a batch four times that in slices of whole lane
    widths; the model's shapes are the paper's."""
    assert blocks.field_pairs(40) == 780
    assert blocks.bilinear_slice_rows(16384, 10, 40) == 16384
    assert 4 * 6 * 780 * 10 * 16384 <= blocks.BILINEAR_WHOLE_BYTES
    rows = blocks.bilinear_slice_rows(65536, 10, 40)
    assert rows < 65536 and rows % 128 == 0
    assert blocks.bilinear_slice_rows(64, 4, 8) == 64
    model = _fibinet_model(emb_dim=10, hidden_dim=400, max_fields=40)
    assert (model.pairs, model.squeezed) == (780, 13)
    shapes = jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
    assert len(shapes) == 12 and sum(a.size for a in shapes.values()) == 6_718_641
    assert sum(k * n for k, n in model.dense_matmuls()) == 6_560_400
    assert model.dense_counters(16384) == {}  # no counter without a reader


def test_config_refuses_a_reduction_under_one():
    with pytest.raises(ValueError, match="senet_reduction"):
        Config(model="fibinet", senet_reduction=0)
    # a reduction wider than the fields leaves one hidden unit, not none
    assert _fibinet_model(senet_reduction=100).squeezed == 1


def test_fibinet_survives_checkpoint_and_artifact_and_serves_the_reference(
    toy_dataset, tmp_path
):
    """The family through the rest of the system's normal path: it trains
    through ``Trainer.train``, its twelve dense arrays (the three-dimensional
    ``bil_p`` / ``bil_q`` among them) restore bit for bit from the
    checkpoint, the engine loaded from the exported artifact scores a raw
    batch as the trainer does AND as the benchmark's reference's ``logit``
    does from the trained state, and train.py's CLI reaches the new field."""
    from xflow_tpu import train
    from xflow_tpu.io.loader import ShardLoader
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        train_path=toy_dataset.train_prefix, test_path=toy_dataset.test_prefix,
        model="fibinet", emb_dim=fibinet_criteo.EMB_DIM, senet_reduction=3,
        hidden_dim=8, deep_layers=3, v_init_scale=0.3, sgd_lr=0.05, epochs=2,
        batch_size=64, table_size_log2=14, max_nnz=24, max_fields=12,
        num_devices=1, checkpoint_dir=str(tmp_path / "ck"),
    )
    with Trainer(cfg) as trainer:
        drawn = jax.device_get(trainer.state["dense"])
        trainer.train()
        before = jax.device_get(trainer.state["dense"])
        assert len(before) == 12 and before["bil_q"].shape == (66, 10, 10)
        assert before["senet_w1"].shape == (12, 4)
        for name in ("senet_w1", "senet_w2", "bil_p", "bil_q", "w1", "w3"):  # it trained
            assert float(np.abs(before[name] - drawn[name]).max()) > 0.0
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
        got = engine.predict(batch)
        np.testing.assert_allclose(got, want, atol=1e-6)
        keys, x, slots = refcheck.entries(trainer.prepare_batch(batch))
        tables = jax.device_get(trainer.state["tables"])
        rows = {t: jnp.asarray(tables[t]["param"][keys]) for t in tables}
        ref = fibinet_criteo.logit(
            rows, jnp.asarray(x), jnp.asarray(slots), cfg.max_fields, before
        )
        real = batch.weights > 0
        np.testing.assert_allclose(
            got[real], np.asarray(jax.nn.sigmoid(ref))[real], atol=2e-6
        )
    args = train.build_parser().parse_args([
        "--model", "fibinet", "--senet-reduction", "4", "--deep-layers", "3",
        "--train", toy_dataset.train_prefix,
    ])
    assert (args.model, args.senet_reduction, args.deep_layers) == ("fibinet", 4, 3)


# -- the kink rule (PR 51) under tier-1 ---------------------------------------
# ``benchmarks/tests/`` is the harness's own suite and tier-1 does not run it;
# the rule FiBiNET's ``correct`` rests on is held here by the same cases,
# imported and collected as this module's.

from benchmarks.tests.test_reference import (  # noqa: E402, F401
    test_a_batch_cannot_hide_behind_its_ties,
    test_a_forward_that_never_calls_relu_has_no_ties_and_no_share,
    test_a_relu_inside_a_checkpoint_is_an_error_not_a_pass,
    test_a_step_that_took_out_ties_may_compile_nothing,
    test_an_example_on_a_relus_kink_is_left_out_of_the_step_on_both_sides,
    test_every_relu_of_a_reference_family_is_the_one_the_check_sees,
    test_relu_hands_out_its_arguments_only_while_looked_at,
)


def test_the_relu_scan_reads_fibinets_reference_and_finds_its_relus_outside_any_checkpoint():
    """The scan above globs ``benchmarks/reference/*.py``: FiBiNET's file is
    among them, takes its bare ``relu`` from ``wide_deep`` and calls it for the
    excitation's two layers and for the hidden stack, in no function that
    ``jax.checkpoint`` wraps; and the check sees all five calls of a three-layer
    model's forward."""
    import ast
    import glob
    import os

    from benchmarks.harness import manifest
    from benchmarks.reference import ftrl

    path = os.path.join(manifest.BENCH_DIR, "reference", "fibinet_criteo.py")
    assert path in glob.glob(os.path.join(manifest.BENCH_DIR, "reference", "*.py"))
    with open(path) as f:
        tree = ast.parse(f.read())
    assert any(
        isinstance(n, ast.ImportFrom) and n.module == "benchmarks.reference.wide_deep"
        and any(a.name == "relu" for a in n.names) for n in tree.body
    )
    assert not any(
        isinstance(n, ast.ImportFrom) and (n.module or "").startswith("xflow_tpu")
        or isinstance(n, ast.Import) and any(a.name.startswith("xflow_tpu") for a in n.names)
        for n in ast.walk(tree)
    )
    functions = [n for n in tree.body if isinstance(n, ast.FunctionDef)]
    wrapped = [f for f in functions if f.decorator_list]
    assert [f.name for f in wrapped] == ["pairs"]

    def relus(node):
        return [
            c for c in ast.walk(node)
            if isinstance(c, ast.Call) and getattr(c.func, "id", "") == "relu"
        ]

    assert not relus(wrapped[0])
    assert sum(len(relus(f)) for f in functions) == 3  # two in gates, one a layer
    model = _fibinet_model()
    rows, batch = _fibinet_rows(model, np.random.default_rng(3))
    dense = model.dense_init(jax.random.PRNGKey(2))
    tables = {t: {"param": r.reshape(-1, r.shape[-1])} for t, r in rows.items()}
    idx = jnp.arange(60, dtype=jnp.int32).reshape(6, 10)
    margin, largest = ftrl.relu_margins(
        fibinet_criteo, tables, idx, batch["vals"] * batch["mask"], batch["slots"],
        model.max_fields, dense,
    )
    assert margin.shape == (5, 6) and largest.shape == (5,)
    assert bool((largest > 0).all())

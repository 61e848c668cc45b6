"""The whole-array optimizer pass over a table of ONE column runs on the
table's flat view (parallel/step.py::_optimizer_pass; PERF.md section 6,
PR 37): a view and two barriers, so the state it leaves is
``optimizer.update_rows`` bit for bit; the dense update's pass over a
wide table that the chip keeps rows-minor is held to that layout (PR 59:
seven layout constraints, the same state bit for bit); every other table
keeps its shape; and the epoch's ``wire`` row says how many elements went
flat and how many stayed on the resident layout."""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_exchange import _trained
from xflow_tpu.config import Config
from xflow_tpu.models import make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.optim.ftrl import FTRL
from xflow_tpu.optim.sgd import SGD
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, resident_pass_selects


def _pass(optimizer, resident=False):
    holder = types.SimpleNamespace(optimizer=optimizer)
    return lambda table, g: TrainStep._optimizer_pass(
        holder, table, g, resident=resident
    )


# width, whether the caller is the dense update on one device, the arm:
# FFM's 160 columns stay on the resident layout only there; DLRM's 128
# fill a lane tile and MVM's 10 go column by column, whoever calls
@pytest.mark.parametrize("d, resident, arm", [
    (1, False, "flat"), (1, True, "flat"), (10, False, "plain"),
    (10, True, "plain"), (128, True, "plain"), (160, False, "plain"),
    (160, True, "resident"),
])
@pytest.mark.parametrize("optimizer", [FTRL(), SGD()], ids=["ftrl", "sgd"])
def test_the_pass_is_update_rows_bit_for_bit(optimizer, d, resident, arm):
    """Three chained passes against update_rows on the same arrays: rows
    no gradient has ever reached (n' = 0 keeps its initial value), rows
    touched once and then handed a zero, rows touched every time, and,
    under FTRL, entries whose |z'| <= lambda1 (a gradient under 5e-5:
    the new weight is exactly 0)."""
    rng = np.random.default_rng(11)
    t = 4096 + 24  # no multiple of a 1024-element tile
    param = jnp.asarray(rng.normal(0, 1e-2, (t, d)), jnp.float32)
    table = {"param": param, **optimizer.init_aux(param)}
    grads = []
    for i in range(3):
        g = rng.normal(0, 1e-3, (t, d))
        g[: t // 2] = 0.0  # never touched
        if i:
            g[t // 2: 3 * t // 4] = 0.0  # touched by the first pass alone
        grads.append(jnp.asarray(g, jnp.float32))
    want, got = table, table
    shipped = _pass(optimizer, resident)
    direct, flat = jax.jit(optimizer.update_rows), jax.jit(shipped)
    for g in grads:
        want, got = direct(want, g), flat(got, g)
    assert set(got) == set(want)
    for name in want:
        assert got[name].shape == (t, d)
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    np.testing.assert_array_equal(got["param"][: t // 2], param[: t // 2])
    assert not np.array_equal(got["param"][t // 2:], param[t // 2:])
    if isinstance(optimizer, FTRL):
        clipped = (np.asarray(got["n"]) > 0) & (np.asarray(got["param"]) == 0)
        assert clipped[t // 2:].any() and not clipped[: t // 2].any()
    # the view is held by a barrier on each side, and only at one column;
    # the resident layout by a constraint on the four operands and the
    # three results (two under SGD, which keeps no n and z), and only for
    # the dense update's wide table
    text = str(jax.make_jaxpr(shipped)(table, grads[0]))
    assert text.count("optimization_barrier") == (2 if arm == "flat" else 0)
    assert text.count("layout_constraint") == (
        2 * len(table) + 1 if arm == "resident" else 0
    ), text
    assert (arm == "resident") == (resident and resident_pass_selects(d))


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("model", ["lr", "fm"])
def test_three_steps_end_where_update_rows_applied_directly_does(
    monkeypatch, model, devices
):
    """Whole train steps (tests/test_exchange.py's three batches: the
    head, the scatter, on four devices the exchange) with the pass as
    shipped and with update_rows handed the [T, D] arrays as they are:
    every row of every array, bit for bit."""
    got = _trained(model, devices, 5, "seg")
    monkeypatch.setattr(
        TrainStep, "_optimizer_pass",
        lambda self, table, g, **_: self.optimizer.update_rows(table, g),
    )
    want = _trained(model, devices, 5, "seg")
    assert set(got) == ({"w"} if model == "lr" else {"w", "v"})
    for table, arrays in want.items():
        assert float(np.max(np.abs(arrays["z"]))) > 0.0
        for name, array in arrays.items():
            np.testing.assert_array_equal(
                got[table][name], array, err_msg=f"{table}.{name}"
            )


# elements, in tables of T = 2^12 rows: dense mode passes once over every
# one-column table; the touched-rows modes never; the sequential dense
# inner once a slice; the hot inner a slice over the [H, 1] head (H = 32)
# and, where the window ends dense, once over the table
@pytest.mark.parametrize("model, overrides, elements", [
    ("lr", {}, 4096),
    ("fm", {}, 4096),
    ("ffm", {}, 4096),
    ("mvm", {}, 0),
    ("lr", {"update_mode": "sparse", "hot_size_log2": 0, "hot_nnz": 0}, 0),
    ("lr", {"update_mode": "sequential", "microbatch": 4,
            "sequential_inner": "dense"}, 4 * 4096),
    ("lr", {"update_mode": "sequential", "microbatch": 4,
            "sequential_inner": "sparse"}, 0),
    ("lr", {"update_mode": "sequential", "microbatch": 4,
            "sequential_inner": "hot"}, 4 * 32 + 4096),
    ("lr", {"update_mode": "sequential", "microbatch": 4,
            "sequential_inner": "hot", "hot_windowend": "sparse"}, 4 * 32),
])
def test_flat_pass_elements_from_shapes(model, overrides, elements):
    cfg = Config(**{
        **dict(
            model=model, optimizer="ftrl", table_size_log2=12, batch_size=64,
            max_nnz=6, hot_size_log2=5, hot_nnz=6, num_devices=1,
        ),
        **overrides,
    })
    step = TrainStep(make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1))
    assert step._flat_pass_elements == elements


@pytest.mark.parametrize("model, devices, tables", [
    ("lr", 1, 1), ("fm", 4, 1), ("ffm", 1, 1), ("mvm", 1, 0),
])
def test_the_wire_row_carries_the_flat_pass(
    toy_dataset, tmp_path, model, devices, tables
):
    """``flat_pass_elements_per_step`` of the epoch's ``wire`` row: T for
    LR, for FM on a mesh (all four blocks of w, none of v) and for FFM
    (w), 0 for MVM, whose one table is ten columns wide.  Beside it
    ``resident_pass_elements_per_step``: FFM's v (20 fields x 4 = 80
    columns here), trained through the arm, and 0 for the others."""
    from xflow_tpu.obs.schema import OPTIONAL, validate_rows
    from xflow_tpu.trainer import Trainer

    out = tmp_path / "m.jsonl"
    cfg = Config(
        model=model, train_path=toy_dataset.train_prefix, epochs=1,
        batch_size=64, table_size_log2=14, max_nnz=24, max_fields=20,
        num_devices=devices, metrics_out=str(out),
    )
    with Trainer(cfg) as trainer:
        widths = [spec.dim for spec in trainer.step.model.tables()]
        assert widths.count(1) == tables
        trainer.train()
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    assert "flat_pass_elements_per_step" in OPTIONAL["wire"]
    row = next(r for r in rows if r["kind"] == "wire")
    assert row["flat_pass_elements_per_step"] == tables << 14
    assert row["resident_pass_elements_per_step"] == sum(
        d for d in widths if resident_pass_selects(d) and devices == 1
    ) << 14
    assert bool(row["resident_pass_elements_per_step"]) == (model == "ffm")

"""The train step's own pull and push on a mesh of more than one device
(parallel/exchange.py): held to the plain reference, to the one-device
program row for row, and — on the compiled four-device program — to its
promise of a few batch-sized collectives outside every loop."""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from benchmarks.harness import manifest, refcheck
from benchmarks.reference import fm, lr
from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.exchange import collectives_in
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, abstract_like, init_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAMILY = {"lr": lr, "fm": fm}
BATCH = 64


def _system(model, devices, hot_log2, impl, table_log2=12, batch=BATCH):
    cfg = Config(
        model=model, optimizer="ftrl", table_size_log2=table_log2,
        batch_size=batch, max_nnz=6, hot_size_log2=hot_log2, hot_nnz=6,
        num_devices=devices, hot_impl=impl, seed=3,
    )
    mesh = make_mesh(devices)
    mdl, opt = make_model(cfg), make_optimizer(cfg)
    return cfg, types.SimpleNamespace(  # what refcheck uses of a Trainer
        step=TrainStep(mdl, opt, cfg, mesh), state=init_state(mdl, opt, cfg, mesh)
    )


def _batches(cfg, count=3):
    """Keys all over the table (every chip's block), a crowded head, hot
    rows that spill into the cold plane, padding slots and examples."""
    rng = np.random.default_rng(5)
    b, k = cfg.batch_size, cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
    out = []
    for _ in range(count):
        keys = rng.integers(0, cfg.table_size, (b, k))
        keys = np.where(rng.random(keys.shape) < 0.5, rng.integers(0, 40, keys.shape), keys)
        mask = (rng.random(keys.shape) < 0.7).astype(np.float32)
        weights = np.ones(b, np.float32)
        weights[-5:] = 0.0
        out.append(make_batch(
            keys.astype(np.int32), np.zeros(keys.shape, np.int32), mask.copy(),
            mask, rng.integers(0, 2, b).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return out


def _trained(model, devices, hot_log2, impl, table_log2=12, count=3):
    cfg, system = _system(model, devices, hot_log2, impl, table_log2)
    for batch in _batches(cfg, count):
        system.state, _ = system.step.train(
            system.state, system.step.put_batch(batch)
        )
    return jax.device_get(system.state["tables"])


# "seg" is what hot_impl=auto picks on a CPU, "mxu" what the chip runs
@pytest.mark.parametrize("impl", ["seg", "mxu"])
@pytest.mark.parametrize("hot_log2", [0, 5])
@pytest.mark.parametrize("table_log2", [12, 16])
def test_fm_on_four_devices_agrees_with_the_reference(table_log2, hot_log2, impl):
    """Three steps running on a four-device mesh, each against
    benchmarks/reference/fm.py + ftrl.py on the rows the batch touches,
    within refcheck's ROWS_RTOL and LOGLOSS_ATOL (1e-6 both, since PR 31)."""
    cfg, system = _system("fm", 4, hot_log2, impl, table_log2)
    got = refcheck.check_train_steps(system, fm, _batches(cfg), cfg)
    assert got["ok"], got
    assert all(s["touched_rows"] > 100 for s in got["steps"])


@pytest.mark.parametrize("impl", ["seg", "mxu"])
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_lr_on_four_devices_agrees_with_the_reference(hot_log2, impl):
    cfg, system = _system("lr", 4, hot_log2, impl)
    got = refcheck.check_train_steps(system, lr, _batches(cfg), cfg)
    assert got["ok"], got


@pytest.mark.parametrize("impl", ["seg", "mxu"])
@pytest.mark.parametrize("hot_log2", [0, 5])
@pytest.mark.parametrize("model", ["lr", "fm"])
def test_four_devices_end_where_one_does(model, hot_log2, impl):
    """The same three batches on one device and on four: every row of
    every array.  Without a head the exchange adds exact zeros and the
    scatter meets each row's slots in the same order, so the state is bit
    for bit the same; the head's gradient is summed per chip and then
    over the chips, another order of the same float32 sum."""
    one = _trained(model, 1, hot_log2, impl)
    four = _trained(model, 4, hot_log2, impl)
    for table, arrays in one.items():
        for name, want in arrays.items():
            got = four[table][name]
            if hot_log2 == 0:
                np.testing.assert_array_equal(got, want, err_msg=f"{table}.{name}")
            else:
                scale = float(np.max(np.abs(want)))
                assert float(np.max(np.abs(got - want))) <= 1e-6 * scale, (
                    f"{table}.{name}"
                )


def test_a_table_out_of_the_mxu_head_and_a_tiny_table():
    """FFM's wide v opts out of the one-hot head (TableSpec.hot=False), so
    its hot occurrences are pulled and pushed like cold ones; and at eight
    devices a 2^8 head is wider than a 2^10 table's block of 128 rows, so
    the head spans blocks."""
    for model, devices, table_log2, hot_log2 in [("ffm", 4, 12, 5), ("fm", 8, 10, 8)]:
        one, many = (
            _trained(model, n, hot_log2, "mxu", table_log2, count=2)
            for n in (1, devices)
        )
        for table, arrays in one.items():
            for name, want in arrays.items():
                np.testing.assert_allclose(
                    many[table][name], want, rtol=1e-5,
                    atol=1e-6 * float(np.max(np.abs(want))),
                    err_msg=f"{model} {table}.{name}",
                )


# collectives a dense step may hold: per table the pulled rows, the pushed
# gradient rows, the head's read and the head gradient's sum; the two key
# planes' all-gathers; and up to three all-reduces of scalars (example
# count, logloss) that the partitioner adds.  XLA may combine some.
def _most_collectives(tables: int) -> int:
    return 4 * tables + 2 + 3


# sizes at which the head's scans take two chunks a chip (ops/hot.py::_chunk)
# and the batch's B x max_nnz slots are fewer than a block's T/4 rows
@pytest.mark.parametrize("model, tables, table_log2, batch", [
    ("fm", 2, 18, 4096), ("lr", 1, 21, 65536),
])
def test_the_compiled_four_device_step_keeps_its_promise(
    model, tables, table_log2, batch
):
    """The compiled train program of a four-device mesh (hot head on, the
    one-hot MXU form, whose scans are the loops in question): no collective
    in a ``while`` body, none with T or T/4 rows, and at most a stated
    few."""
    cfg, system = _system(model, 4, 10, "mxu", table_log2, batch)
    arrays = system.step.put_batch(_batches(cfg, 1)[0])
    text = system.step.train.lower(
        abstract_like(system.state), abstract_like(arrays)
    ).compile().as_text()
    assert " while(" in text  # the head's scans are there
    found = collectives_in(text)
    assert found and not [c for c in found if c["in_loop"]], found
    slots = batch * cfg.max_nnz
    assert slots < cfg.table_size // 4  # a batch-sized operand is no block
    assert max(c["rows"] for c in found) <= slots, found
    assert len(found) <= _most_collectives(tables), found


def test_one_device_writes_no_exchange():
    cfg, system = _system("fm", 1, 5, "mxu")
    arrays = system.step.put_batch(_batches(cfg, 1)[0])
    text = system.step.train.lower(
        abstract_like(system.state), abstract_like(arrays)
    ).compile().as_text()
    assert collectives_in(text) == [] and "xf.exchange" not in text
    assert system.step.exchange_bytes(cfg.batch_size) == 0


def test_collectives_in_reads_loops_and_tuples():
    text = "\n".join([
        "%body.1 (p: f32[8]) -> f32[8] {",
        "  %ar = f32[8]{0} all-reduce(%p), to_apply=%add",
        "}",
        "%add (a: f32[], b: f32[]) -> f32[] {",
        "  ROOT %s = f32[] add(%a, %b)",
        "}",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %w = f32[8]{0} while(%x), condition=%cond.1, body=%body.1",
        "  %ag = (f32[64,10]{1,0}, f32[32]{0}) all-gather-start(%x, %x), dimensions={0}",
        "}",
    ])
    got = collectives_in(text)
    assert [(c["op"], c["rows"], c["in_loop"]) for c in got] == [
        ("all-reduce", 8, True), ("all-gather", 64, False),
    ]
    # one asynchronous all-gather that the TPU's compiler continues inside
    # a neighbouring loop is one collective, and not the loop's
    chained = "\n".join([
        "%body.2 (p: f32[8]) -> f32[8] {",
        '  %ag.1 = f32[32]{0} all-gather(%p), frontend_attributes={chain_id="0"}',
        '  %ag.2 = f32[32]{0} all-gather(%p), frontend_attributes={chain_id="1"}',
        "}",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        '  %ag.0 = f32[32]{0} all-gather(%x), frontend_attributes={chain_id="0"}',
        "  %w = f32[8]{0} while(%x), condition=%cond.2, body=%body.2",
        "}",
    ])
    assert [(c["pieces"], c["in_loop"]) for c in collectives_in(chained)] == [
        (2, False), (1, True),
    ]


def test_exchange_bytes_of_the_benchmark_cell():
    """The counter behind the ``wire`` row's ``exchange_bytes_per_step``,
    from shapes, at the geometry of fm_tb_x4.train_packed."""
    doc = manifest.config_file("benchmarks/configs/fm_ftrl_criteo_tb.json")
    cfg = Config(**{k: v for k, v in doc.items() if k not in manifest.CONFIG_META})
    step = TrainStep(
        make_model(cfg), make_optimizer(cfg), cfg, make_mesh(cfg.num_devices)
    )
    b, k, h = 131072, 8, 1 << 14
    want = 2 * b * k * 4 + 2 * b * k * (1 + 10) * 4 + 5 * h * (1 + 10) * 4
    assert step.exchange_bytes(b) == want == 104_267_776


@pytest.mark.parametrize("stated, devices, ok", [
    (0, 4, True), (4, 4, True), (4, 0, False), (2, 4, False), (1, 1, True),
])
def test_a_stated_table_layout_is_held_to_the_mesh(stated, devices, ok):
    """``table_shards`` chooses nothing: a deployment states how many row
    blocks its tables are cut into, and a mesh that would cut them
    otherwise is refused (``num_devices=0`` takes all eight virtual
    devices here)."""
    from xflow_tpu.trainer import Trainer

    fields = dict(model="fm", table_size_log2=12, batch_size=64, max_nnz=6)
    if stated and devices and stated != devices:
        with pytest.raises(ValueError, match="table_shards"):
            Config(**fields, num_devices=devices, table_shards=stated)
        return
    cfg = Config(**fields, num_devices=devices, table_shards=stated)
    if not ok:
        with pytest.raises(ValueError, match="table_shards 4 stated.*8 device"):
            Trainer(cfg)
        return
    with Trainer(cfg) as trainer:
        block = trainer.state["tables"]["v"]["param"].addressable_shards[0].data
        assert block.shape[0] == cfg.table_size // (stated or devices)


def test_the_cell_states_its_layout():
    """The cell's configuration states four row blocks, so a program
    without the field (the parent of PR 27, whose partitioner-made step
    takes 8.2 s) refuses the file at ``Config(**fields)``."""
    doc = manifest.config_file("benchmarks/configs/fm_ftrl_criteo_tb.json")
    assert doc["table_shards"] == doc["num_devices"] == 4
    assert "table_shards" not in manifest.PATH_SELECTORS


def test_the_wire_row_carries_the_exchange(toy_dataset, tmp_path):
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        model="fm", train_path=toy_dataset.train_prefix, epochs=1,
        batch_size=64, table_size_log2=14, max_nnz=24, max_fields=20,
        num_devices=4, metrics_out=str(tmp_path / "m.jsonl"),
    )
    trainer = Trainer(cfg)
    try:
        stats = trainer.train_epoch()
    finally:
        trainer.close()
    assert stats["_wire"]["exchange_bytes_per_step"] == (
        trainer.step.exchange_bytes(64)
    )
    ops = {tuple(row) for row in stats["_scopes"]["ops"]}
    assert any(scope == "xf.exchange" for _, _, scope in ops)


def test_the_cell_rehearses_on_four_virtual_devices():
    """benchmarks/run.py --workload fm_tb_x4.train_packed --rehearsal: every
    line of the cell's code at toy sizes, the reference check included."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "fm_tb_x4.train_packed", "--rehearsal", "--trace", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={
            **os.environ, "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["backend"]["count"] == 4
    assert all(last["checks"].values()), last["checks"]
    assert last["counts"]["wire_format"] == "compact"
    assert last["counts"]["reference_worst_rows_rel_err"] <= refcheck.ROWS_RTOL

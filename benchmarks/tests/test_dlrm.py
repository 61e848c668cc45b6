"""DLRM's cell as data and as a rehearsal: the rows whose integer fields
carry values, the driver that packs them with their values plane, the
operations the reference and the roofline reader count at the Criteo-Terabyte
sizes, and the files the manifest finds for the cell by name."""

import json
import types

import numpy as np
import pytest

from benchmarks import control, run
from benchmarks.drivers import train_replay_values
from benchmarks.generators.rows import RowGenerator, RowSpec
from benchmarks.generators.rows_values import VALUE_TOKEN_W, ValueRowGenerator
from benchmarks.harness import costs, manifest, train_cell
from benchmarks.layer_metrics import (
    interact_ms_per_step, interact_mxu_roofline, wire_values_bytes_per_example,
)
from benchmarks.reference import dlrm_criteo

DOC = manifest.load()
CELL = "dlrm_tb.train_packed"
MIX = manifest.traffic("replay_packed_zipf_values")
SPEC = RowSpec.from_params(MIX["rows"])
SHAPES = {
    "bot_w1": (13, 512), "bot_b1": (512,), "bot_w2": (512, 256), "bot_b2": (256,),
    "bot_w3": (256, 128), "bot_b3": (128,), "top_w1": (479, 1024), "top_b1": (1024,),
    "top_w2": (1024, 1024), "top_b2": (1024,), "top_w3": (1024, 512), "top_b3": (512,),
    "top_w4": (512, 256), "top_b4": (256,), "w_out": (256, 1), "b_out": (1,),
}
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s_bf16": 197e12}
BIG_SEED = 2_147_483_777  # the driver's seeds pass 2^31


def test_the_generator_draws_the_stock_ids_and_seed_pure_values():
    """Same spec, same seed: the ids and so the table rows are the stock
    generator's (what ``train_cell`` counts its dropped share from); the
    values follow from (seed, the row's ids, field) alone."""
    stock, valued = RowGenerator(SPEC, BIG_SEED), ValueRowGenerator(SPEC, BIG_SEED, MIX["values"])
    gid, _ = stock.draw(4000, (1, 0))
    mine, labels = valued.draw(4000, (1, 0))
    assert (gid == mine).all()
    assert (stock.keys(gid, 1 << 22, 9) == valued.keys(mine, 1 << 22, 9)).all()
    assert (gid[:, :13] == np.arange(13)).all()  # one index a numeric field
    values = valued.values(mine)
    assert values.dtype == np.float32 and values.shape == (4000, 13)
    again = ValueRowGenerator(SPEC, BIG_SEED, MIX["values"])
    assert (again.values(mine[::-1])[::-1] == values).all()  # no stream, no order
    assert (ValueRowGenerator(SPEC, 5, MIX["values"]).values(mine) != values).mean() > 0.5
    counts = valued.counts(mine)
    assert counts.min() == 0 and 1000 < counts.max() <= 65535
    assert 0.10 < (counts == 0).mean() < 0.20  # rank 1 of the power law
    assert abs(values.mean() - valued.value_mean) < 0.1
    # the label reads the values: the same ids under other values, other labels
    assert 0.25 < labels.mean() < 0.5
    flat = ValueRowGenerator(SPEC, BIG_SEED, {**MIX["values"], "u_scale": 0.0})
    assert (flat.draw(4000, (1, 0))[1] != labels).mean() > 0.1


def test_values_are_printed_so_that_they_read_back_exactly():
    """Ten characters a value, every one of the 65 536 checked when the
    generator is built; the program's two parsers read them back to the bit,
    and a parser that keeps no values reads the same keys."""
    from xflow_tpu.io.libffm import parse_block
    from xflow_tpu.io.loader import make_parse_fn

    gen = ValueRowGenerator(SPEC, 11, MIX["values"])
    gid, labels = gen.draw(500, (2, 0))
    text = gen.text(gid, labels)
    line = 2 + 13 * VALUE_TOKEN_W + 26 * 16
    assert len(text) == 500 * line and text[line - 1:line] == b"\n"
    want = gen.values(gid)
    keys = gen.keys(gid, 1 << 20, 5)
    for parse in (
        make_parse_fn(1 << 20, True, 5, numeric_fields=13),
        lambda d: parse_block(d[: 50 * line], 1 << 20, True, 5, 13),
    ):
        block = parse(text)
        n = block.num_samples
        vals = block.vals.reshape(n, 39)
        assert (vals[:, :13] == want[:n]).all() and (vals[:, 13:] == 1.0).all()
        assert (block.keys.reshape(n, 39) == keys[:n]).all()
        assert (block.labels == labels[:n]).all()
    plain = make_parse_fn(1 << 20, True, 5)(text)  # the reference's loader
    assert (plain.vals == 1.0).all() and (plain.keys.reshape(500, 39) == keys).all()


def test_first_batches_of_the_packed_shards_carry_the_values_the_text_held(
    tmp_path, monkeypatch
):
    """The driver's own packing step keeps the values, and the batches the
    harness hands the reference (``train_cell._first_batches``, whose parser
    keeps none) read them from the records' plane."""
    from xflow_tpu.config import Config
    from xflow_tpu.io.batch import numeric_plane
    from xflow_tpu.io.loader import ShardLoader, make_parse_fn

    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    doc = manifest.apply_rehearsal(
        json.load(open(f"{manifest.BENCH_DIR}/configs/dlrm_ftrl_criteo_tb.json")), True
    )
    fields = {k: v for k, v in doc.items() if k not in manifest.CONFIG_META}
    fields.update(batch_size=128, mlp_bottom="16-128", mlp_top="16-8")
    work = tmp_path / ".bench_cache" / "run0"
    work.mkdir(parents=True)
    ctx = types.SimpleNamespace(
        workload=CELL, seed=BIG_SEED, work=str(work), fields=fields,
        traffic=manifest.apply_rehearsal(MIX, True),
    )
    stock = RowGenerator(SPEC, BIG_SEED)
    data = train_replay_values.build_corpus(ctx, stock, fields)
    assert data["cache"] == "miss" and data["rows"] == 4 * 128 and len(data["shards"]) == 4
    cfg = Config(**fields, seed=BIG_SEED, train_path=data["train_path"])
    batches = train_cell._first_batches(
        cfg, data, 2, ShardLoader, make_parse_fn(cfg.table_size, True, cfg.seed)
    )
    valued = ValueRowGenerator(SPEC, BIG_SEED, MIX["values"])
    gid, _ = valued.draw(128, (0, 0))
    assert (numeric_plane(batches[0], 13) == valued.values(gid)).all()
    assert (numeric_plane(batches[0], 13) != 1.0).mean() > 0.8
    # the mix and the configuration have to agree on the numeric fields
    with pytest.raises(ValueError, match="writes values for 13"):
        train_replay_values.build_corpus(ctx, stock, {**fields, "numeric_fields": 12})


def test_the_rehearsal_of_the_cell_is_correct_and_its_control_fails(capsys):
    """``run.py --rehearsal`` on the cell: every check holds, the wire is the
    dictionary's; ``control.py`` (the reference from operands rounded to
    bfloat16) fails the reference's check and no other."""
    argv = ["--workload", CELL, "--seed", str(BIG_SEED), "--seconds", "0.5"]
    assert run.main([*argv, "--trace", "0", "--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] is True and all(line["checks"].values()), line["checks"]
    assert line["counts"]["wire_format"] == "dict"
    assert {"dense_rel_err.bot_w1", "dense_rel_err.top_w4", "relu_tie_share"} <= set(line["compared"])
    assert control.main([*argv, "--rehearsal"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["checks_failed"] == ["steps_match_reference"]


def test_the_reference_declares_the_stacks_and_the_output():
    """170 496 + 2 194 688 multiply-adds an example in eight products; the
    pairs' dots are the roofline reader's, not these."""
    products = dlrm_criteo.matmuls(SHAPES)
    assert products == [
        (13, 512), (512, 256), (256, 128), (479, 1024), (1024, 1024),
        (1024, 512), (512, 256), (256, 1),
    ]
    fields = {"table_size_log2": 22, "hot_size_log2": 14, "batch_size": 32768}
    got = costs.train_step(fields, dlrm_criteo.TABLES, 32768 * 38.9, 0.91, products)
    assert got["flops"] == 6.0 * 32768 * 2_365_184  # 4.65e11
    assert (dlrm_criteo.depth(SHAPES, "bot_"), dlrm_criteo.depth(SHAPES, "top_")) == (3, 4)


def test_the_new_readers_on_a_fixture(monkeypatch):
    fields = manifest.config(DOC, manifest.cell(DOC, CELL)["config"])
    assert interact_mxu_roofline.interact_macs_per_example(fields) == 351 * 128
    assert interact_mxu_roofline.interact_flops(fields) == 6.0 * 32768 * 351 * 128
    run_doc = {"fields": fields, "peaks": PEAKS, "trace": None, "epochs": []}
    # a program without the scope or the counter (the parent) reports nothing
    assert interact_ms_per_step.read(run_doc) is None
    assert interact_mxu_roofline.read(run_doc) is None
    assert wire_values_bytes_per_example.read(run_doc) is None
    assert wire_values_bytes_per_example.read({"epochs": [{"_wire": {"format": "dict"}}]}) is None
    # only the operations set the roofline: 8.83e9 / 197e12 = 44.8 us, so at
    # the 12 ms it is expected to take the share is a third of a percent, and
    # under 100 at any time the six float32 passes allow
    for ms, want in ((12.0, 0.3737), (6 * 0.04484, 16.66)):
        monkeypatch.setattr(interact_ms_per_step, "read", lambda run, ms=ms: ms)
        share = interact_mxu_roofline.read(run_doc)
        assert abs(share - want) < 0.01 * want and share < 100.0
    monkeypatch.setattr(interact_ms_per_step, "read", lambda run: 12.0)
    other = manifest.config(DOC, "autoint_ftrl_criteo_tb")
    assert interact_mxu_roofline.read({**run_doc, "fields": other}) is None
    rows = [{"_wire": {"values_bytes_per_example": 52.0}}, {"_wire": {"values_bytes_per_example": 52.0}}]
    assert wire_values_bytes_per_example.read({"epochs": rows}) == 52.0


def test_the_manifest_resolves_the_cells_files():
    entry = manifest.cell(DOC, CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "replay_packed_zipf_values")
    config = manifest.config(DOC, entry["config"])  # sets no path selector
    assert not manifest.PATH_SELECTORS & set(config)
    assert manifest.reference(config["family"]) is dlrm_criteo
    assert manifest.driver(MIX["kind"]) is train_replay_values
    assert config["reduced"].keys() == {"table_size_log2"}
    assert (config["emb_dim"], config["max_fields"], config["numeric_fields"]) == (128, 40, 13)
    assert (config["mlp_bottom"], config["mlp_top"]) == ("512-256-128", "1024-1024-512-256")
    assert dlrm_criteo.TABLES == {"emb": config["emb_dim"]}
    assert config["beta"] * config["batch_size"] == 1.0
    assert config["lambda2"] * config["batch_size"] == 10.0
    assert MIX["values"]["fields"] == config["numeric_fields"]
    # the rows are the stock mix's but for the numeric fields' one index
    stock = manifest.traffic("replay_packed_zipf")["rows"]
    assert {**stock, "int_vocab": 1} == MIX["rows"]
    mine = {m["name"] for m in manifest.metrics_of(DOC, "per_layer", CELL)}
    assert {
        "interact_ms_per_step", "interact_mxu_roofline", "wire_values_bytes_per_example",
        "dense_ms_per_step", "dense_matmul_roofline", "gather_rows_roofline",
        "scatter_rows_roofline", "wire_slots_bytes_per_example",
    } <= mine
    assert not {"attn_ms_per_step", "bilinear_ms_per_step", "touched_rows_indices_per_step"} & mine
    for name in ("interact_ms_per_step", "interact_mxu_roofline", "wire_values_bytes_per_example"):
        only = next(m for m in DOC["per_layer"] if m["name"] == name)
        assert only["workloads"] == [CELL]
    assert CELL in next(m for m in DOC["end_to_end"] if m["name"] == "train_examples_per_s")["workloads"]
    assert interact_ms_per_step.SCOPE == "xf.interact"

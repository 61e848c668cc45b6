"""Device time by scope and idle time by host span: the arithmetic on a
hand-made trace and scope map, a trace recorded on the chip, and the whole
join through a tiny trainer under the profiler on whatever backend runs the
tests (a CPU backend names operations by ``hlo_op`` alone)."""

import json
import os

import pytest

from benchmarks.harness import scope_times as st
from benchmarks.harness import trace_reduce as tr

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fixtures", "scopes_lr_tb.train_packed.json",
)
MS = 1e6  # ns


def test_self_times_give_each_instant_to_the_innermost_operation():
    ops = [
        ("while.1", 0, 100),  # holds its body's operations
        ("fusion.1", 10, 20), ("fusion.2", 40, 20),
        ("copy-start.1", 90, 30),  # runs on past the loop's end
        ("fusion.3", 130, 10),
    ]
    got = st.self_times(ops, 0, 200)
    assert got == {
        "while.1": 50, "fusion.1": 20, "fusion.2": 20, "copy-start.1": 30,
        "fusion.3": 10,
    }
    # exclusive times add up to the union, plain durations to more
    assert sum(got.values()) == tr.length(tr.union([(s, s + d) for _, s, d in ops]))
    # clipped to the window
    assert st.self_times(ops, 50, 95) == {"while.1": 30, "fusion.2": 10, "copy-start.1": 5}


def hand_made():
    """One device over [0, 100) ms, two steps.

    decode [0,30), a ``while`` [30,50) whose body gathers [32,40) and
    [42,48), an optimizer pass [50,60), a copy nobody scoped [60,62), an
    operation two programs place differently [62,66), one no program holds
    [66,67); idle [67,100) but for a late scatter [90,95).
    host: stream threads 1 and 2 open shards over [65,85) and [70,92)
    (``remap_digest`` inside, [66,84) and [71,91)); the main thread waits
    for input over [60,93).
    """
    ops = [
        ("fusion.1 s32[64] kCustom", 0, 30 * MS),
        ("while.7 s32[]", 30 * MS, 20 * MS),
        ("fusion.2 f32[64,1] kCustom", 32 * MS, 8 * MS),
        ("fusion.2 f32[64,1] kCustom", 42 * MS, 6 * MS),
        ("compare_select_fusion f32[1024,1] kLoop", 50 * MS, 10 * MS),
        ("copy.3 f32[1024,1]", 60 * MS, 2 * MS),
        ("fusion.4 s32[64] kCustom", 62 * MS, 4 * MS),
        ("slice.9 f32[1]", 66 * MS, 1 * MS),
        ("fusion.5 f32[1024] kCustom", 90 * MS, 5 * MS),
    ]
    rows = [
        ["fusion.1", "s32[64]", "xf.wire_decode"],
        ["fusion.1", "u8[64]", "xf.scatter"],  # another program, another type
        ["while.7", "s32[]", "xf.gather"],
        ["fusion.2", "f32[64,1]", "xf.gather"],
        ["compare_select_fusion", "f32[1024,1]", "xf.optimizer"],
        ["copy.3", "f32[1024,1]", ""],
        ["fusion.4", "s32[64]", "xf.wire_decode"],
        ["fusion.4", "s32[64]", "xf.scatter"],  # two programs, two scopes
        ["fusion.5", "f32[1024]", "xf.scatter"],
    ]
    host = [
        ("xf.shard_open", 1, 65 * MS, 20 * MS),
        ("xf.remap_digest", 1, 66 * MS, 18 * MS),
        ("xf.shard_open", 2, 70 * MS, 22 * MS),
        ("xf.remap_digest", 2, 71 * MS, 20 * MS),
        ("xf.input_stall", 0, 60 * MS, 33 * MS),
    ]
    trace = tr.Trace({0: ops}, [("epoch", 0, 100 * MS)])
    return trace, host, rows


def test_hand_made_case():
    trace, host, rows = hand_made()
    got = st.attribute(trace, host, (0, 100 * MS), 2, rows)
    assert got["busy_s"] == pytest.approx(0.072)
    assert got["device_s_by_scope"] == {
        "xf.gather": pytest.approx(0.020),  # the loop and its body, once
        "xf.optimizer": pytest.approx(0.010),
        "xf.scatter": pytest.approx(0.005),
        "xf.wire_decode": pytest.approx(0.030),
    }
    # what no scope covers, by why
    assert got["unscoped_parts_s"] == {
        "no_scope": pytest.approx(0.002),
        "ambiguous": pytest.approx(0.004),
        "unmapped": pytest.approx(0.001),
    }
    assert got["unscoped_s"] == pytest.approx(0.007)
    assert got["top_unscoped"][0] == ["fusion.4 s32[64] kCustom", pytest.approx(0.004)]
    # the scope times and the unscoped part add up to busy time
    assert sum(got["device_s_by_scope"].values()) + got["unscoped_s"] == pytest.approx(
        got["busy_s"]
    )
    assert got["sum_over_busy"] == pytest.approx(1.0)
    # idle is [67,90) + [95,100); the two threads' spans overlap and count
    # once: shards were open over [65,92), 23 ms of them idle
    assert got["idle_s"] == pytest.approx(0.028)
    assert got["idle_s_by_span"]["xf.shard_open"] == pytest.approx(0.023)
    assert got["open_s_by_span"]["xf.shard_open"] == pytest.approx(0.027)
    assert got["idle_s_by_span"]["xf.remap_digest"] == pytest.approx(0.023)
    assert got["idle_s_by_span"]["xf.input_stall"] == pytest.approx(0.023)
    assert got["threads_by_span"] == {
        "xf.input_stall": 1, "xf.remap_digest": 2, "xf.shard_open": 2,
    }


@pytest.mark.parametrize("op, want", [
    ("fusion.1 s32[64] kCustom", {"xf.wire_decode"}),  # name and type
    ("fusion.1 u8[64] kCustom", {"xf.scatter"}),
    ("fusion.4 s32[64] kCustom", {"xf.wire_decode", "xf.scatter"}),  # ambiguous
    ("compare_select_fusion", {"xf.optimizer"}),  # a CPU backend: the name alone
    ("fusion.1", {"xf.wire_decode", "xf.scatter"}),  # alone, the name is ambiguous
    ("fusion.1 f32[9] kCustom", None),  # a type no program gives that name
    ("copy.3 f32[1024,1]", {""}),
    ("slice-start.2", None),
])
def test_scope_map_joins_on_name_and_type(op, want):
    _, _, rows = hand_made()
    assert st.ScopeMap(rows).scopes_of(op) == want


def test_readers_read_nothing_where_nothing_is_there():
    """An untraced run, a rehearsal on a CPU backend, and a program from
    before the scopes (the parent of the PR that brought them): every
    reader returns None and raises nothing."""
    from benchmarks.harness import manifest

    names = [
        "wire_decode_ms_per_step", "gather_ms_per_step", "scatter_ms_per_step",
        "optimizer_ms_per_step", "step_unscoped_frac", "first_batch_wait_s",
        "idle_in_shard_open_s",
    ]
    times = st.attribute(*hand_made()[:2], (0, 100 * MS), 2, [])
    old_program = {
        "trace": {"source": "device_planes", "steps": 2},
        "epochs": [{"steps": 2, "phases": {"input_stall": 1.0}}],
        "scope_times": {**times, "idle_s_by_span": {}},
    }
    for run in (
        {}, {"trace": None, "epochs": []},
        {"trace": {"source": "host_threads", "steps": 2}, "scope_times": times},
        old_program,
    ):
        for name in names:
            assert manifest.layer_metric(name).read(run) is None, (name, run)


def test_readers_on_the_hand_made_case():
    from benchmarks.harness import manifest

    trace, host, rows = hand_made()
    run = {
        "trace": {"source": "device_planes", "steps": 2},
        "epochs": [{"first_batch_wait_s": 4.0}, {"first_batch_wait_s": 5.0}],
        "scope_times": st.attribute(trace, host, (0, 100 * MS), 2, rows),
    }
    read = lambda name: manifest.layer_metric(name).read(run)  # noqa: E731
    assert read("wire_decode_ms_per_step") == pytest.approx(15.0)
    assert read("gather_ms_per_step") == pytest.approx(10.0)
    assert read("scatter_ms_per_step") == pytest.approx(2.5)
    assert read("optimizer_ms_per_step") == pytest.approx(5.0)
    assert read("step_unscoped_frac") == pytest.approx(7 / 72)
    assert read("first_batch_wait_s") == pytest.approx(4.5)
    assert read("idle_in_shard_open_s") == pytest.approx(0.023)


def test_a_trace_recorded_on_the_chip():
    """The traced epoch of ``lr_tb.train_packed`` on a v5e, from the
    ``train_epoch()`` call to the end of its second step, with the scope rows
    of the operations in it: the join finds every operation, and the numbers
    are the ones the chip run computed."""
    with open(FIXTURE) as f:
        doc = json.load(f)
    trace = tr.Trace.from_json(doc)
    host = [tuple(h) for h in doc["host_spans"]]
    got = st.attribute(trace, host, tuple(doc["window"]), doc["steps"], doc["rows"])
    want = doc["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["unscoped_s"] == pytest.approx(want["unscoped_s"], rel=1e-9)
    assert got["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    for key in ("device_s_by_scope", "idle_s_by_span", "unscoped_parts_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["threads_by_span"] == want["threads_by_span"]
    # what the fixture is there to pin: on a TPU the join is on name AND
    # type, nothing is left unmapped, and the scopes cover the step
    assert got["unscoped_parts_s"]["unmapped"] < 0.01 * got["busy_s"]
    assert got["sum_over_busy"] == pytest.approx(1.0)
    assert set(got["device_s_by_scope"]) >= {
        "xf.wire_decode", "xf.gather", "xf.scatter", "xf.optimizer",
    }
    assert got["threads_by_span"]["xf.shard_open"] == 4  # four streams


@pytest.fixture(scope="module")
def traced_toy_epoch(tmp_path_factory):
    """A tiny trainer over packed shards under the profiler, as the harness
    traces an epoch: (run record, xplane path)."""
    import sys

    import jax

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(root, "tests"))
    from gen_data import generate_dataset

    from xflow_tpu.config import Config
    from xflow_tpu.io import packed
    from xflow_tpu.trainer import Trainer

    work = tmp_path_factory.mktemp("toy_epoch")
    ds = generate_dataset(str(work / "data"), num_train_shards=2, lines_per_shard=256)
    out = str(work / "pk")
    assert packed.main([
        "--train", ds.train_prefix, "--out", out, "--batch-size", "64",
        "--max-nnz", "24", "--table-size-log2", "14",
    ]) == 0
    cfg = Config(
        train_path=out, model="lr", epochs=2, batch_size=64, table_size_log2=14,
        max_nnz=24, num_devices=1, metrics_out=str(work / "m.jsonl"),
    )
    with Trainer(cfg) as trainer:
        warm = trainer.train_epoch()
        trainer.epoch += 1
        trace_dir = str(work / "trace")
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "epoch"):
                stats = trainer.train_epoch()
        finally:
            jax.profiler.stop_trace()
    path = tr.find_xplane(trace_dir)
    trace = tr.load_xplane(path)
    reduced = tr.reduce(trace, tr.span_window(trace, "epoch"), steps=stats["steps"])
    return {"epochs": [stats], "warmup": [warm], "trace": reduced}, path


def test_the_whole_join_through_a_toy_trainer(traced_toy_epoch, monkeypatch):
    """The program's scope rows against the profiler's operation events, and
    its phases as host spans, with no fixture in between."""
    run, path = traced_toy_epoch
    monkeypatch.setattr(st, "find_xplane", lambda: path)
    got = st.load(run)
    assert got is run["scope_times"] and st.load(run) is got  # read once
    assert got["steps"] == run["epochs"][0]["steps"] > 0
    assert got["scope_rows"] == len(run["warmup"][0]["_scopes"]["ops"]) > 0
    # every operation of the traced epoch belongs to the train program
    assert got["unscoped_parts_s"]["unmapped"] < 0.05 * got["busy_s"]
    assert got["unscoped_parts_s"]["ambiguous"] == 0.0
    scoped = got["device_s_by_scope"]
    assert {"xf.scatter", "xf.optimizer"} <= set(scoped)
    assert sum(scoped.values()) > 0.5 * got["busy_s"]
    assert sum(scoped.values()) + got["unscoped_s"] == pytest.approx(got["busy_s"])
    assert got["busy_s"] == pytest.approx(run["trace"]["busy_s"])
    # the trainer's phases, from the threads that ran them
    spans = got["open_s_by_span"]
    assert {
        "xf.train_epoch", "xf.input_stall", "xf.dispatch", "xf.device_block",
        "xf.h2d", "xf.shard_open", "xf.remap_digest",
    } <= set(spans)
    assert got["threads_by_span"]["xf.input_stall"] == 1
    assert spans["xf.remap_digest"] <= spans["xf.shard_open"]
    assert spans["xf.input_stall"] == pytest.approx(
        run["epochs"][0]["phases"]["input_stall"], rel=0.2, abs=2e-3
    )
    # a CPU backend is not a device: the device_trace readers decline
    if run["trace"]["source"] != "device_planes":
        assert st.on_device(run) is None
        assert st.scope_ms_per_step(run, "xf.scatter") is None


def test_no_trace_to_find_is_no_result(monkeypatch, tmp_path):
    monkeypatch.setattr(st.manifest, "ROOT", str(tmp_path))
    assert st.find_xplane() is None
    run = {"trace": {"source": "device_planes", "steps": 4}, "epochs": []}
    assert st.load(run) is None and run["scope_times"] is None
    for n in ("a", "b"):  # two runs' traces: not this run's for sure
        d = tmp_path / ".bench_cache" / n / "trace" / "plugins" / "profile" / "t"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    assert st.find_xplane() is None

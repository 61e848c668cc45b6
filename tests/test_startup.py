"""ISSUE 55: the process's start-up timeline and compile watch
(``xflow_tpu/obs/startup.py``), their carriers (the first epoch's
``_startup`` -> the ``startup`` metrics row; ``serve_stats.startup``), the
``batch_read`` phase over the packed reader's pull, and the benchmark's seven
readers (``benchmarks/harness/startup_spans.py`` and ``layer_metrics/``) on
hand-made inputs.  Where two host clocks are compared a test asserts their
ORDER, never their ratio (ROADMAP C7)."""

import glob
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

from benchmarks.harness import manifest, startup_spans
from xflow_tpu.config import Config
from xflow_tpu.obs import make_obs, startup
from xflow_tpu.trainer import WORKER_PHASES, Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETUP_METRICS = [
    "setup_trainer_init_s", "setup_first_epoch_s", "setup_fleet_load_s",
    "setup_compile_s", "setup_programs_compiled", "setup_outside_program_s",
]
NEW_METRICS = SETUP_METRICS + ["idle_in_batch_read_s"]
TRAIN_CELLS = [
    w["name"] for w in manifest.load()["workloads"]
    if w["name"].endswith(".train_packed")
]


def _cfg(toy_dataset, **over):
    return Config(**{
        "train_path": toy_dataset.train_prefix,
        "test_path": toy_dataset.test_prefix,
        "model": "lr", "epochs": 2, "batch_size": 64, "table_size_log2": 14,
        "max_nnz": 24, "num_devices": 1, **over,
    })


def _within(inner: dict, outer: dict) -> bool:
    return (
        outer["start"] <= inner["start"]
        and inner["start"] + inner["seconds"] <= outer["start"] + outer["seconds"]
        and inner["thread"] == outer["thread"]
    )


def _named(snap: dict, name: str) -> list[dict]:
    return [p for p in snap["phases"] if p["name"] == name]


# -- (a) the timeline ------------------------------------------------------------


def test_phases_nest_by_time_on_one_thread():
    tl = startup.Timeline()
    with tl.phase("outer"):
        with tl.phase("inner"):
            pass
        with tl.phase("inner"):
            pass
    snap = tl.snapshot()
    assert [p["name"] for p in snap["phases"]] == ["inner", "inner", "outer"]
    outer = snap["phases"][-1]
    assert all(_within(p, outer) for p in snap["phases"][:-1])
    assert snap["origin"] <= outer["start"] <= snap["at"]
    assert outer["thread"] == threading.current_thread().name
    json.dumps(snap)  # JSON types only


def test_a_phase_on_another_thread_says_so():
    tl = startup.Timeline()

    def work():
        with tl.phase("worker"):
            pass

    t = threading.Thread(target=work, name="xf-test-worker")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert tl.snapshot()["phases"][0]["thread"] == "xf-test-worker"


def test_the_timeline_is_bounded_to_its_newest():
    tl = startup.Timeline(max_phases=4, max_events=3)
    for i in range(10):
        with tl.phase(f"p{i}"):
            pass
        tl._on_duration(startup.COMPILE_EVENT, 0.5, fun_name=f"f{i}")
    snap = tl.snapshot()
    assert [p["name"] for p in snap["phases"]] == ["p6", "p7", "p8", "p9"]
    assert [e["fun_name"] for e in snap["compiles"]["recent"]] == ["f7", "f8", "f9"]
    assert snap["compiles"]["requests"] == 10  # the totals are not bounded


def test_a_phase_that_raises_is_still_recorded():
    tl = startup.Timeline()
    with pytest.raises(ValueError):
        with tl.phase("broken"):
            raise ValueError("x")
    assert [p["name"] for p in tl.snapshot()["phases"]] == ["broken"]


def test_a_live_obs_books_the_phase_too():
    """``phase.startup_<name>`` in the registry and a span in the tracer;
    the timeline needs neither."""
    tl = startup.Timeline()
    obs = make_obs(trace=True)
    with tl.phase("load", obs) as ph:
        pass
    booked = obs.registry.snapshot().phase_seconds()
    assert booked == {"startup_load": pytest.approx(ph.seconds)}
    assert [e["name"] for e in obs.tracer.events()] == ["startup_load"]
    assert tl.snapshot()["phases"][0]["seconds"] == ph.seconds


def test_a_phase_is_a_profiler_span(monkeypatch):
    import xflow_tpu.obs as obs_mod

    made: list[str] = []
    real = obs_mod.TraceAnnotation
    monkeypatch.setattr(
        obs_mod, "TraceAnnotation",
        lambda name, **kw: made.append(name) or real(name, **kw),
    )
    with startup.Timeline().phase("x"):
        pass
    assert made == ["xf.startup_x"]


def test_process_age_is_the_imports_before_the_timeline():
    snap = startup.snapshot()
    if "process_age_at_origin_s" not in snap:
        pytest.skip("no /proc here")
    # the interpreter started before this module was imported, and not a
    # day before
    assert 0.0 <= snap["process_age_at_origin_s"] < 86400.0


# -- (b) the compile watch -------------------------------------------------------


def test_the_watch_counts_a_first_call_and_not_a_second():
    import jax
    import jax.numpy as jnp

    startup.watch_compiles()
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7)  # made before the reading: iota is a program too
    before = startup.compile_totals()
    f(x).block_until_ready()
    first = startup.compile_totals()
    f(x).block_until_ready()
    second = startup.compile_totals()
    assert first["requests"] == before["requests"] + 1
    assert first["seconds"] > before["seconds"]
    assert second == first
    assert startup.compile_delta(before, second)["compiles"] == 1
    newest = startup.snapshot()["compiles"]["recent"][-1]
    assert newest["cached"] is False and "lambda" in newest["fun_name"]


def test_a_cache_hit_marks_its_own_request_and_no_other():
    """The hit event comes before its request's duration event on the
    compiling thread: the request is ``cached``, the next is not, and
    ``compiled = requests - cache_hits``."""
    tl = startup.Timeline()
    tl._on_event(startup.CACHE_HIT_EVENT)
    tl._on_duration(startup.COMPILE_EVENT, 0.25, fun_name="jit(loaded)")
    tl._on_duration(startup.COMPILE_EVENT, 2.0, fun_name="jit(compiled)")
    tl._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    tl._on_event("/jax/compilation_cache/cache_misses")
    got = tl.snapshot()["compiles"]
    assert (got["requests"], got["cache_hits"], got["compiled"]) == (2, 1, 1)
    assert got["seconds"] == 2.25
    assert [e["cached"] for e in got["recent"]] == [True, False]


def test_the_watch_is_registered_once_a_process():
    from jax._src import monitoring

    from xflow_tpu.utils.compile_cache import enable_compile_cache

    def mine():
        return [
            cb for cb in monitoring.get_event_duration_listeners()
            if getattr(cb, "__self__", None) is startup.TIMELINE
        ]

    enable_compile_cache()
    enable_compile_cache()
    startup.watch_compiles()
    assert len(mine()) == 1


def test_one_listener_in_the_program():
    """``grep -rn backend_compile_duration --include=*.py xflow_tpu`` finds
    one file, in ``obs/``; ``chip_smoke.py`` reads that watch and registers
    nothing."""
    hits = [
        os.path.relpath(p, ROOT)
        for p in glob.glob(os.path.join(ROOT, "xflow_tpu", "**", "*.py"), recursive=True)
        if "backend_compile_duration" in open(p).read()
    ]
    assert hits == ["xflow_tpu/obs/startup.py"]
    smoke = open(os.path.join(ROOT, "chip_smoke.py")).read()
    assert "register_event" not in smoke and "compile_totals" in smoke


# -- (c) the trainer's carriers --------------------------------------------------


@pytest.fixture(scope="module")
def two_epochs(toy_dataset):
    with Trainer(_cfg(toy_dataset)) as t:
        assert not t.obs.enabled  # the timeline needs no Obs
        return t.train_epoch(), t.train_epoch()


def test_the_first_epoch_carries_the_timeline(two_epochs):
    first, second = two_epochs
    snap = first["_startup"]
    init, state, epoch = (
        _named(snap, n)[-1] for n in ("trainer_init", "state_init", "first_epoch")
    )
    assert _within(state, init)
    assert _within(_named(snap, "step_build")[-1], init)
    assert init["start"] + init["seconds"] <= epoch["start"]
    assert epoch["start"] + epoch["seconds"] <= snap["at"]
    assert "_startup" not in second


def test_an_epoch_that_met_a_new_shape_says_it_compiled(two_epochs):
    first, second = two_epochs
    assert first["compiles"] >= 1 and first["compile_seconds"] > 0.0
    assert 0 <= first["compiles_cached"] <= first["compiles"]
    assert (second["compiles"], second["compiles_cached"]) == (0, 0)
    assert second["compile_seconds"] == 0.0
    # the train program is among the snapshot's newest requests
    names = [e["fun_name"] for e in first["_startup"]["compiles"]["recent"]]
    assert any("_train_impl" in n for n in names)


def test_the_timeline_survives_two_trainers_in_one_process(toy_dataset):
    with Trainer(_cfg(toy_dataset)) as a:
        a.train_epoch()
    with Trainer(_cfg(toy_dataset)) as b:
        snap = b.train_epoch()["_startup"]
    inits, epochs = _named(snap, "trainer_init"), _named(snap, "first_epoch")
    assert len(inits) >= 2 and len(epochs) >= 2
    # each trainer's first epoch follows its own init: a, a, b, b
    order = [p["name"] for p in snap["phases"] if p["name"] in ("trainer_init", "first_epoch")]
    assert order[-4:] == ["trainer_init", "first_epoch"] * 2
    assert len(snap["phases"]) <= 256


def test_a_metrics_file_holds_one_startup_row_a_trainer(toy_dataset, tmp_path):
    from xflow_tpu.obs.__main__ import main as obs_main
    from xflow_tpu.obs.schema import SCHEMA, load_jsonl, validate_rows

    out = tmp_path / "m.jsonl"
    with Trainer(_cfg(toy_dataset, epochs=3, metrics_out=str(out))) as t:
        history = t.train()
    assert all("_startup" not in e for e in history)
    rows = load_jsonl(str(out))
    assert validate_rows(rows) == [] and "startup" in SCHEMA
    assert obs_main(["validate", str(out)]) == 0
    (row,) = [r for r in rows if r["kind"] == "startup"]
    assert {"trainer_init", "first_epoch"} <= {p["name"] for p in row["phases"]}
    assert row["compiles"]["compiled"] == (
        row["compiles"]["requests"] - row["compiles"]["cache_hits"]
    )
    epochs = [r for r in rows if r["kind"] == "train_epoch"]
    assert len(epochs) == 3
    assert all(
        {"compiles", "compiles_cached", "compile_seconds"} <= set(e) for e in epochs
    )
    assert epochs[0]["compiles"] >= 1 and epochs[2]["compiles"] == 0
    # the first epoch's op_scopes is inside first_epoch; ``phases`` still
    # sums to the epoch's own seconds (the start-up phase is not in it)
    assert not any(k.startswith("startup_") for e in epochs for k in e["phases"])


def test_restore_is_a_phase_and_is_booked_where_an_obs_is_live(toy_dataset, tmp_path):
    cfg = _cfg(
        toy_dataset, epochs=1, checkpoint_dir=str(tmp_path / "ck"),
        metrics_out=str(tmp_path / "m.jsonl"),
    )
    with Trainer(cfg) as t:
        t.train()
    with Trainer(cfg) as t:
        before = len(_named(startup.snapshot(), "restore"))
        assert t.restore() is not None
        assert len(_named(startup.snapshot(), "restore")) == before + 1
        assert "startup_restore" in t.obs.registry.snapshot().phase_seconds()


def test_the_train_cli_names_the_backends_start(toy_dataset):
    from xflow_tpu import train

    before = len(_named(startup.snapshot(), "backend_init"))
    assert train.main([
        "--model", "lr", "--train", toy_dataset.train_prefix, "--epochs", "1",
        "--batch-size", "64", "--table-size-log2", "14", "--max-nnz", "24",
        "--num-devices", "1", "--skip-eval",
    ]) == 0
    snap = startup.snapshot()
    backend = _named(snap, "backend_init")
    assert len(backend) == before + 1
    init = _named(snap, "trainer_init")[-1]
    assert backend[-1]["start"] + backend[-1]["seconds"] <= init["start"]


# -- (d) the fill: phase batch_read ----------------------------------------------


@pytest.fixture(scope="module")
def packed_shards(toy_dataset, tmp_path_factory):
    from xflow_tpu.io import packed

    out = str(tmp_path_factory.mktemp("startup_packed") / "pk")
    assert packed.main([
        "--train", toy_dataset.train_prefix, "--out", out,
        "--batch-size", "64", "--max-nnz", "24",
        "--table-size-log2", "14", "--block-mib", "0.01",
    ]) == 0
    return out


def test_batch_read_is_overlapped_for_a_packed_shard(toy_dataset, packed_shards, tmp_path):
    assert "batch_read" in WORKER_PHASES
    cfg = _cfg(toy_dataset, train_path=packed_shards, metrics_out=str(tmp_path / "m.jsonl"))
    with Trainer(cfg) as t:
        stats = t.train_epoch()
    assert stats["overlapped"]["batch_read"] > 0.0
    assert "batch_read" not in stats["phases"]
    assert sum(stats["phases"].values()) <= stats["seconds"] * 1.01


def test_batch_read_wraps_the_pull_not_the_consumer(toy_dataset, packed_shards, monkeypatch):
    """Every ``xf.batch_read`` annotation opens and closes between two
    yields: what the consumer does with a batch is never inside one."""
    import xflow_tpu.obs as obs_mod
    from xflow_tpu.io.loader import ShardLoader, make_parse_fn

    log: list[str] = []

    class Recorded:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            if self.name == "xf.batch_read":
                log.append("open")

        def __exit__(self, *exc):
            if self.name == "xf.batch_read":
                log.append("close")

    monkeypatch.setattr(obs_mod, "TraceAnnotation", Recorded)
    loader = ShardLoader(
        packed_shards + "-00000", batch_size=64, max_nnz=24, table_size=1 << 14,
        hash_seed=0, parse_fn=make_parse_fn(1 << 14, True, 0), obs=make_obs(),
    )
    batches = 0
    for _batch, _ in loader.iter_batches():
        log.append("consume")
        batches += 1
    # one pull a batch and the one that found the shard's end
    assert log == ["open", "close", "consume"] * batches + ["open", "close"]
    assert batches >= 2


def test_batch_read_costs_nothing_without_an_obs(toy_dataset, packed_shards):
    from xflow_tpu.obs import NULL_OBS
    from xflow_tpu.obs.trace import NULL_SPAN

    assert NULL_OBS.phase("batch_read") is NULL_SPAN
    with Trainer(_cfg(toy_dataset, train_path=packed_shards)) as t:
        stats = t.train_epoch()
    assert stats["overlapped"] == {}


# -- (e) the serve carrier -------------------------------------------------------


@pytest.fixture(scope="module")
def served(toy_dataset, tmp_path_factory):
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.fleet import ReplicaFleet

    art = str(tmp_path_factory.mktemp("startup_serve") / "artifact")
    with Trainer(_cfg(toy_dataset)) as trainer:
        export_artifact(trainer, art)
    fleet = ReplicaFleet.load(art, replicas=1, buckets=(4, 8), cache_capacity=0)
    try:
        fleet.submit(np.asarray([1, 2, 3], np.int64)).result(timeout=60)
        yield {"artifact": art, "rows": [fleet.emit_stats()["stats"] for _ in range(2)]}
    finally:
        fleet.close()


def test_serve_stats_carries_the_load_by_part(served):
    from xflow_tpu.obs.schema import validate_rows

    first, second = served["rows"]
    snap = first["startup"]
    load, engine = _named(snap, "fleet_load")[-1], _named(snap, "engine_load")[-1]
    assert _within(engine, load)
    for name in ("artifact_read", "weights_put", "bucket_warm"):
        assert _within(_named(snap, name)[-1], engine), name
    # the same process trained and exported first: both are on the timeline
    export = _named(snap, "export_artifact")[-1]
    assert _named(snap, "trainer_init")[-1]["start"] <= export["start"]
    assert export["start"] + export["seconds"] <= load["start"]
    assert second["startup"] == snap  # a constant between loads
    assert validate_rows([{**first, "t": 0.0, "kind": "serve_stats"}]) == []


def test_a_committed_rollout_renews_the_snapshot(served):
    from xflow_tpu.serve.fleet import ReplicaFleet

    fleet = ReplicaFleet.load(
        served["artifact"], replicas=2, buckets=(4, 8), cache_capacity=0
    )
    try:
        before = fleet.emit_stats()["stats"]["startup"]
        fleet.begin_rollout(served["artifact"], canary_frac=0.5)
        fleet.commit_rollout(force=True)
        after = fleet.emit_stats()["stats"]["startup"]
    finally:
        fleet.close()
    assert after["at"] > before["at"]
    loads = lambda snap: len(_named(snap, "engine_load"))  # noqa: E731
    assert loads(after) == loads(before) + 1


# -- (f) the benchmark's readers, on hand-made runs ------------------------------


def _snap(phases: dict, requests=12, hits=12, seconds=3.5) -> dict:
    at = 100.0
    return {
        "origin": 90.0, "at": at,
        "phases": [
            {"name": n, "start": 91.0 + i, "seconds": s, "thread": "MainThread"}
            for i, (n, s) in enumerate(phases.items())
        ],
        "compiles": {
            "requests": requests, "cache_hits": hits,
            "compiled": requests - hits, "seconds": seconds, "recent": [],
        },
    }


def _train_run(setup_s=21.5, **kw) -> dict:
    snap = _snap({"state_init": 1.5, "trainer_init": 2.25, "first_epoch": 6.5}, **kw)
    return {
        "setup_s": setup_s,
        "warmup": [
            {"seconds": 6.4, "_startup": snap},
            {"seconds": 1.75},
        ],
        "epochs": [{"seconds": 1.7}],
    }


def _serve_run(setup_s=26.0) -> dict:
    snap = _snap({
        "trainer_init": 3.0, "export_artifact": 4.5, "engine_load": 5.0,
        "fleet_load": 5.25,
    }, requests=10, hits=9, seconds=8.0)
    stats = {"requests": 5, "startup": snap}
    return {
        "setup_s": setup_s,
        "warmup": {"seconds": 2, "serve_stats": stats},
        "window": {"seconds": 30.0, "serve_stats": stats},
    }


def _reader(name):
    return manifest.layer_metric(name)


@pytest.mark.parametrize("name, run, want", [
    ("setup_trainer_init_s", _train_run(), 2.25),
    # the first epoch by the program's span, a later one by its record
    ("setup_first_epoch_s", _train_run(), 6.5 + 1.75),
    ("setup_fleet_load_s", _serve_run(), 5.25),
    ("setup_compile_s", _train_run(), 3.5),
    ("setup_compile_s", _serve_run(), 8.0),
    ("setup_programs_compiled", _train_run(), 0),
    ("setup_programs_compiled", _train_run(requests=13, hits=12), 1),
    ("setup_programs_compiled", _serve_run(), 1),
    ("setup_outside_program_s", _train_run(), 21.5 - 2.25 - 6.5 - 1.75),
    ("setup_outside_program_s", _serve_run(), 26.0 - 3.0 - 4.5 - 5.25 - 2),
])
def test_a_setup_reader_reads_its_part(name, run, want):
    assert _reader(name).read(run) == pytest.approx(want, abs=1e-12)


@pytest.mark.parametrize("run", [_train_run(), _serve_run()], ids=["train", "serve"])
def test_outside_plus_the_programs_parts_is_setup_s(run):
    parts = startup_spans.program_parts(run)
    inside = sum(parts.values())
    if isinstance(run["warmup"], dict):
        inside += run["warmup"]["seconds"]
    outside = _reader("setup_outside_program_s").read(run)
    assert outside + inside == pytest.approx(run["setup_s"], abs=1e-9)
    assert outside > 0.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_from_before_the_timeline_reads_as_nothing(name):
    """The parent's records: epoch records without ``_startup``, a
    ``serve_stats`` row without ``startup``, a trace without the span."""
    old_train = {
        "setup_s": 20.0, "warmup": [{"seconds": 6.0}], "epochs": [{"seconds": 1.7}],
        "trace": None,
    }
    stats = {"requests": 5}
    old_serve = {
        "setup_s": 25.0, "trace": None,
        "warmup": {"seconds": 2, "serve_stats": stats},
        "window": {"seconds": 30.0, "serve_stats": stats},
    }
    reader = _reader(name)
    assert reader.read(old_train) is None and reader.read(old_serve) is None
    assert reader.read({}) is None and reader.read({"warmup": []}) is None


def test_a_serve_cell_has_no_trainer_parts_and_a_train_cell_no_fleet():
    assert _reader("setup_fleet_load_s").read(_train_run()) is None
    assert _reader("setup_first_epoch_s").read(_serve_run()) is None
    # a part missing from the snapshot: no sum is made up
    run = _train_run()
    run["warmup"][0]["_startup"]["phases"] = [
        p for p in run["warmup"][0]["_startup"]["phases"] if p["name"] != "trainer_init"
    ]
    assert _reader("setup_outside_program_s").read(run) is None


def test_idle_in_batch_read_reads_its_span(monkeypatch):
    from benchmarks.harness import scope_times

    reader = _reader("idle_in_batch_read_s")
    times = {"idle_s_by_span": {"xf.batch_read": 0.41, "xf.shard_open": 0.02}}
    monkeypatch.setattr(scope_times, "on_device", lambda run: times)
    assert reader.read({"trace": {}}) == 0.41
    monkeypatch.setattr(
        scope_times, "on_device", lambda run: {"idle_s_by_span": {"xf.h2d": 0.1}}
    )
    assert reader.read({"trace": {}}) is None
    monkeypatch.setattr(scope_times, "on_device", lambda run: None)
    assert reader.read({"trace": {}}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_manifest_lists_the_metric_where_it_can_be_read(name):
    doc = manifest.load()
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    reader = _reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE
    )
    everything = [w["name"] for w in doc["workloads"]]
    want = {
        "setup_trainer_init_s": TRAIN_CELLS, "setup_first_epoch_s": TRAIN_CELLS,
        "setup_fleet_load_s": ["lr_tb.serve_rows"],
        "idle_in_batch_read_s": TRAIN_CELLS,
    }.get(name, everything)
    assert entry["workloads"] == want and entry["better"] == "lower"
    if name != "idle_in_batch_read_s":
        assert entry["moves"] == "setup_s" and entry["layer"] == "setup"


# -- (g) the cells' rehearsals ---------------------------------------------------


@pytest.mark.parametrize("cell, names", [
    ("lr_tb.train_packed", set(SETUP_METRICS) - {"setup_fleet_load_s"}),
    ("lr_tb.serve_rows", {
        "setup_fleet_load_s", "setup_compile_s", "setup_programs_compiled",
        "setup_outside_program_s",
    }),
])
def test_a_cell_rehearses_with_the_setup_metrics(cell, names):
    """benchmarks/run.py --rehearsal --trace 1: the set-up metrics of the
    cell's kind are reported (``idle_in_batch_read_s`` needs device planes,
    which a CPU backend has not), and the parts add up to the run's
    ``setup_s`` in ``.last.json``."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--rehearsal", "--trace", "1", "--seconds", "2", "--seed", "2400000011"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and all(last["checks"].values())
    assert names <= set(last["per_layer_reported"])
    with open(os.path.join(ROOT, ".bench_cache", f"{cell}.last.json")) as f:
        record = json.load(f)
    layer, run = record["per_layer"], record["run"]
    inside = sum(startup_spans.program_parts(run).values())
    if isinstance(run["warmup"], dict):
        inside += run["warmup"]["seconds"]
    assert layer["setup_outside_program_s"] + inside == pytest.approx(
        record["end_to_end"]["setup_s"], abs=1e-9
    )
    assert 0.0 < layer["setup_outside_program_s"] < record["end_to_end"]["setup_s"]
    # a CPU-pinned run has no persistent cache: every request compiled
    assert layer["setup_programs_compiled"] >= 1
    assert layer["setup_compile_s"] > 0.0


# -- (h) the docs name what the code opens ---------------------------------------


def test_the_docs_name_every_startup_phase_the_code_opens():
    opened = set()
    for path in glob.glob(os.path.join(ROOT, "xflow_tpu", "**", "*.py"), recursive=True):
        # ``startup.phase("x")``, and the module's own bare ``phase("x")``
        opened |= set(re.findall(
            r'(?:startup\.|(?<![\w.]))phase\(\s*"([a-z_]+)"', open(path).read()
        ))
    assert {
        "trainer_init", "step_build", "state_init", "remap_init", "restore",
        "first_epoch", "export_artifact", "engine_load", "artifact_read",
        "weights_put", "bucket_warm", "fleet_load", "backend_init",
    } == opened
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for name in sorted(opened):
        assert f"`startup_{name}`" in doc or f"`{name}`" in doc, name
    assert "xf.batch_read" in doc and "`startup`" in doc

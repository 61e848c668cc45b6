"""Structured metrics JSONL + profiler trace hooks (SURVEY §5 gaps),
and the obs subsystem (ISSUE 1): span tracer, phase accounting,
pipeline-health metrics, schema, summarize/compare toolchain."""

import json
import os
import re
import sys
import time

import pytest

from xflow_tpu.config import Config
from xflow_tpu.trainer import Trainer


def test_metrics_jsonl(toy_dataset, tmp_path):
    out = tmp_path / "metrics.jsonl"
    cfg = Config(
        train_path=toy_dataset.train_prefix,
        test_path=toy_dataset.test_prefix,
        model="lr",
        epochs=2,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
        metrics_out=str(out),
    )
    t = Trainer(cfg)
    t.train()
    t.evaluate()
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    kinds = [r["kind"] for r in rows]
    assert kinds.count("train_epoch") == 2
    assert kinds.count("eval") == 1
    epoch_row = next(r for r in rows if r["kind"] == "train_epoch")
    for field in ("examples", "steps", "train_logloss", "examples_per_sec", "t"):
        assert field in epoch_row
    eval_row = next(r for r in rows if r["kind"] == "eval")
    assert 0.0 <= eval_row["auc"] <= 1.0


def test_eval_every_epochs(toy_dataset, tmp_path):
    """--eval-every N runs mid-training evals (convergence curves,
    VERDICT round 3 item 3); each eval record carries its epoch."""
    out = tmp_path / "metrics.jsonl"
    cfg = Config(
        train_path=toy_dataset.train_prefix,
        test_path=toy_dataset.test_prefix,
        model="lr",
        epochs=4,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
        metrics_out=str(out),
        eval_every_epochs=2,
    )
    t = Trainer(cfg)
    t.train()
    t.evaluate()  # the caller's final eval (train.py main does this)
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    evals = [r for r in rows if r["kind"] == "eval"]
    # mid-run at epoch 2 (epoch 4 == cfg.epochs is left to the caller)
    # plus the final one
    assert [e["epoch"] for e in evals] == [2, 4]


def test_profile_trace_written(toy_dataset, tmp_path):
    prof = tmp_path / "prof"
    cfg = Config(
        train_path=toy_dataset.train_prefix,
        test_path=toy_dataset.test_prefix,
        model="lr",
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
        epochs=3,
        profile_dir=str(prof),
        # larger than one epoch's step count: the trigger must carry
        # across epochs (global-step based, not per-epoch)
        profile_start_step=8,
        profile_steps=2,
    )
    t = Trainer(cfg)
    t.train()
    # jax writes plugins/profile/<ts>/*.pb under the trace dir
    produced = list(prof.rglob("*"))
    assert any(p.is_file() for p in produced), produced


# -- ISSUE 1: obs subsystem -------------------------------------------------


def _toy_cfg(toy_dataset, **overrides):
    base = dict(
        train_path=toy_dataset.train_prefix,
        test_path=toy_dataset.test_prefix,
        model="lr",
        epochs=2,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
    )
    base.update(overrides)
    return Config(**base)


def test_span_tracer_nesting_and_export(tmp_path):
    """Nested spans land as Chrome 'X' events whose intervals nest, and
    the export is loadable trace-event JSON."""
    from xflow_tpu.obs.trace import SpanTracer

    tr = SpanTracer(capacity=16, rank=3)
    with tr.span("outer", {"epoch": 1}):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    events = tr.events()
    assert [e["name"] for e in events] == ["inner", "inner", "outer"]
    outer = events[-1]
    for inner in events[:-1]:
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert all(e["ph"] == "X" and e["pid"] == 3 for e in events)
    assert outer["args"]["epoch"] == 1

    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e


def test_span_tracer_ring_buffer():
    from xflow_tpu.obs.trace import SpanTracer

    tr = SpanTracer(capacity=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    events = tr.events()
    assert len(events) == 8  # only the newest capacity spans survive
    assert events[-1]["name"] == "s19"


def test_null_obs_is_shared_noop():
    """Disabled obs allocates nothing per step: phase()/span() return
    the one shared no-op object and the registry snapshot is empty."""
    from xflow_tpu.obs import NULL_OBS

    s1 = NULL_OBS.phase("a")
    s2 = NULL_OBS.phase("b")
    assert s1 is s2 is NULL_OBS.span("c")
    with s1:
        pass
    NULL_OBS.counter("x", 1.0)
    NULL_OBS.observe("y", 2.0)
    snap = NULL_OBS.registry.snapshot()
    assert snap.counters == {} and snap.hists == {}
    assert NULL_OBS.tracer.events() == []


def test_registry_percentiles_and_phases():
    from xflow_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    for v in range(1, 101):
        reg.observe("lat", float(v))
    reg.counter_add("phase.parse", 1.5)
    reg.counter_add("phase.parse", 0.5)
    reg.gauge_set("depth", 3.0)
    snap = reg.snapshot(reset=True)
    h = snap.hists["lat"]
    assert h["count"] == 100
    assert abs(h["p50"] - 50) <= 2 and abs(h["p99"] - 99) <= 2
    assert snap.phase_seconds() == {"parse": 2.0}
    assert snap.gauges["depth"] == 3.0
    assert reg.snapshot().counters == {}  # reset cleared it


def test_run_start_header_splits_runs(toy_dataset, tmp_path):
    """Append mode + run_start delimiter: two runs into one file never
    merge in summarize (ISSUE 1 satellite)."""
    from xflow_tpu.obs.summary import load_runs

    out = tmp_path / "m.jsonl"
    for epochs in (1, 1):
        with Trainer(_toy_cfg(toy_dataset, epochs=epochs, metrics_out=str(out))) as t:
            t.train()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    headers = [r for r in rows if r["kind"] == "run_start"]
    assert len(headers) == 2
    for h in headers:
        for field in ("run_id", "config_digest", "rank", "num_hosts"):
            assert field in h
    runs = load_runs(str(out))
    assert len(runs) == 2
    assert all(len(r.epochs) == 1 for r in runs)


def test_epoch_phase_accounting(toy_dataset, tmp_path):
    """Main-thread phases are disjoint and account for most of the
    epoch wall-clock; overlapped worker phases are reported separately
    (the summarize >= 90% contract is checked run-level by
    scripts/check_metrics_schema.py)."""
    out = tmp_path / "m.jsonl"
    with Trainer(_toy_cfg(toy_dataset, metrics_out=str(out))) as t:
        t.train()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    epochs = [r for r in rows if r["kind"] == "train_epoch"]
    assert len(epochs) == 2
    wall = sum(e["seconds"] for e in epochs)
    accounted = sum(sum(e["phases"].values()) for e in epochs)
    assert accounted <= wall * 1.01  # exclusive phases can't exceed wall
    assert accounted >= wall * 0.8, (accounted, wall, epochs)
    for e in epochs:
        assert "input_stall" in e["phases"] and "dispatch" in e["phases"]
        # single-host transfer-ahead: h2d rides the worker thread
        assert "h2d" in e["overlapped"]
        assert 0.0 <= e["input_stall_frac"] <= 1.0
        assert e["step_time_p50"] <= e["step_time_p99"]
        assert e["checkpoint_seconds"] == 0.0  # no checkpointing here


def test_stall_accounting_slow_loader(toy_dataset, tmp_path, monkeypatch):
    """An artificially slow input pipeline shows up as input_stall
    seconds, not as deflated mystery throughput."""
    # large enough that the injected stall dominates CPU-dispatch
    # wall-clock noise: at 0.02 the frac bound below flaked under
    # full-suite load (observed 0.16-0.18 vs the standalone ~0.3)
    delay = 0.05
    orig = Trainer.iter_train_batches

    def slow(self, *a, **kw):
        for item in orig(self, *a, **kw):
            time.sleep(delay)
            yield item

    monkeypatch.setattr(Trainer, "iter_train_batches", slow)
    out = tmp_path / "m.jsonl"
    with Trainer(
        _toy_cfg(toy_dataset, epochs=1, metrics_out=str(out))
    ) as t:
        t.train()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    e = next(r for r in rows if r["kind"] == "train_epoch")
    # every batch was delayed on the path the main thread blocks on
    assert e["phases"]["input_stall"] >= e["steps"] * delay * 0.7
    # the frac bound is loose: the dict wire (Config.wire_dedup)
    # compiles a second shape bucket for partial tail batches, and a
    # loaded CI box inflates this toy run's dispatch wall-clock
    # relative to the injected stall (the absolute-seconds assertion
    # above is the real accounting check; 0.196 observed at a 0.2
    # bound under full-suite load — keep clear margin)
    assert e["input_stall_frac"] >= 0.15, e


def test_checkpoint_seconds_separated(toy_dataset, tmp_path):
    """Satellite: checkpoint-save time is reported as its own field and
    excluded from examples_per_sec instead of silently deflating it."""
    out = tmp_path / "m.jsonl"
    ckpt = tmp_path / "ckpt"
    with Trainer(_toy_cfg(
        toy_dataset, epochs=1, metrics_out=str(out),
        checkpoint_dir=str(ckpt), checkpoint_every_steps=3,
    )) as t:
        t.train()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    e = next(r for r in rows if r["kind"] == "train_epoch")
    assert e["checkpoint_seconds"] > 0.0
    assert "checkpoint" in e["phases"]
    # throughput uses compute time: seconds minus checkpoint time
    # checkpoint_seconds is rounded in the record; allow that slack
    expect = e["examples"] / (e["seconds"] - e["checkpoint_seconds"])
    assert abs(e["examples_per_sec"] - expect) / expect < 1e-3


def test_schema_covers_all_emitted_kinds(toy_dataset, tmp_path):
    """Every emitted JSONL row carries its kind's required fields, and
    every kind the pipeline emits is in the schema."""
    from xflow_tpu.obs.schema import SCHEMA, validate_rows

    out = tmp_path / "m.jsonl"
    ckpt = tmp_path / "ckpt"
    with Trainer(_toy_cfg(
        toy_dataset, metrics_out=str(out),
        checkpoint_dir=str(ckpt), checkpoint_every_steps=4,
        eval_every_epochs=1,
    )) as t:
        t.train()
        t.evaluate()
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    kinds = {r["kind"] for r in rows}
    assert kinds <= set(SCHEMA)
    assert {"run_start", "train_epoch", "eval", "shard"} <= kinds


def test_trainer_trace_export(toy_dataset, tmp_path):
    """Config.obs_trace_out: the trainer writes a loadable Chrome trace
    containing the span taxonomy's hot-path names."""
    trace = tmp_path / "trace.json"
    with Trainer(_toy_cfg(
        toy_dataset, epochs=1, obs_trace_out=str(trace)
    )) as t:
        t.train()
    doc = json.loads(trace.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"train_epoch", "dispatch", "input_stall", "h2d"} <= names
    steps = [e["args"]["step"] for e in doc["traceEvents"] if "args" in e]
    assert steps and all(isinstance(s, int) for s in steps)


def test_summarize_and_compare_cli(toy_dataset, tmp_path, capsys):
    from xflow_tpu.obs.__main__ import main

    out = tmp_path / "m.jsonl"
    with Trainer(_toy_cfg(toy_dataset, metrics_out=str(out))) as t:
        t.train()
        t.evaluate()
    assert main(["summarize", str(out)]) == 0
    text = capsys.readouterr().out
    for token in ("phase", "accounted", "input_stall", "eval epoch"):
        assert token in text, text
    assert main(["compare", str(out), str(out)]) == 0
    text = capsys.readouterr().out
    assert "examples/sec" in text and "input_stall_frac" in text
    assert main(["validate", str(out)]) == 0


def test_metrics_logger_closes_on_exception(toy_dataset, tmp_path, monkeypatch):
    """Satellite: the logger is closed (rows flushed, file released) when
    training dies mid-run."""
    out = tmp_path / "m.jsonl"
    t = Trainer(_toy_cfg(toy_dataset, metrics_out=str(out)))

    def boom(*a, **kw):
        raise RuntimeError("loader died")

    monkeypatch.setattr(Trainer, "iter_train_batches", boom)
    with pytest.raises(RuntimeError, match="loader died"):
        t.train()
    assert t.metrics_logger.closed
    # late logs after close are swallowed, not crashes
    t.metrics_logger.log("eval", {})
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert [r["kind"] for r in rows] == ["run_start"]


def test_check_metrics_schema_script():
    """The CI lint (scripts/check_metrics_schema.py) passes on the toy
    pipeline — run as a subprocess exactly as CI would."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "check_metrics_schema.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


# -- ISSUE 4: flight recorder / watchdog / doctor ---------------------------


def test_histogram_alltime_max_survives_ring_overflow():
    """Satellite regression: `max` is an exact all-time aggregate (like
    count/sum/mean), `window_max` covers the retained ring.  The old
    code reported max(ring) as `max`, so a spike older than `capacity`
    observations silently vanished."""
    from xflow_tpu.obs.registry import Histogram

    h = Histogram(capacity=8)
    h.observe(100.0)  # the spike, soon evicted from the ring
    for v in range(20):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 21
    assert s["max"] == 100.0  # all-time, despite eviction
    assert s["window_max"] == 19.0  # newest capacity=8 values: 12..19
    assert abs(s["mean"] - (100.0 + sum(range(20))) / 21) < 1e-9
    empty = Histogram(capacity=4).summary()
    assert empty["max"] == 0.0 and empty["window_max"] == 0.0


def test_run_start_carries_hostname_and_pid(tmp_path):
    """Satellite: every run_start row is stamped with hostname/pid by
    MetricsLogger itself, so every emitter (trainer, serve bench,
    smokes) gets host labels for `obs merge`/`doctor` for free."""
    import socket

    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.utils.logging import MetricsLogger

    out = tmp_path / "m.jsonl"
    with MetricsLogger(str(out), run_header={
        "run_id": "x", "config_digest": "y", "rank": 0, "num_hosts": 1,
    }):
        pass
    row = json.loads(out.read_text().splitlines()[0])
    assert row["hostname"] == socket.gethostname()
    assert row["pid"] == os.getpid()
    assert validate_rows([row]) == []
    # the fields are OPTIONAL in the schema: pre-upgrade files (and
    # old headers in append-mode files that span the upgrade) still
    # validate, but a present field is still type-checked
    legacy = {k: v for k, v in row.items() if k not in ("hostname", "pid")}
    assert validate_rows([legacy]) == []
    bad = dict(row, pid="not-an-int")
    assert validate_rows([bad]) != []


def test_flight_recorder_dump_roundtrip(tmp_path):
    """The black box: notes ring-buffer, dump is atomic JSON carrying
    the active phase, thread stacks, and the last batch/checkpoint."""
    from xflow_tpu.obs.flight import FlightRecorder, load_dump

    fl = FlightRecorder(capacity=4)
    for i in range(10):
        fl.note_phase("input_stall", step=i)
    fl.note_phase("dispatch", step=10)
    fl.note_batch({"rows": 64, "cold_nnz": 24, "hot_nnz": 0, "shard": 1})
    fl.note_checkpoint(7)
    fl.note_loader("block")
    path = str(tmp_path / "flight.json")
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        assert fl.dump(path, "exception", exc=e) == path
    doc = load_dump(path)
    assert doc["reason"] == "exception"
    assert doc["active_phase"] == "dispatch"
    assert doc["exception"]["type"] == "RuntimeError"
    rec = doc["record"]
    assert len(rec["events"]) == 4  # ring kept only the newest capacity
    assert rec["last_checkpoint_step"] == 7
    assert rec["last_batch"]["rows"] == 64
    assert {"train", "loader"} <= set(rec["channels"])
    assert any(t["stack"] for t in doc["threads"])
    # no leftover tmp file from the atomic write
    assert [p.name for p in tmp_path.iterdir()] == ["flight.json"]


def test_watchdog_classifies_silence_per_phase(tmp_path):
    """Unit classification: silence while in input_stall is input
    starvation (input threshold); while in dispatch/device_block it is
    a device hang (device threshold); 'idle' silence never trips."""
    from xflow_tpu.obs.flight import FlightRecorder
    from xflow_tpu.obs.watchdog import Watchdog

    fl = FlightRecorder()
    wd = Watchdog(fl, input_s=0.5, device_s=2.0, serve_s=1.0)
    fl.note_phase("input_stall", 1)
    now = time.perf_counter()
    assert wd.check(now + 0.1) == []  # within threshold
    rows = wd.check(now + 0.6)
    assert [r["cause"] for r in rows] == ["input_stall"]
    assert rows[0]["channel"] == "train"
    assert rows[0]["threshold_seconds"] == 0.5
    # recovery on the next beat
    fl.note_phase("dispatch", 2)
    now = time.perf_counter()
    rows = wd.check(now)
    assert [r["cause"] for r in rows] == ["recovered:input_stall"]
    # dispatch silence: device threshold, not the (tighter) input one
    assert wd.check(now + 1.0) == []
    rows = wd.check(now + 2.5)
    assert [r["cause"] for r in rows] == ["device_hang"]
    fl.note_phase("idle", 3)
    wd.check()  # recovery row for the device incident
    assert wd.check(time.perf_counter() + 999) == []  # idle never trips


def test_watchdog_serve_queue_stall_gated_on_pending(tmp_path):
    """Serve-channel silence only trips while work is pending; an idle
    batcher is healthy no matter how long it sits."""
    from xflow_tpu.obs.flight import FlightRecorder
    from xflow_tpu.obs.watchdog import Watchdog

    fl = FlightRecorder()
    wd = Watchdog(fl, input_s=1.0, device_s=1.0, serve_s=0.5)
    pending = [False]
    wd.set_pending("serve", lambda: pending[0])
    fl.note_serve("batch")
    now = time.perf_counter()
    assert wd.check(now + 10.0) == []  # silent but idle: healthy
    pending[0] = True
    rows = wd.check(now + 10.0)
    assert [r["cause"] for r in rows] == ["serve_queue_stall"]


def test_watchdog_escalates_to_flight_dump(tmp_path):
    """Trip → health row; persistence past 2x threshold → exactly one
    flight dump per incident, written where flight_out points."""
    from xflow_tpu.obs.flight import FlightRecorder, load_dump
    from xflow_tpu.obs.watchdog import Watchdog

    out = str(tmp_path / "flight.json")
    fl = FlightRecorder()
    wd = Watchdog(fl, input_s=0.5, device_s=2.0, serve_s=1.0, flight_out=out)
    fl.note_phase("input_stall", 5)
    now = time.perf_counter()
    wd.check(now + 0.6)  # trip
    assert not os.path.exists(out)  # not yet escalated
    wd.check(now + 1.1)  # past 2x threshold
    assert wd.dump_count == 1
    doc = load_dump(out)
    assert doc["reason"] == "watchdog"
    assert doc["active_phase"] == "input_stall"
    wd.check(now + 5.0)  # still silent: same incident, no second dump
    assert wd.dump_count == 1


def test_stalled_run_trips_watchdog_and_doctor_blames_input(
    toy_dataset, tmp_path, monkeypatch
):
    """ISSUE 4 acceptance: a deliberately stalled toy run (loader sleep
    injected) trips the watchdog within its threshold, lands a `health`
    row plus a flight dump, and `obs doctor` names input_stall as the
    dominant cause."""
    from xflow_tpu.obs.doctor import doctor
    from xflow_tpu.obs.flight import load_dump
    from xflow_tpu.obs.schema import validate_rows

    delay = 0.6
    orig = Trainer.iter_train_batches

    def slow(self, *a, **kw):
        for item in orig(self, *a, **kw):
            time.sleep(delay)
            yield item

    monkeypatch.setattr(Trainer, "iter_train_batches", slow)
    out = tmp_path / "m.jsonl"
    flight = tmp_path / "flight.json"
    with Trainer(_toy_cfg(
        toy_dataset,
        epochs=1,
        metrics_out=str(out),
        obs_flight_out=str(flight),
        obs_watchdog=True,
        obs_watchdog_input_s=0.2,  # delay > 2x threshold => escalation
        obs_watchdog_device_s=30.0,
    )) as t:
        t.train()
        assert t._watchdog.trip_count >= 1
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    health = [r for r in rows if r["kind"] == "health"]
    trips = [r for r in health if r["cause"] == "input_stall"]
    assert trips, health
    # tripped within its threshold: the classified silence is of
    # threshold order, nowhere near the full injected delay
    assert trips[0]["silence_seconds"] < delay
    assert trips[0]["channel"] == "train"
    # the loader-channel context rode along (starvation forensics)
    assert "loader" in trips[0]["channels"]
    doc = load_dump(str(flight))
    assert doc["reason"] == "watchdog"
    assert doc["active_phase"] == "input_stall"
    assert any(r["kind"] == "flight_dump" for r in rows)
    text, rc = doctor(str(out), flight_path=str(flight))
    assert rc == 1
    # ranked diagnosis: the dominant (first) finding is the input stall
    first = next(l for l in text.splitlines() if l.strip().startswith("["))
    assert "input_stall" in first, text


def _epoch_row(epoch, rank=None, p50=0.002, p90=None, p99=None, stall=0.1):
    row = {
        "t": 1.0 + epoch, "kind": "train_epoch", "epoch": epoch,
        "examples": 640.0, "steps": 10, "train_logloss": 0.6,
        "examples_per_sec": 1000.0, "seconds": 1.0,
        "checkpoint_seconds": 0.0, "preempted": False,
        "phases": {"input_stall": stall, "dispatch": 1.0 - stall},
        "overlapped": {}, "input_stall_frac": stall,
        "step_time_p50": p50,
        "step_time_p90": p90 if p90 is not None else p50 * 1.1,
        "step_time_p99": p99 if p99 is not None else p50 * 1.2,
    }
    if rank is not None:
        row["rank"] = rank
    return row


def _run_header(rank, t0=1000.0):
    return {
        "t": 0.0, "kind": "run_start", "run_id": f"r{rank}",
        "config_digest": "abc", "rank": rank, "num_hosts": 2,
        "time_unix": t0, "hostname": f"host{rank}", "pid": 100 + rank,
    }


def test_obs_merge_ranks_and_aligns_time(tmp_path):
    """`obs merge`: per-host files combine into one rank-tagged stream
    whose rows carry absolute time (header time_unix + t) and sort by
    it; the merged file still validates."""
    from xflow_tpu.obs.__main__ import main
    from xflow_tpu.obs.schema import validate_rows

    a, b = tmp_path / "m-r0.jsonl", tmp_path / "m-r1.jsonl"
    a.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0, t0=1000.0), _epoch_row(0), _epoch_row(1),
    ]) + "\n")
    b.write_text("\n".join(json.dumps(r) for r in [
        _run_header(1, t0=1000.5), _epoch_row(0), _epoch_row(1),
    ]) + "\n")
    merged = tmp_path / "merged.jsonl"
    assert main(["merge", str(a), str(b), "--out", str(merged)]) == 0
    rows = [json.loads(l) for l in merged.read_text().splitlines()]
    assert len(rows) == 6
    assert validate_rows(rows) == []
    assert all("rank" in r and "time_unix" in r for r in rows)
    times = [r["time_unix"] for r in rows]
    assert times == sorted(times)
    # rank-1 rows interleave by wall-clock, not file order
    assert [r["rank"] for r in rows] == [0, 1, 0, 1, 0, 1]


def test_doctor_flags_straggler_rank(tmp_path, capsys):
    """ISSUE 4 acceptance: a two-rank merged fixture where one rank's
    step times are ~2x the other's makes `doctor` call out the slow
    rank as a straggler."""
    from xflow_tpu.obs.__main__ import main

    a, b = tmp_path / "m-r0.jsonl", tmp_path / "m-r1.jsonl"
    a.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0),
        _epoch_row(0, p50=0.002), _epoch_row(1, p50=0.002),
    ]) + "\n")
    b.write_text("\n".join(json.dumps(r) for r in [
        _run_header(1, t0=1000.2),
        _epoch_row(0, p50=0.004), _epoch_row(1, p50=0.0042),
    ]) + "\n")
    merged = tmp_path / "merged.jsonl"
    assert main(["merge", str(a), str(b), "--out", str(merged)]) == 0
    capsys.readouterr()
    rc = main(["doctor", str(merged)])
    text = capsys.readouterr().out
    assert rc == 1
    assert "straggler" in text and "rank 1" in text, text
    # balanced ranks stay clean
    b.write_text("\n".join(json.dumps(r) for r in [
        _run_header(1, t0=1000.2),
        _epoch_row(0, p50=0.0021), _epoch_row(1, p50=0.002),
    ]) + "\n")
    assert main(["merge", str(a), str(b), "--out", str(merged)]) == 0
    capsys.readouterr()
    assert main(["doctor", str(merged)]) == 0


def test_doctor_recompile_suspicion(tmp_path, capsys):
    """Bimodal step times (p99 >> p50, p90 near p50) past epoch 0 read
    as recompile suspicion; smooth ones read clean."""
    from xflow_tpu.obs.__main__ import main

    m = tmp_path / "m.jsonl"
    m.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0),
        _epoch_row(0, p50=0.002),  # warmup epoch: exempt however it looks
        # p99 60ms vs p50 2ms: an unmistakable recompile-scale spike,
        # comfortably past the BIMODAL_MIN_EXCESS_S noise floor
        _epoch_row(1, p50=0.002, p90=0.0022, p99=0.06),
    ]) + "\n")
    rc = main(["doctor", str(m)])
    text = capsys.readouterr().out
    assert rc == 1
    assert "recompile_suspicion" in text, text
    # smooth step times: clean
    m.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0), _epoch_row(0), _epoch_row(1),
    ]) + "\n")
    capsys.readouterr()
    assert main(["doctor", str(m)]) == 0


def test_doctor_warmup_exemption_survives_merge(tmp_path, capsys):
    """Regression: in a merged stream both hosts' run_start headers
    sort before every epoch row, so run membership must come from the
    merge's rank/run_id tags — EACH host's first (compile-spiky) epoch
    stays exempt from recompile suspicion, not just one."""
    from xflow_tpu.obs.__main__ import main

    spiky = dict(p50=0.002, p90=0.0022, p99=0.05)
    a, b = tmp_path / "m-r0.jsonl", tmp_path / "m-r1.jsonl"
    a.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0), _epoch_row(0, **spiky), _epoch_row(1),
    ]) + "\n")
    b.write_text("\n".join(json.dumps(r) for r in [
        _run_header(1, t0=1000.2), _epoch_row(0, **spiky), _epoch_row(1),
    ]) + "\n")
    merged = tmp_path / "merged.jsonl"
    assert main(["merge", str(a), str(b), "--out", str(merged)]) == 0
    capsys.readouterr()
    rc = main(["doctor", str(merged)])
    text = capsys.readouterr().out
    assert "recompile_suspicion" not in text, text
    assert rc == 0


def test_compare_fail_on_regress(tmp_path, capsys):
    """`obs compare --fail-on-regress FRAC` exits 3 when the last run
    of metrics file B fell more than FRAC below A's in examples/sec."""
    from xflow_tpu.obs.__main__ import main

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    for path, seconds in ((a, 1.0), (b, 1.25)):  # 640 and 512 ex/s
        rows = [_run_header(0)] + [
            {**_epoch_row(e), "seconds": seconds} for e in (0, 1)
        ]
        path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    assert main(["compare", str(a), str(b)]) == 0  # no flag: report only
    capsys.readouterr()
    assert main([
        "compare", "--fail-on-regress", "0.1", str(a), str(b)
    ]) == 3
    err = capsys.readouterr().err
    assert "REGRESS" in err
    # within tolerance passes, and improvement always passes
    assert main([
        "compare", "--fail-on-regress", "0.25", str(a), str(b)
    ]) == 0
    capsys.readouterr()
    assert main([
        "compare", "--fail-on-regress", "0.1", str(b), str(a)
    ]) == 0


def test_check_doctor_smoke_script():
    """Tier-1 wiring for scripts/check_doctor_smoke.py: the toy
    pipeline with the watchdog armed stays trip-free and `obs doctor`
    reports clean."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "check_doctor_smoke.py")],
        capture_output=True,
        text=True,
        timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_doctor_shed_storm_and_canary_stuck(tmp_path, capsys):
    """Serving-tier forensics (ISSUE 10): a serve_shed window where
    admission control rejected most traffic reads as shed_storm and is
    blamed on capacity — explicitly naming any serve_queue_stall trips
    as the same condition — and a rollout stream that ends on its
    open-rollout heartbeat reads as canary_stuck.  A resolved rollout
    and a quiet shed window stay clean."""
    from xflow_tpu.obs.__main__ import main

    def shed_row(frac, total):
        return {
            "t": 2.0, "kind": "serve_shed", "admitted": total * 2,
            "shed_total": total, "shed_frac": frac,
            "by_cause": {"queue_age": total}, "errors": 0,
            "depth": 12, "queue_age_s": 0.3,
        }

    def rollout_row(event):
        return {
            "t": 3.0, "kind": "rollout", "event": event,
            "from_digest": "aaa", "to_digest": "bbb",
            "canary_frac": 0.25, "canary_requests": 40,
            "canary_errors": 0, "detail": "",
        }

    stall = {
        "t": 1.0, "kind": "health", "cause": "serve_queue_stall",
        "channel": "serve", "silence_seconds": 2.0,
        "threshold_seconds": 0.5, "detail": "batch", "channels": {},
    }
    m = tmp_path / "storm.jsonl"
    m.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0), stall, shed_row(0.8, 80),
        rollout_row("begin"), rollout_row("canary"),
    ]) + "\n")
    rc = main(["doctor", str(m)])
    text = capsys.readouterr().out
    assert rc == 1
    assert "shed_storm" in text and "80%" in text
    assert "same capacity condition" in text  # not misread as a queue bug
    assert "canary_stuck" in text and "'canary'" in text

    # resolved rollout + sub-threshold shedding: serving checks clean
    m.write_text("\n".join(json.dumps(r) for r in [
        _run_header(0), shed_row(0.02, 4),
        rollout_row("begin"), rollout_row("commit"),
    ]) + "\n")
    assert main(["doctor", str(m)]) == 0
    text = capsys.readouterr().out
    # finding-code form: the tmp dir name itself contains "shed_storm"
    assert "shed_storm:" not in text and "canary_stuck:" not in text


# -- ISSUE 24: the program's names on the profiler's timeline ----------------

SCOPES = {
    "xf.wire_decode", "xf.gather", "xf.forward_backward", "xf.scatter",
    "xf.optimizer", "xf.metrics",
}


def _host_event_names(trace_dir) -> dict[str, set[int]]:
    """name -> the host lines (threads) that carry an event of that name,
    from the one xplane the profiler wrote under ``trace_dir``."""
    import jax

    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    found: dict[str, set[int]] = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                for ev in line.events:
                    found.setdefault(ev.name, set()).add(thread)
    return found


@pytest.fixture(scope="module")
def profiled_phases(tmp_path_factory):
    """One profiler session over a live Obs: a phase on the main thread, a
    phase on a worker thread, an enclosing span."""
    import threading

    import jax

    from xflow_tpu.obs import make_obs

    obs = make_obs()
    trace_dir = tmp_path_factory.mktemp("xf_spans")

    def worker():
        with obs.phase("on_worker"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(trace_dir))
    try:
        with obs.span("enclosing"):
            with obs.phase("on_main"):
                time.sleep(0.002)
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    return obs, _host_event_names(trace_dir)


@pytest.mark.parametrize("name", ["on_main", "on_worker", "enclosing"])
def test_phase_is_a_profiler_span(profiled_phases, name):
    """Every phase and span of a live Obs is an ``xf.<name>`` event on the
    JAX profiler's own timeline, from whichever thread ran it."""
    _, events = profiled_phases
    assert "xf." + name in events, sorted(n for n in events if n[:3] == "xf.")
    if name == "on_worker":
        assert events["xf.on_worker"].isdisjoint(events["xf.on_main"])


def test_profiled_phase_still_books_its_counter(profiled_phases):
    from xflow_tpu.obs import NULL_OBS
    from xflow_tpu.obs.trace import NULL_SPAN

    obs, _ = profiled_phases
    phases = obs.registry.snapshot().phase_seconds()
    assert phases["on_main"] >= 0.002 and phases["on_worker"] >= 0.002
    assert "enclosing" not in phases  # a span books no seconds
    # the disabled facade is untouched: one shared no-op, no annotation
    assert NULL_OBS.phase("x") is NULL_SPAN and NULL_OBS.span("x") is NULL_SPAN


@pytest.fixture(scope="module")
def packed_run(toy_dataset, tmp_path_factory):
    """Two epochs from packed shards with a live Obs: the metrics rows."""
    from xflow_tpu.io import packed

    root = tmp_path_factory.mktemp("xf_packed")
    out = str(root / "pk")
    assert packed.main([
        "--train", toy_dataset.train_prefix, "--out", out,
        "--batch-size", "64", "--max-nnz", "24",
        "--table-size-log2", "14", "--block-mib", "0.01",
    ]) == 0
    metrics = root / "m.jsonl"
    cfg = _toy_cfg(toy_dataset, train_path=out, metrics_out=str(metrics))
    with Trainer(cfg) as t:
        t.train()
    return [json.loads(line) for line in metrics.read_text().splitlines()]


def test_packed_epoch_books_shard_opens_overlapped(packed_run):
    """The shard opens run on stream threads: their seconds are
    ``overlapped``, and ``phases`` still sums to (at most) ``seconds``."""
    epochs = [r for r in packed_run if r["kind"] == "train_epoch"]
    assert len(epochs) == 2
    for e in epochs:
        assert {"shard_open", "remap_digest"} <= set(e["overlapped"])
        assert not {"shard_open", "remap_digest"} & set(e["phases"])
        assert e["overlapped"]["remap_digest"] <= e["overlapped"]["shard_open"]
        assert e["shard_opens"] == 3  # the toy set's three shards
        assert sum(e["phases"].values()) <= e["seconds"] * 1.01


def test_first_batch_wait_is_part_of_input_stall(packed_run):
    for e in (r for r in packed_run if r["kind"] == "train_epoch"):
        assert 0.0 < e["first_batch_wait_s"] <= e["phases"]["input_stall"]


def test_scopes_row_once_per_new_shape(packed_run):
    """The instruction -> scope map is logged by the epoch that first met
    a train shape and by no later one; the file passes the schema."""
    from xflow_tpu.obs.schema import validate_rows

    assert validate_rows(packed_run) == []
    rows = [r for r in packed_run if r["kind"] == "scopes"]
    assert [r["epoch"] for r in rows] == [0]
    scopes = {scope for _, _, scope in rows[0]["ops"]}
    assert {"xf.scatter", "xf.optimizer", "xf.metrics"} <= scopes <= SCOPES | {""}
    first = next(r for r in packed_run if r["kind"] == "train_epoch")
    assert first["phases"]["op_scopes"] > 0.0 and "_scopes" not in first


@pytest.mark.parametrize("wire_mode, wire_dedup, wire", [
    ("auto", "auto", "dict"), ("compact", "off", "compact"),
    ("full", "off", "full"),
])
def test_op_scopes_names_all_six(toy_dataset, wire_mode, wire_dedup, wire):
    """A tiny LR step with a hot head (the MXU form, whose scans keep
    their instructions apart on any backend): every instruction is given
    its name, its type as a profiler prints it, and the first xf.* of its
    path; all six scopes appear, the wire's decode among them."""
    import re

    cfg = _toy_cfg(
        toy_dataset, hot_size_log2=6, hot_nnz=8, hot_impl="mxu",
        wire_mode=wire_mode, wire_dedup=wire_dedup,
    )
    with Trainer(cfg) as t:
        assert t.step.wire_format == wire
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        rows = t.step.op_scopes(t.state, t.step.put_batch(batch))
        # lowered from shapes: the state was neither donated nor held
        assert not t.state["tables"]["w"]["param"].is_deleted()
    assert {scope for _, _, scope in rows} == SCOPES | {""}
    for name, type_, _ in rows:
        assert name and " " not in name and not name.startswith("%")
        assert type_ == "" or re.fullmatch(r"[a-z0-9]+\[[0-9,]*\]", type_)


@pytest.mark.parametrize("devices", [1, 4])
def test_op_scopes_reads_the_running_program(toy_dataset, tmp_path, devices):
    """With a live Obs the process keys its compiles by source before the
    first one, and the epoch that maps a new shape compiles nothing more:
    the text is the running executable's (one device, and the mesh's
    program with its exchange)."""
    import jax
    import jax.monitoring as mon
    from xflow_tpu.parallel.step import abstract_like

    compiled: list[float] = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(secs)

    mon.register_event_duration_secs_listener(on_duration)
    cfg = _toy_cfg(
        toy_dataset, num_devices=devices, metrics_out=str(tmp_path / "m.jsonl")
    )
    with Trainer(cfg) as t:
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        arrays = t.step.put_batch(batch)
        shapes = abstract_like(arrays)
        t.state, _ = t.step.train(t.state, arrays)
        before = len(compiled)
        rows = t.step.op_scopes(t.state, shapes)
        assert len(compiled) == before
    scopes = {scope for _, _, scope in rows}
    assert "xf.optimizer" in scopes
    assert ("xf.exchange" in scopes) == (devices > 1)


def test_null_obs_maps_no_scopes_and_annotates_nothing(toy_dataset, monkeypatch):
    """With metrics_out, obs_trace_out, obs_flight_out and obs_watchdog
    unset the trainer never calls op_scopes and creates no
    TraceAnnotation but its start-up phases'; a live Obs does both."""
    import xflow_tpu.obs as obs_mod
    from xflow_tpu.parallel.step import TrainStep

    made: list[str] = []
    real = obs_mod.TraceAnnotation

    def counting(name, **kw):
        made.append(name)
        return real(name, **kw)

    mapped: list[int] = []
    real_op_scopes = TrainStep.op_scopes

    def counting_op_scopes(self, state, arrays):
        mapped.append(1)
        return real_op_scopes(self, state, arrays)

    monkeypatch.setattr(obs_mod, "TraceAnnotation", counting)
    monkeypatch.setattr(TrainStep, "op_scopes", counting_op_scopes)
    with Trainer(_toy_cfg(toy_dataset)) as t:
        assert not t.obs.enabled
        stats = t.train_epoch()
    # the process's start-up timeline is always on (obs/startup.py): a
    # handful of annotations a TRAINER, none a step
    assert all(n.startswith("xf.startup_") for n in made) and mapped == []
    assert len(made) <= 8
    assert "_scopes" not in stats and "first_batch_wait_s" not in stats

    with Trainer(_toy_cfg(toy_dataset, obs_flight_out="unused")) as t:
        first = t.train_epoch()
        second = t.train_epoch()
    assert "xf.input_stall" in made and "xf.train_epoch" in made
    assert len(mapped) >= 1 and "_scopes" in first and "_scopes" not in second


@pytest.mark.parametrize("model, wire_mode, wire_dedup, wire, ships", [
    ("mvm", "auto", "auto", "dict", True),
    ("mvm", "compact", "off", "compact", True),
    ("lr", "auto", "auto", "dict", False),
    ("lr", "compact", "off", "compact", False),
])
def test_wire_row_says_what_the_field_ids_cost(
    toy_dataset, tmp_path, model, wire_mode, wire_dedup, wire, ships
):
    """The epoch's ``wire`` row carries ``slots_bytes_per_example`` (counter
    wire.slots_bytes, TrainStep._book_wire): the planes of field ids, which
    the compact and dictionary wires ship for a model that reads them (MVM)
    and not for LR.  It is part of wire_bytes_per_example, and the row
    validates against the schema."""
    from xflow_tpu.obs.schema import OPTIONAL, validate_rows

    out = tmp_path / "m.jsonl"
    cfg = _toy_cfg(
        toy_dataset, model=model, epochs=1, metrics_out=str(out),
        wire_mode=wire_mode, wire_dedup=wire_dedup,
    )
    with Trainer(cfg) as t:
        assert t.step.wire_format == wire and t.step._ship_slots == ships
        t.train()
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    assert "slots_bytes_per_example" in OPTIONAL["wire"]
    row = next(r for r in rows if r["kind"] == "wire")
    if ships:
        # a byte a padded slot on the compact wire (max_nnz 24 a row, the
        # last batch's padding rows too); a byte a live entry, in plane
        # capacities, on the dictionary wire
        floor = 24.0 if wire == "compact" else 1.0
        assert floor <= row["slots_bytes_per_example"] < row["wire_bytes_per_example"]
    else:
        assert row["slots_bytes_per_example"] == 0


@pytest.mark.parametrize("model, widths, plain, by_rows", [
    ("lr", {"w": 1}, [], []),
    ("fm", {"w": 1, "v": 10}, [], []),
    ("mvm", {"v": 10}, [], []),
    ("ffm", {"w": 1, "v": 64}, ["v"], ["v"]),
])
def test_wire_row_counts_the_table_rows_a_step_moves(
    toy_dataset, tmp_path, model, widths, plain, by_rows
):
    """The epoch's ``wire`` row carries, a batch and from shapes
    (TrainStep._book_wire): ``gather_row_bytes_per_step``, a row of every
    table per index the cold gather hands it; ``scatter_row_bytes_per_step``,
    a row read and a row written per padded cold slot; and in both the hot
    slots of a table that opted out of the MXU head (FFM's v), which
    ``plain_hot_slots_per_step`` counts: B x hot_nnz for FFM, 0 for the
    families whose every table rides the head; the slots that do ride it
    are ``hot_plain_slots_per_step`` / ``hot_scan_slots_per_step`` by the
    form of their gather (tests/test_tpu_compile.py holds the TPU's
    split at the benchmark's geometries).  And
    ``cold_row_layout_slots_per_step``: the padded cold slots of every
    table wide enough for the dictionary route to lay its rows out by row
    gathers (dict_cold_rows): 0 for LR, B x max_nnz for FFM (v, not w)."""
    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.parallel.step import ROW_LAYOUT_MIN_COLUMNS

    b, kc, kh = 64, 24, 8
    out = tmp_path / "m.jsonl"
    cfg = _toy_cfg(
        toy_dataset, model=model, epochs=1, metrics_out=str(out),
        batch_size=b, max_nnz=kc, hot_size_log2=6, hot_nnz=kh, max_fields=4,
        ffm_v_dim=16,
    )
    with Trainer(cfg) as t:
        assert t.step.wire_format == "dict"
        assert {s.name: s.dim for s in t.step.model.tables()} == widths
        assert [n for n, mxu in t.step._mxu_hot.items() if not mxu] == plain
        caps = []
        book = t.step._book_wire

        def spy(nbytes, examples, cb=None, **shapes):
            caps.append(len(cb.cu) + len(cb.ct))
            book(nbytes, examples, cb=cb, **shapes)

        t.step._book_wire = spy
        t.train()
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    row = next(r for r in rows if r["kind"] == "wire")
    # by hand: float32 rows; the head's own traffic is not table rows
    row_bytes = 4 * sum(widths.values())
    plain_bytes = 4 * sum(widths[n] for n in plain)
    assert row["plain_hot_slots_per_step"] == b * kh * len(plain)
    # the tables ON the head, by the form their gather took: off the TPU
    # every one is indexed ("seg"), none scanned (ops/hot.py::gather_form)
    assert t.step._hot_impl == "seg"
    assert row["hot_plain_slots_per_step"] == b * kh * (len(widths) - len(plain))
    assert row["hot_scan_slots_per_step"] == 0
    # and by the form their scatter summed them in (scatter_form): the same
    assert row["hot_scatter_plain_slots_per_step"] == row["hot_plain_slots_per_step"]
    assert row["hot_scatter_scan_slots_per_step"] == 0
    assert row["padded_cold_slots_per_step"] == b * kc
    assert by_rows == [
        n for n, d in widths.items() if d >= ROW_LAYOUT_MIN_COLUMNS
    ]
    assert row["cold_row_layout_slots_per_step"] == b * kc * len(by_rows)
    assert row["scatter_row_bytes_per_step"] == 2 * (
        b * kc * row_bytes + b * kh * plain_bytes
    )
    assert row["gather_row_bytes_per_step"] == round(
        sum(caps) / len(caps) * row_bytes + b * kh * plain_bytes
    )
    assert row["gather_row_bytes_per_step"] < row["scatter_row_bytes_per_step"]


# -- the dense half's scope and counters (PR 39) ------------------------------

_DENSE_MODELS = {
    "dcn": dict(emb_dim=4, hidden_dim=8, cross_layers=2, deep_layers=2),
    "wide_deep": dict(emb_dim=4, hidden_dim=8),
    "two_tower": dict(emb_dim=4, hidden_dim=8, tower_dim=4, tower_split_field=4),
    "xdeepfm": dict(emb_dim=10, hidden_dim=8, cross_layers=3, cin_maps=6, deep_layers=2),
    "autoint": dict(emb_dim=16, attn_heads=2, attn_dim=4, cross_layers=2),
    "fibinet": dict(emb_dim=10, hidden_dim=8, deep_layers=3, senet_reduction=3),
}


def _op_scope_rows(toy_dataset, **overrides):
    cfg = _toy_cfg(toy_dataset, max_fields=8, **overrides)
    with Trainer(cfg) as t:
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        return t.step.op_scopes(t.state, t.step.put_batch(batch))


@pytest.mark.parametrize("model", sorted(_DENSE_MODELS))
def test_a_dense_family_runs_its_dense_half_under_xf_dense(toy_dataset, model):
    """A family that owns replicated dense parameters opens ``xf.dense``
    inside ``xf.forward_backward``, and ``op_scopes`` gives an instruction
    the INNERMOST name of its path: the dense half's operations, forward
    and backward, map to ``xf.dense``, the field contraction around them
    stays ``xf.forward_backward``'s, and the dense SGD ``xf.optimizer``'s."""
    rows = _op_scope_rows(toy_dataset, model=model, **_DENSE_MODELS[model])
    scopes = {scope for _, _, scope in rows}
    assert {"xf.dense", "xf.forward_backward", "xf.optimizer"} <= scopes
    assert scopes <= SCOPES | {"xf.dense", "xf.cin", "xf.attn", "xf.bilinear", ""}
    assert ("xf.cin" in scopes) == (model == "xdeepfm")
    assert ("xf.attn" in scopes) == (model == "autoint")
    assert ("xf.bilinear" in scopes) == (model == "fibinet")


def test_xf_cin_is_the_innermost_name_of_every_cin_operation(toy_dataset):
    """xDeepFM's CIN runs under ``xf.cin``, a sibling of ``xf.dense`` inside
    ``xf.forward_backward``, through a loop over slices and a rematerialised
    backward.  In the compiled step every operation whose path holds
    ``xf.cin`` has it as the INNERMOST ``xf.`` name, whatever wraps it
    (``jvp``, ``transpose``, ``while/body``, ``closed_call/checkpoint``),
    and both kinds are there: the forward's, and the rematerialised
    backward's inside its loop; the CIN's contractions are among both, and
    ``op_scopes`` maps the compiled instructions to it.  (The whole step through
    ``xf.forward_backward``: tests/test_tpu_compile.py reads the same off
    the program compiled for a v5e at the cell's geometry.)"""
    from xflow_tpu.parallel.step import _SCOPE_RE, abstract_like, scope_of

    cfg = _toy_cfg(toy_dataset, model="xdeepfm", max_fields=8, **_DENSE_MODELS["xdeepfm"])
    with Trainer(cfg) as t:
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        arrays = t.step.put_batch(batch)
        text = t.step.train.lower(
            abstract_like(t.state), abstract_like(arrays)
        ).compile().as_text()
        rows = t.step.op_scopes(t.state, arrays)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    cin = [p for p in paths if "xf.cin" in p]
    assert len(cin) >= 10
    assert all(scope_of(p) == "xf.cin" for p in cin)
    assert all(_SCOPE_RE.findall(p)[0] == "xf.forward_backward" for p in cin)
    forward = [p for p in cin if "jvp(xf.cin)" in p and "transpose" not in p]
    backward = [p for p in cin if "transpose(jvp(xf.cin))" in p]
    rematerialised = [p for p in backward if "/checkpoint/" in p]
    assert forward and backward and rematerialised
    assert all("while/body" in p for p in rematerialised)
    # the contractions with a cin_w are the CIN's, forward and backward,
    # and no product is among what the backward multiplies again
    dots = [p for p in cin if p.endswith("dot_general")]
    assert [p for p in dots if p in forward] and [p for p in dots if p in backward]
    assert not [p for p in dots if "rematted_computation" in p]
    assert sum(scope == "xf.cin" for _, _, scope in rows) >= 10


def test_xf_attn_is_the_innermost_name_of_every_attention_operation(toy_dataset):
    """AutoInt's interacting layers run under ``xf.attn``, a sibling of
    ``xf.dense`` inside ``xf.forward_backward``, through a loop over slices
    and a backward that computes each slice's forward again.  In the compiled
    step every operation whose path holds ``xf.attn`` has it as the INNERMOST
    ``xf.`` name, whatever wraps it, and all three kinds are there: the
    forward's inside its loop, the forward done again inside the backward's
    loop (``checkpoint/rematted_computation``), and the backward's; the
    products (projections, scores, weighted sums) are among all three, the
    softmax's ``exp`` among the first two, and ``op_scopes`` maps the
    compiled instructions to the scope.  The output product stays
    ``xf.dense``'s."""
    from xflow_tpu.parallel.step import _SCOPE_RE, abstract_like, scope_of

    cfg = _toy_cfg(toy_dataset, model="autoint", max_fields=8, **_DENSE_MODELS["autoint"])
    with Trainer(cfg) as t:
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        arrays = t.step.put_batch(batch)
        text = t.step.train.lower(
            abstract_like(t.state), abstract_like(arrays)
        ).compile().as_text()
        rows = t.step.op_scopes(t.state, arrays)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    attn = [p for p in paths if "xf.attn" in p]
    assert len(attn) >= 20
    assert all(scope_of(p) == "xf.attn" for p in attn)
    assert all(_SCOPE_RE.findall(p)[0] == "xf.forward_backward" for p in attn)
    forward = [p for p in attn if "jvp(xf.attn)" in p and "transpose" not in p]
    backward = [p for p in attn if "transpose(jvp(xf.attn))" in p]
    again = [p for p in backward if "rematted_computation" in p]
    assert forward and backward and again
    assert all("while/body" in p and "/checkpoint/" in p for p in again)
    for kind in (forward, again, [p for p in backward if p not in again]):
        assert [p for p in kind if p.endswith("dot_general")]
    assert [p for p in forward if p.endswith("/exp")]
    assert [p for p in again if p.endswith("/exp")]
    assert sum(scope == "xf.attn" for _, _, scope in rows) >= 20
    assert sum(scope == "xf.dense" for _, _, scope in rows) >= 2


def test_xf_bilinear_is_the_innermost_name_of_every_block_operation(toy_dataset):
    """FiBiNET's gate-and-bilinear block runs under ``xf.bilinear``, a sibling
    of ``xf.dense`` inside ``xf.forward_backward``.  In the compiled step every
    operation whose path holds ``xf.bilinear`` has it as the INNERMOST ``xf.``
    name, whatever wraps it (``jvp``, ``transpose``, ``checkpoint``), forward
    and backward are both there with the fields' products among them, the
    hidden stack's products stay ``xf.dense``'s, and ``op_scopes`` maps the
    compiled instructions to it: the rows the ``_scopes`` record ships (the
    schema holds a scope as a string: ``test_dense_counters_reach_the_wire_row``
    validates a run of this family)."""
    from xflow_tpu.parallel.step import _SCOPE_RE, abstract_like, scope_of

    cfg = _toy_cfg(toy_dataset, model="fibinet", max_fields=8, **_DENSE_MODELS["fibinet"])
    with Trainer(cfg) as t:
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        arrays = t.step.put_batch(batch)
        text = t.step.train.lower(
            abstract_like(t.state), abstract_like(arrays)
        ).compile().as_text()
        rows = t.step.op_scopes(t.state, arrays)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    block = [p for p in paths if "xf.bilinear" in p]
    assert len(block) >= 10
    assert all(scope_of(p) == "xf.bilinear" for p in block)
    assert all(_SCOPE_RE.findall(p)[0] == "xf.forward_backward" for p in block)
    forward = [p for p in block if "transpose" not in p]
    backward = [p for p in block if "transpose(jvp(xf.bilinear))" in p]
    assert forward and backward
    dots = [p for p in block if p.endswith("dot_general")]
    assert [p for p in dots if p in forward] and [p for p in dots if p in backward]
    dense = [p for p in paths if scope_of(p) == "xf.dense"]
    assert [p for p in dense if p.endswith("dot_general")]
    assert sum(scope == "xf.bilinear" for _, _, scope in rows) >= 20
    assert sum(scope == "xf.dense" for _, _, scope in rows) >= 6


@pytest.mark.parametrize("model, overrides", [
    ("lr", {}), ("fm", {}), ("mvm", {"max_fields": 8}), ("ffm", {"max_fields": 8}),
])
def test_the_measured_families_keep_their_six_scopes(toy_dataset, model, overrides):
    """LR, FM, MVM and FFM have no dense half: no ``xf.dense`` row, and no
    instruction whose path nests two different ``xf.`` names, so the
    innermost-name rule maps them as the first-name rule did."""
    from xflow_tpu.parallel.step import _SCOPE_RE, abstract_like

    cfg = _toy_cfg(
        toy_dataset, model=model, hot_size_log2=6, hot_nnz=8, **overrides
    )
    with Trainer(cfg) as t:
        batch, _, _ = next(iter(t.iter_train_batches(0, 0)))
        arrays = t.step.put_batch(batch)
        rows = t.step.op_scopes(t.state, arrays)
        text = t.step.train.lower(
            abstract_like(t.state), abstract_like(arrays)
        ).compile().as_text()
    assert {scope for _, _, scope in rows} <= SCOPES | {""}
    nested = [
        path for path in re.findall(r'op_name="([^"]*)"', text)
        if len(set(_SCOPE_RE.findall(path))) > 1
    ]
    assert not nested, nested[:3]


def test_scope_of_takes_the_innermost_name():
    from xflow_tpu.parallel.step import scope_of

    assert scope_of("jit(f)/xf.forward_backward/jvp(xf.dense)/dot_general") == "xf.dense"
    assert scope_of(
        "jit(f)/xf.forward_backward/transpose(xf.forward_backward)/jvp(xf.dense)/select_n"
    ) == "xf.dense"
    assert scope_of("jit(f)/xf.scatter/xf.scatter/scatter-add") == "xf.scatter"
    assert scope_of("jit(f)/transpose(jvp(xf.gather))/mul") == "xf.gather"
    assert scope_of("jit(f)/reduce_sum") == ""
    assert scope_of(
        "jit(f)/xf.forward_backward/transpose(jvp(xf.cin))/while/body/closed_call/"
        "checkpoint/rematted_computation/mul"
    ) == "xf.cin"
    assert scope_of(
        "jit(f)/xf.forward_backward/transpose(jvp(xf.attn))/while/body/closed_call/"
        "checkpoint/rematted_computation/exp"
    ) == "xf.attn"
    assert scope_of(
        "jit(f)/xf.forward_backward/transpose(jvp(xf.bilinear))/checkpoint/"
        "rematted_computation/dot_general"
    ) == "xf.bilinear"


@pytest.mark.parametrize("model", sorted(_DENSE_MODELS))
def test_dense_counters_reach_the_wire_row_from_shapes(toy_dataset, tmp_path, model):
    """``dense_param_bytes`` is the bytes of the state's dense arrays and
    ``dense_matmul_flops_per_step`` the benchmark's own count
    (``harness/costs.py``) for the products the model declares; for DCN
    those are the products the benchmark's reference reads off the dense
    arrays' shapes."""
    from benchmarks.harness import costs
    from benchmarks.layer_metrics import attn_mxu_roofline, cin_mxu_roofline
    from benchmarks.reference import (
        autoint_criteo, dcn_criteo, fibinet_criteo, xdeepfm_criteo,
    )
    from xflow_tpu.obs.schema import OPTIONAL, validate_rows

    metrics = tmp_path / "m.jsonl"
    cfg = _toy_cfg(
        toy_dataset, model=model, max_fields=8, epochs=1,
        metrics_out=str(metrics), **_DENSE_MODELS[model],
    )
    with Trainer(cfg) as t:
        t.train()
        dense = t.state["dense"]
        matmuls = t.step.model.dense_matmuls()
        shapes = {name: list(a.shape) for name, a in dense.items()}
        if model == "dcn":
            assert dcn_criteo.matmuls(shapes) == matmuls
        if model == "xdeepfm":
            assert xdeepfm_criteo.matmuls(shapes) == matmuls
        if model == "autoint":
            assert autoint_criteo.matmuls(shapes) == matmuls
        if model == "fibinet":
            assert fibinet_criteo.matmuls(shapes) == matmuls
        param_bytes = sum(a.size * 4 for a in dense.values())
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert validate_rows(rows) == []
    (wire,) = [r for r in rows if r["kind"] == "wire"]
    assert {"dense_param_bytes", "dense_matmul_flops_per_step"} <= set(OPTIONAL["wire"])
    assert wire["dense_param_bytes"] == param_bytes
    want = costs.train_step(
        {"table_size_log2": cfg.table_size_log2, "batch_size": cfg.batch_size},
        {"w": 1}, entries_per_step=1.0, hot_share=0.0, matmuls=matmuls,
    )["flops"]
    assert wire["dense_matmul_flops_per_step"] == want > 0
    # what else a family hands the step (Model.dense_counters) reaches the
    # row under its own name: the slice of xDeepFM's CIN, the operations,
    # score bytes and slice of AutoInt's attention, and nothing of any other
    # family.  The CIN's operations have ONE owner in the program, the
    # model's dense_matmuls; the benchmark's reader counts them again from
    # the configuration's fields, and the two agree
    own = set(wire) - {"dense_param_bytes", "dense_matmul_flops_per_step"}
    own = {k for k in own if k.startswith("dense_")}
    if model == "autoint":
        assert own == {
            "dense_attn_flops", "dense_attn_score_bytes", "dense_attn_slice_rows",
        } <= set(OPTIONAL["wire"])
        assert wire["dense_attn_slice_rows"] == cfg.batch_size  # a toy batch goes whole
        b, m, layers = cfg.batch_size, cfg.max_fields, cfg.cross_layers
        assert wire["dense_attn_score_bytes"] == 4 * b * layers * 2 * cfg.attn_heads * m * m
        # the block's operations: the model's declared products less the
        # output's, and the benchmark reader's own count from the fields
        assert wire["dense_attn_flops"] == 6 * b * sum(
            k * n for k, n in matmuls[:-1]
        ) == attn_mxu_roofline.attn_flops({
            "batch_size": b, "emb_dim": cfg.emb_dim, "max_fields": m,
            "attn_heads": cfg.attn_heads, "attn_dim": cfg.attn_dim,
            "cross_layers": layers,
        })
        return
    if model != "xdeepfm":
        assert not own
        return
    assert own == {"dense_cin_slice_rows"} <= set(OPTIONAL["wire"])
    assert wire["dense_cin_slice_rows"] == cfg.batch_size  # a toy batch goes whole
    b, d, m, maps = cfg.batch_size, cfg.emb_dim, cfg.max_fields, cfg.cin_maps
    assert cin_mxu_roofline.cin_flops(
        {"batch_size": b, "emb_dim": d, "max_fields": m, "cin_maps": maps,
         "cross_layers": cfg.cross_layers}
    ) == 6 * b * sum(k * n for k, n in matmuls[:cfg.cross_layers])


def test_a_family_without_dense_parameters_books_no_dense_counter(packed_run):
    (wire, *_) = [r for r in packed_run if r["kind"] == "wire"]
    assert not [k for k in wire if k.startswith("dense_")]


# -- the pass on the resident layout (PR 59) ----------------------------------

_DLRM_128 = dict(
    emb_dim=128, numeric_fields=3, max_fields=8, mlp_bottom="16-128",
    mlp_top="32-16",
)


# tables of T = 2^12 rows; by hand: the elements of every table of 64
# columns or more that (8,128) tiles pad less rows-minor (D rounded up to
# 8 under D rounded up to 128), where the dense update makes its one pass
# a step on one device
@pytest.mark.parametrize("model, overrides, devices, elements", [
    ("lr", {}, 1, 0),                                     # w [T, 1]
    ("fm", {}, 1, 0),                                     # v [T, 10]
    ("mvm", {}, 1, 0),                                    # v [T, 10]
    ("ffm", {"max_fields": 40, "ffm_v_dim": 4}, 1, 4096 * 160),
    ("ffm", {"max_fields": 4, "ffm_v_dim": 16}, 1, 4096 * 64),
    ("ffm", {"max_fields": 32, "ffm_v_dim": 4}, 1, 0),    # 128: a lane tile
    ("dlrm", _DLRM_128, 1, 0),                            # emb [T, 128]
    ("ffm", {"max_fields": 40, "ffm_v_dim": 4}, 4, 0),    # a mesh
    ("ffm", {"max_fields": 40, "ffm_v_dim": 4, "update_mode": "sequential",
             "microbatch": 4, "sequential_inner": "dense"}, 1, 0),
    ("ffm", {"max_fields": 40, "ffm_v_dim": 4, "update_mode": "sparse",
             "hot_size_log2": 0, "hot_nnz": 0}, 1, 0),
])
def test_wire_row_counts_the_elements_of_the_resident_pass(
    model, overrides, devices, elements
):
    """``resident_pass_elements`` of TrainStep._book_wire (the ``_wire``
    row's ``resident_pass_elements_per_step``, optional in the schema),
    from shapes: the elements of the tables whose dense pass
    _optimizer_pass holds to the layout the chip keeps the state in
    (step.py::resident_pass_selects).  FFM's v at libffm's 40 x 4 = 160
    columns and nothing else the benchmark measures: LR's, FM's and MVM's
    tables are narrower, DLRM's 128 columns fill a lane tile; and nothing
    on a mesh or where the update makes no one pass a step."""
    import types

    from xflow_tpu.models import make_model
    from xflow_tpu.obs.schema import OPTIONAL
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep

    cfg = Config(**{
        **dict(
            model=model, optimizer="ftrl", table_size_log2=12, batch_size=64,
            max_nnz=6, hot_size_log2=5, hot_nnz=6, num_devices=devices,
        ),
        **overrides,
    })
    step = TrainStep(
        make_model(cfg), make_optimizer(cfg), cfg, make_mesh(devices)
    )
    booked: dict[str, float] = {}
    step.obs = types.SimpleNamespace(
        counter=lambda name, v=1.0: booked.__setitem__(name, v)
    )
    b = cfg.batch_size
    step._book_wire(0, b, cold_slots=b * cfg.max_nnz, hot_slots=b * cfg.hot_nnz)
    assert booked["wire.resident_pass_elements"] == elements
    assert "resident_pass_elements_per_step" in OPTIONAL["wire"]

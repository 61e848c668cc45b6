"""ISSUE 36: the serving worker says what it is doing, on the profiler's
clock.  Spans of a live fleet under a profiler session on the CPU, the same
boundaries as seconds in the ``serve_stats`` row, the collector hook, and the
benchmark's readers (``benchmarks/harness/serve_spans.py`` and the nine
``layer_metrics``) on hand-made inputs."""

import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmarks.harness import manifest, scope_times, serve_spans
from benchmarks.harness import trace_reduce as tr
from xflow_tpu.config import Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = ["xf.serve_wait", "xf.serve_coalesce", "xf.serve_batch"]
CHILDREN = [
    "xf.serve_featurize", "xf.serve_h2d", "xf.serve_dispatch",
    "xf.serve_fetch", "xf.serve_resolve",
]
NEW_FIELDS = [
    f"{leg}_{p}" for leg in ("h2d", "dispatch", "fetch", "resolve")
    for p in ("p50", "p99", "max")
] + [
    "coalesce_p50", "workers", "worker_busy_s", "batch_p99", "batch_max",
    "seal_late_p99", "seal_late_max", "gc_pauses", "gc_pause_max",
    "gc_pause_total",
]
MS = 1e6  # ns


def _engine(model="lr", **over):
    """A warmed engine over an untrained state: no trainer, no data."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import init_state
    from xflow_tpu.serve.engine import PredictEngine

    cfg = Config(**{
        "model": model, "table_size_log2": 10, "batch_size": 8, "max_nnz": 8,
        "max_fields": 8, "tower_split_field": 4, "tower_dim": 4,
        "num_devices": 1, **over,
    })
    mesh = make_mesh(1)
    state = init_state(make_model(cfg), make_optimizer(cfg), cfg, mesh)
    return PredictEngine(cfg, state, mesh=mesh, buckets=(4, 8))


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1024, 3).astype(np.int64) for _ in range(n)]


def _item_index(n=6, dim=6, nnz=3):
    rng = np.random.default_rng(0)
    return {
        "count": n, "dim": dim,
        "item_index": rng.normal(size=(n, dim)).astype(np.float32),
        "item_ids": (10 + np.arange(n)).astype(np.int64),
        "item_keys": rng.integers(0, 1024, (n, nnz)).astype(np.int64),
        "item_slots": np.full((n, nnz), 5, np.int32),
        "item_vals": np.ones((n, nnz), np.float32),
        "item_nnz": np.full(n, nnz, np.int32),
    }


# -- (a) the spans, under a profiler session ---------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One replica, loaded WITHOUT an Obs as the benchmark loads it, under one
    profiler session: bursts of rows with pauses between (so the worker
    waits, coalesces and scores), and one forced collection."""
    import jax

    from xflow_tpu.serve.fleet import ReplicaFleet

    trace_dir = tmp_path_factory.mktemp("serve_spans")
    fleet = ReplicaFleet(_engine(), replicas=1, max_wait_ms=2.0)
    try:
        fleet.submit(_rows(1)[0]).result(timeout=60)  # past the first call
        jax.profiler.start_trace(str(trace_dir))
        try:
            # the slice, as the benchmark's driver marks it
            with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + "loadgen"):
                for burst in range(6):
                    # a span still open when the session stops is not
                    # recorded: pause BEFORE each burst, so that the slice
                    # ends on a batch and not inside a wait
                    time.sleep(0.004)
                    if burst == 3:
                        gc.collect()
                    futs = [fleet.submit(r) for r in _rows(11, burst)]
                    for f in futs:
                        f.result(timeout=60)
        finally:
            jax.profiler.stop_trace()
        row = fleet.emit_stats()["stats"]
    finally:
        fleet.close()
    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    return scope_times.read_host_spans(str(path)), row, str(path)


@pytest.mark.parametrize("name", WORKER + CHILDREN + ["xf.gc"])
def test_span_is_on_the_host_timeline(traced, name):
    spans, _, _ = traced
    assert any(s[0] == name for s in spans), sorted({s[0] for s in spans})


def test_worker_is_inside_one_span_at_a_time(traced):
    spans, _, _ = traced
    mine = sorted((s, s + d, n) for n, _, s, d in spans if n in WORKER)
    threads = {t for n, t, _, _ in spans if n in WORKER}
    assert len(threads) == 1  # one replica, one worker thread
    for (_, end, name), (start, _, nxt) in zip(mine, mine[1:]):
        assert start >= end, (name, nxt)
    # and it cycles: a batch follows every coalesce
    order = [n for _, _, n in mine]
    for i, name in enumerate(order[:-1]):
        if name == "xf.serve_coalesce":
            assert order[i + 1] == "xf.serve_batch"


@pytest.mark.parametrize("child", CHILDREN)
def test_children_lie_inside_their_batch(traced, child):
    spans, _, _ = traced
    batches = [(s, s + d, t) for n, t, s, d in spans if n == "xf.serve_batch"]
    kids = [(s, s + d, t) for n, t, s, d in spans if n == child]
    assert len(kids) == len(batches)
    for s, e, t in kids:
        assert any(bs <= s and e <= be and bt == t for bs, be, bt in batches)


# -- (b) the same boundaries as seconds ---------------------------------------


@pytest.mark.parametrize("leg", ["score", "topk"])
def test_call_legs_add_up_to_the_device_call(leg):
    """Per batch, h2d + dispatch + fetch lie inside the device call: three
    disjoint intervals between consecutive reads of the clock that times the
    call (``engine.py::_put_dispatch_fetch`` inside ``batcher.py::
    _score_sealed``), so each is there, none is negative and their sum is
    not over the call's.  What the call holds beside them (the compact-wire
    check before, the slice after, and whatever the scheduler took from the
    worker's thread between two reads) is a host's, not the program's: no
    share of the call is asserted.  The batch span's ``phases`` carry the
    split under the new keys, for both legs."""
    from xflow_tpu.obs.reqtrace import ReqTraceSink
    from xflow_tpu.serve.fleet import ReplicaFleet

    sink = ReqTraceSink(sample=1.0)
    rows = [(k, np.arange(3, dtype=np.int32), None) for k in _rows(5)]
    if leg == "topk":
        engine = _engine("two_tower")
        engine.attach_item_index(_item_index(), topk_k=4)
    else:
        engine = _engine()
    bursts = (1, 5, 5, 5, 5, 5, 5, 5)
    with ReplicaFleet(
        engine, replicas=1, topk=leg == "topk", reqtrace=sink,
        depth_budget=1024, deadline_budget_ms=60000.0,
    ) as fleet:
        for measured in (False, True):
            # the first pass compiles both buckets: a compile is no leg's,
            # it is the phase serve_compile
            for n in bursts:
                for f in [fleet.submit(*r) for r in rows[:n]]:
                    f.result(timeout=60)
            if not measured:
                sink.flush()
        batches = [r for r in sink.flush() if r["span"] == "batch"]
        stats = fleet.emit_stats()["stats"]
    # a burst is sent when the one before it has resolved, so no batch
    # holds rows of two; a submitter that the host held longer than the
    # coalescing wait between two rows has its burst sealed in pieces
    assert len(batches) >= len(bursts)
    assert sum(b["n"] for b in batches) == sum(bursts)
    for b in batches:
        ph = b["phases"]
        assert set(ph) == {"featurize", "device", "h2d", "dispatch", "fetch"}
        legs = ph["h2d"] + ph["dispatch"] + ph["fetch"]
        assert min(ph["h2d"], ph["dispatch"], ph["fetch"]) >= 0.0
        assert legs <= ph["device"] + 3e-6, ph  # each rounded to the µs
    # every leg's clock was read: none is booked as nothing
    assert min(stats["h2d_p50"], stats["dispatch_p50"], stats["fetch_p50"]) > 0


@pytest.mark.parametrize("stream", ["new", "old"])
def test_stats_row_validates_with_and_without_the_fields(traced, stream):
    from xflow_tpu.obs.schema import validate_row

    _, row, _ = traced
    assert set(NEW_FIELDS) <= set(row)
    if stream == "old":  # a stream from before ISSUE 36
        row = {k: v for k, v in row.items() if k not in NEW_FIELDS}
    assert validate_row({"t": 0.0, "kind": "serve_stats", **row}) == []


def test_stats_row_accounts_for_the_worker(traced):
    _, row, _ = traced
    assert row["workers"] == 1 and row["batches"] >= 6
    # the worker was busy for at least its batches' device calls and
    # resolves, and for no longer than the window
    assert row["worker_busy_s"] >= row["batches"] * min(
        row["fetch_p50"], row["resolve_p50"]
    ) > 0
    for leg in ("h2d", "dispatch", "fetch", "resolve"):
        assert 0 < row[f"{leg}_p50"] <= row[f"{leg}_p99"] <= row[f"{leg}_max"]
    # no batch is longer than the busy seconds, none shorter than its legs
    assert row["fetch_max"] < row["batch_max"] <= row["worker_busy_s"]
    assert row["batch_p99"] <= row["batch_max"]
    # the forced collection was booked, with the others of the window
    assert row["gc_pauses"] >= 1
    assert 0 < row["gc_pause_max"] <= row["gc_pause_total"]


# -- (c) the collector hook ----------------------------------------------------


def test_gc_hook_books_a_collection_and_goes_with_close():
    from xflow_tpu.obs import GcPauses
    from xflow_tpu.serve.fleet import ReplicaFleet

    before = list(gc.callbacks)
    fleet = ReplicaFleet(_engine(), replicas=1)
    try:
        hooks = [cb for cb in gc.callbacks if isinstance(cb, GcPauses)]
        assert len(hooks) == len(
            [cb for cb in before if isinstance(cb, GcPauses)]
        ) + 1
        fleet.emit_stats()
        gc.collect()
        row = fleet.emit_stats()["stats"]
        assert row["gc_pauses"] >= 1 and row["gc_pause_max"] > 0
        # the non-destructive view books them too
        gc.collect(0)
        assert fleet.stats()["stats"]["gc_pauses"] >= 1
        snap = fleet.registry.snapshot()
        assert any(
            k.startswith("serve.gc_pause_seconds.gen") for k in snap.counters
        )
    finally:
        fleet.close()
    assert gc.callbacks == before
    fleet.close()  # idempotent: nothing to remove twice
    assert gc.callbacks == before


def test_gc_hook_takes_no_lock_inside_a_collection():
    """A collection can start inside the registry's own allocation, under
    its lock: the hook must gather without it."""
    from xflow_tpu.obs import GcPauses
    from xflow_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    hook = GcPauses(reg, "serve")
    with reg._lock:  # held, as by an observe() the collector interrupted
        hook("start", {"generation": 2})
        hook("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    hook("stop", {"generation": 0})  # a stop with no start: ignored
    assert reg.snapshot().hists == {}
    hook.flush()
    snap = reg.snapshot()
    assert snap.hists["serve.gc_pause_seconds"]["count"] == 1
    assert snap.counters["serve.gc_pause_seconds.gen2"] > 0


def test_gc_hook_loses_no_collection_under_threads():
    """More threads than cores making garbage, a short switch interval and a
    flusher running beside them: every collection the interpreter counted is
    booked exactly once."""
    from xflow_tpu.obs import GcPauses
    from xflow_tpu.obs.registry import MetricsRegistry

    def collections():
        return sum(g["collections"] for g in gc.get_stats())

    reg = MetricsRegistry()
    hook = GcPauses(reg, "serve")
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            junk = [[i] for i in range(500)]
            junk.append(junk)  # a cycle: only the collector frees it

    def flusher():
        while not stop.is_set():
            hook.flush()

    interval = sys.getswitchinterval()
    threads = [threading.Thread(target=churn) for _ in range((os.cpu_count() or 4) + 2)]
    threads.append(threading.Thread(target=flusher))
    gc.disable()  # no collection between the readings and the hook's life
    try:
        sys.setswitchinterval(1e-5)
        hook.install()
        before = collections()
        gc.enable()
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        gc.disable()
        ran = collections() - before
        hook.remove()
    finally:
        stop.set()
        hook.remove()
        sys.setswitchinterval(interval)
        gc.enable()
    hook.flush()
    assert ran > 10
    assert reg.snapshot().hists["serve.gc_pause_seconds"]["count"] == ran


# -- (d) seal_late --------------------------------------------------------------


class _Echo:
    """Minimal engine contract for MicroBatcher, no jax involved."""

    buckets = (1, 8)
    digest = "echo0000"

    def featurize(self, rows):
        return [keys for keys, _, _ in rows]

    def predict_prepared(self, batch):
        return np.asarray([float(k[0]) for k in batch])


@pytest.mark.parametrize("sealed_by", ["max_batch", "deadline"])
def test_seal_late_and_coalesce(sealed_by):
    from xflow_tpu.serve.batcher import MicroBatcher

    wait_ms = 400.0 if sealed_by == "max_batch" else 20.0
    mb = MicroBatcher(_Echo(), max_wait_ms=wait_ms, max_batch=4)
    try:
        n = 4 if sealed_by == "max_batch" else 2
        futs = [mb.submit(np.asarray([i])) for i in range(n)]
        assert [f.result(timeout=30) for f in futs] == list(map(float, range(n)))
    finally:
        row = mb.close()
    assert row["batches"] == 1 and row["workers"] == 1
    if sealed_by == "max_batch":
        # full before its deadline: never late, and it did not wait it out
        assert row["seal_late_max"] == 0.0
        assert row["coalesce_p50"] < wait_ms / 1e3
    else:
        # held open until the deadline ran out, sealed at or after it
        assert row["coalesce_p50"] >= wait_ms / 1e3
        assert row["seal_late_max"] == pytest.approx(
            row["coalesce_p50"] - wait_ms / 1e3, abs=2e-6
        )
    assert row["worker_busy_s"] > 0 and row["resolve_p50"] > 0
    # an engine that keeps no split books none
    assert row["h2d_p50"] == row["dispatch_p50"] == row["fetch_p50"] == 0.0


# -- (e) serve_spans.attribute on a hand-made trace -----------------------------


def hand_made():
    """One device over [0, 100) ms, one worker thread (1), a collection on
    another (2).

    device: [10,11) and [30,31) inside two fetches, [90,92) inside a third.
    worker: wait [0,5), coalesce [5,7), batch [7,14) with fetch [9,13);
    wait [14,25), coalesce [25,27), batch [27,34) with fetch [29,33);
    wait [34,80), coalesce [80,82), batch [82,95) with fetch [88,94);
    wait [95,100).  gc [40,70) on thread 2.
    idle gaps: [0,10) [11,30) [31,90) [92,100).
    """
    ops = [
        ("fusion f32[64]", 10 * MS, 1 * MS), ("fusion f32[64]", 30 * MS, 1 * MS),
        ("fusion f32[64]", 90 * MS, 2 * MS),
    ]
    host = []
    for w0, c0, b0, f0, f1, b1 in [
        (0, 5, 7, 9, 13, 14), (14, 25, 27, 29, 33, 34), (34, 80, 82, 88, 94, 95),
    ]:
        host += [
            ("xf.serve_wait", 1, w0 * MS, (c0 - w0) * MS),
            ("xf.serve_coalesce", 1, c0 * MS, (b0 - c0) * MS),
            ("xf.serve_batch", 1, b0 * MS, (b1 - b0) * MS),
            ("xf.serve_fetch", 1, f0 * MS, (f1 - f0) * MS),
        ]
    host += [
        ("xf.serve_wait", 1, 95 * MS, 5 * MS), ("xf.gc", 2, 40 * MS, 30 * MS),
    ]
    return tr.Trace({0: ops}, [("loadgen", 0, 100 * MS)]), host


def test_serve_spans_on_a_hand_made_trace():
    trace, host = hand_made()
    got = serve_spans.attribute(trace, host, (0, 100 * MS), gaps=2)
    assert got["busy_s"] == pytest.approx(0.004)
    assert got["idle_s"] == pytest.approx(0.096)
    # wait is open [0,5) [14,25) [34,80) [95,100): all of it idle
    assert got["idle_s_by_span"]["xf.serve_wait"] == pytest.approx(0.067)
    assert got["open_s_by_span"]["xf.serve_wait"] == pytest.approx(0.067)
    assert got["idle_s_by_span"]["xf.gc"] == pytest.approx(0.030)
    # fetch is open 4 + 4 + 6 ms, the device busy 1 + 1 + 2 of them
    assert got["open_s_by_span"]["xf.serve_fetch"] == pytest.approx(0.014)
    assert got["busy_s_by_span"]["xf.serve_fetch"] == pytest.approx(0.004)
    assert got["busy_s_by_span"]["xf.serve_wait"] == 0.0
    # the worker is always inside one of its three: every idle second is
    assert got["idle_under_worker_s"] == pytest.approx(got["idle_s"])
    assert got["threads_by_span"]["xf.serve_batch"] == 1
    # the longest gap [31,90) starts inside the second batch's fetch and
    # lies mostly under the wait that followed; the collection is in it
    first, second = got["longest_gaps"]
    assert first["s"] == pytest.approx(0.059) and first["at_s"] == pytest.approx(0.031)
    assert first["span"] == "xf.serve_batch"
    assert first["open"] == ["xf.serve_batch", "xf.serve_fetch"]
    assert first["mostly"] == "xf.serve_wait"
    assert first["s_by_span"]["xf.serve_wait"] == pytest.approx(0.046)
    assert first["s_by_span"]["xf.gc"] == pytest.approx(0.030)
    assert first["s_by_span"]["xf.serve_batch"] == pytest.approx(0.003 + 0.008)
    assert second["s"] == pytest.approx(0.019) and second["mostly"] == "xf.serve_wait"
    # a batch still open when the session stopped was not recorded: its
    # child that was names it
    lost = [s for s in host if not (s[0] == "xf.serve_batch" and s[2] == 27 * MS)]
    got = serve_spans.attribute(trace, lost, (0, 100 * MS), gaps=1)
    assert got["longest_gaps"][0]["open"] == ["xf.serve_fetch"]
    assert got["longest_gaps"][0]["span"] == "xf.serve_batch"


def test_serve_spans_without_the_programs_spans():
    """The parent of the PR that brought the spans: the arithmetic holds, no
    span is reported, every gap is nameless, and the readers read nothing."""
    trace, _ = hand_made()
    got = serve_spans.attribute(trace, [], (0, 100 * MS))
    assert got["idle_s"] == pytest.approx(0.096)
    assert got["idle_s_by_span"] == got["busy_s_by_span"] == {}
    assert got["idle_under_worker_s"] == 0.0
    assert [g["span"] for g in got["longest_gaps"]] == [None] * 4
    run = {
        "trace": {"source": "device_planes"}, "window": {"seconds": 1.0},
        "serve_span_times": got,
    }
    for name in ("serve_fetch_device_busy_frac", "serve_idle_in_wait_frac"):
        assert manifest.layer_metric(name).read(run) is None


def test_load_joins_the_run_in_progress(traced, monkeypatch):
    """``load`` on a real profile (a CPU backend's: operations on host
    threads): the slice is the ``xfb:loadgen`` span, every idle second lies
    under the worker's three spans but their seams, and the result is kept
    in ``run``; a metric of the device reads nothing off a CPU's trace."""
    _, _, path = traced
    monkeypatch.setattr(scope_times, "find_xplane", lambda: path)
    run = {"trace": {"source": "host_threads"}, "window": {"seconds": 1.0}}
    got = serve_spans.load(run)
    assert got is run["serve_span_times"] and got["source"] == "host_threads"
    assert got["idle_s"] > 0 and got["busy_s"] > 0
    # (how MUCH of the idle time lies under them is read exactly on the
    # hand-made trace above; here the rest is the seams between two spans,
    # as long as the host leaves the worker's thread off a core in one)
    assert 0 < got["idle_under_worker_s"] <= got["idle_s"]
    assert got["busy_s_by_span"]["xf.serve_batch"] > 0
    assert got["busy_s_by_span"]["xf.serve_wait"] == 0.0
    # (the wait that was open when the session started is not recorded, so
    # the gap at the slice's first instant is the one that may be nameless)
    assert all(
        g["span"] in serve_spans.WORKER_SPANS
        for g in got["longest_gaps"] if g["at_s"] > 0
    )
    assert serve_spans.on_device(run) is None
    # no trace, no window (a train cell): nothing, and the file is not sought
    monkeypatch.setattr(scope_times, "find_xplane", lambda: 1 / 0)
    assert serve_spans.load({"trace": None, "window": {}}) is None
    assert serve_spans.load({"trace": {"steps": 16}}) is None


def test_fetch_share_does_not_mind_the_device_clocks_lead():
    """On the chip the device planes lead the host planes by more than a
    fetch lasts: the intersection moves to another span, the reading of
    ``serve_fetch_device_busy_frac`` stays."""
    trace, host = hand_made()
    early = tr.Trace(
        {0: [(n, s - 5 * MS, d) for n, s, d in trace.devices[0]]}, trace.spans
    )
    reader = manifest.layer_metric("serve_fetch_device_busy_frac")
    runs = [
        {"trace": {"source": "device_planes"}, "window": {},
         "serve_span_times": serve_spans.attribute(t, host, (0, 100 * MS))}
        for t in (trace, early)
    ]
    assert runs[1]["serve_span_times"]["busy_s_by_span"]["xf.serve_fetch"] == 0.0
    assert reader.read(runs[0]) == reader.read(runs[1]) == pytest.approx(4 / 14)


# -- (f) the readers ---------------------------------------------------------------


def _run(stats, times=None, source="device_planes"):
    return {
        "trace": {"source": source},
        "window": {"seconds": 30.0, "serve_stats": stats},
        "serve_span_times": times,
    }


STATS = {
    "device_p50": 0.0035, "h2d_p50": 0.0011, "dispatch_p50": 0.0004,
    "fetch_p50": 0.0019, "resolve_p50": 0.0007, "worker_busy_s": 36.0,
    "workers": 2, "seal_late_p99": 0.0012, "gc_pause_max": 0.0,
}


@pytest.mark.parametrize("name, want", [
    ("serve_h2d_ms_p50", 1.1), ("serve_dispatch_ms_p50", 0.4),
    ("serve_fetch_ms_p50", 1.9), ("serve_resolve_ms_p50", 0.7),
    ("serve_worker_busy_frac", 0.6), ("serve_seal_late_ms_p99", 1.2),
    ("serve_gc_pause_ms_max", 0.0),  # no collection ran: 0.0, not None
    ("serve_fetch_device_busy_frac", 4 / 14),  # all busy time ÷ fetch open
    ("serve_idle_in_wait_frac", 67 / 96),
])
def test_reader(name, want):
    reader = manifest.layer_metric(name)
    times = serve_spans.attribute(*hand_made(), (0, 100 * MS))
    assert reader.read(_run(STATS, times)) == pytest.approx(want)
    # a row from before the fields, a trace from before the spans, a CPU
    # backend's trace, an untraced run, a train cell: nothing, and no raise
    old = {"device_p50": 0.0035}
    assert reader.read(_run(old, None)) is None
    assert reader.read(_run(old, times, source="host_threads")) in (None,)
    assert reader.read({"trace": None, "window": {"seconds": 30.0, "serve_stats": old}}) is None
    assert reader.read({"trace": {"source": "device_planes", "steps": 16}, "epochs": []}) is None
    entry = next(
        m for m in manifest.load()["per_layer"] if m["name"] == name
    )
    assert entry["workloads"] == ["lr_tb.serve_rows"]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) == (
        reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE
    )


# -- (g) the cell's rehearsal -------------------------------------------------------


def test_the_serve_cell_rehearses_with_the_new_metrics():
    """benchmarks/run.py --workload lr_tb.serve_rows --rehearsal --trace 1:
    the fleet is loaded bare, as the driver loads it, and the seven metrics
    of the ``serve_stats`` row are reported (the two of the device trace need
    device planes, which a CPU backend has not)."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "lr_tb.serve_rows",
         "--rehearsal", "--trace", "1", "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and all(last["checks"].values())
    assert {
        "serve_h2d_ms_p50", "serve_dispatch_ms_p50", "serve_fetch_ms_p50",
        "serve_resolve_ms_p50", "serve_worker_busy_frac",
        "serve_seal_late_ms_p99", "serve_gc_pause_ms_max",
        "serve_device_ms_p50", "serve_queue_ms_p50",
    } <= set(last["per_layer_reported"])

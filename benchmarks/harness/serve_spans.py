"""What the serving worker was doing while the device did nothing: the join
of a serve cell's profiler slice with the program's ``xf.serve_*`` spans.

The micro-batcher's worker thread is, at any instant, inside exactly one of
``xf.serve_wait`` (blocked on an empty queue), ``xf.serve_coalesce`` (holding
a batch open) and ``xf.serve_batch`` (featurize, the engine's h2d / dispatch /
fetch, resolve), and a collector pause is ``xf.gc`` on whichever thread it ran
(``xflow_tpu/serve/batcher.py``, ``engine.py``, ``obs/__init__.py``), whether
or not the fleet was handed an ``Obs``.  ``scope_times.attribute`` already
lays device idle time against host spans; this module adds device BUSY time
under a span and the longest gaps with the spans they lay under.

``attribute`` is the arithmetic, on a ``trace_reduce.Trace`` and plain lists,
so that it can be checked without a chip.  ``load`` finds the trace of the run
in progress and is what the readers under ``layer_metrics/`` call; it keeps
its result in ``run["serve_span_times"]``, which lands in ``.last.json``.
"""

from __future__ import annotations

from benchmarks.harness import scope_times, trace_reduce

# the three of which the worker is always inside exactly one
WORKER_SPANS = ("xf.serve_wait", "xf.serve_coalesce", "xf.serve_batch")
# what nests inside ``xf.serve_batch``: the profiler keeps no span that is
# still open when its session stops, so at the slice's end a child can be
# there without its parent
BATCH_CHILDREN = (
    "xf.serve_featurize", "xf.serve_h2d", "xf.serve_dispatch",
    "xf.serve_fetch", "xf.serve_resolve",
)


def _opened(host_spans: list, name: str, t0: float, t1: float) -> list:
    return trace_reduce.union(trace_reduce.clip(
        [(s, s + d) for n, _, s, d in host_spans if n == name], t0, t1
    ))


def _inside(a: list, b: list) -> float:
    """Nanoseconds of disjoint sorted ``a`` that lie in disjoint sorted ``b``."""
    return trace_reduce.length(a) - trace_reduce.length(trace_reduce.subtract(a, b))


def attribute(
    trace: trace_reduce.Trace,
    host_spans: list[scope_times.HostSpan],
    window: trace_reduce.Interval,
    gaps: int = 5,
) -> dict:
    """``scope_times.attribute``'s idle and open seconds by span over
    ``window``, and beside them: device busy seconds under each span (the
    worst device's, as the idle is), the idle seconds under any of
    ``WORKER_SPANS``, and the ``gaps`` longest idle gaps, each with the
    worker's span that was open at its start (``span``), every ``xf.`` span
    open there from the outermost in (``open``), its seconds by span
    (``s_by_span``) and which of ``WORKER_SPANS`` holds the most of it
    (``mostly``: a gap starts where a device operation ends, inside a batch,
    and one that runs on through the next wait lies mostly under the wait).

    The device planes' clock is not the host planes': on the v5e the
    operations of a batch's program read 1.5-1.9 ms BEFORE the start of the
    ``xf.serve_dispatch`` that enqueued it (PERF.md, PR 36).  Gaps and idle
    seconds of many milliseconds do not mind; ``busy_s_by_span`` of a span of
    one millisecond does, and puts the busy time under the span that was
    open that much earlier."""
    t0, t1 = window
    times = scope_times.attribute(trace, host_spans, window, 0, [])
    busy = {
        dev: trace_reduce.union(
            trace_reduce.clip([(s, s + d) for _, s, d in ops], t0, t1)
        )
        for dev, ops in trace.devices.items()
    }
    worst = busy[min(busy, key=lambda dev: trace_reduce.length(busy[dev]))]
    idle = trace_reduce.subtract([(t0, t1)], worst)
    opened = {name: _opened(host_spans, name, t0, t1) for name in times["open_s_by_span"]}
    worker = trace_reduce.union(
        [iv for name in WORKER_SPANS for iv in opened.get(name, [])]
    )
    longest = []
    for s, e in sorted(idle, key=lambda iv: iv[0] - iv[1])[:gaps]:
        at_start = sorted(
            (start, name) for name, _, start, dur in host_spans
            if start <= s < start + dur
        )
        names = [name for _, name in at_start]
        by_span = {
            name: _inside([(s, e)], ivs) / 1e9 for name, ivs in opened.items()
        }
        longest.append({
            "s": (e - s) / 1e9,
            "at_s": (s - t0) / 1e9,
            "span": next(
                (n for n in names if n in WORKER_SPANS),
                "xf.serve_batch" if set(names) & set(BATCH_CHILDREN) else None,
            ),
            "open": names,
            "mostly": max(
                (n for n in WORKER_SPANS if by_span.get(n)),
                key=by_span.get, default=None,
            ),
            "s_by_span": {n: v for n, v in sorted(by_span.items()) if v > 0},
        })
    return {
        "source": trace.source,
        "window_s": times["window_s"],
        "busy_s": trace_reduce.length(worst) / 1e9,
        "idle_s": times["idle_s"],
        "idle_s_by_span": times["idle_s_by_span"],
        "open_s_by_span": times["open_s_by_span"],
        "threads_by_span": times["threads_by_span"],
        "busy_s_by_span": {
            name: _inside(ivs, worst) / 1e9 for name, ivs in sorted(opened.items())
        },
        "idle_under_worker_s": _inside(idle, worker) / 1e9,
        "longest_gaps": longest,
    }


def load(run: dict) -> dict | None:
    """``attribute`` over the traced slice of a serve cell's ``run``;
    ``None`` where there is no such trace to read (an untraced run, a
    train cell)."""
    if "serve_span_times" in run:
        return run["serve_span_times"]
    run["serve_span_times"] = None
    path = (
        scope_times.find_xplane()
        if run.get("trace") and run.get("window") else None
    )
    if path is None:
        return None
    trace = trace_reduce.load_xplane(path)
    try:
        window = trace_reduce.span_window(trace, "loadgen")
    except ValueError:
        return None
    run["serve_span_times"] = attribute(
        trace, scope_times.read_host_spans(path), window
    )
    return run["serve_span_times"]


def on_device(run: dict) -> dict | None:
    """``load`` for a metric of the device: ``None`` unless the trace's
    operations come from device planes (a CPU backend's host threads are
    not a device), as ``scope_times.on_device``."""
    reduced = run.get("trace")
    if not reduced or reduced.get("source") != "device_planes":
        return None
    return load(run)


def stats_field(run: dict, field: str):
    """``field`` of the window's ``serve_stats`` row; ``None`` where the run
    has no window or the program's row no such field (a program from before
    the field)."""
    window = run.get("window")
    return window["serve_stats"].get(field) if window else None


def stats_ms(run: dict, field: str):
    """``stats_field`` of a field in seconds, in milliseconds."""
    seconds = stats_field(run, field)
    return None if seconds is None else 1e3 * seconds

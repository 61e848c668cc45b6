"""Device time by the program's own names, and device idle time by what
the program's host threads were doing: the join of a run's profiler trace
with what the program says about itself.

The program puts every name it gives its own work on the profiler's timeline
under the prefix ``xf.``: device operations through ``jax.named_scope``, host
phases through ``jax.profiler.TraceAnnotation``.  A device operation's event
carries the instruction's name, not its scope, so the program also says, once
per train program, which instruction belongs to which scope
(``TrainStep.op_scopes``): the trainer's epoch record carries those rows as
``_scopes``, and ``run["epochs"]`` / ``run["warmup"]`` are epoch records
passed through whole.

``attribute`` is the arithmetic, on a ``trace_reduce.Trace`` and plain lists,
so that it can be checked without a chip.  ``load`` finds the trace of the run
in progress and is what the readers under ``layer_metrics/`` call; it keeps
its result in ``run["scope_times"]``, which lands in ``.last.json``.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

from benchmarks.harness import manifest, trace_reduce

PREFIX = "xf."
_TYPE_RE = re.compile(r"^[a-z0-9]+\[[0-9,]*\]$")
# (span name, thread, start_ns, duration_ns): thread is the index of the
# host line the event was on
HostSpan = tuple[str, int, float, float]


def scope_rows(run: dict) -> list[list[str]]:
    """Every ``[name, type, scope]`` row the trainer logged in this run."""
    return [
        row
        for epoch in run.get("epochs", []) + run.get("warmup", [])
        for row in epoch.get("_scopes", {}).get("ops", [])
    ]


class ScopeMap:
    """From a device operation as the trace names it to the scope the
    program gave it.  ``None``: no program of the run has such an
    instruction.  ``""``: it has no scope, or two programs give the key two
    different scopes (``ambiguous``)."""

    def __init__(self, rows: list[list[str]]):
        self.by_key: dict[tuple[str, str], set[str]] = {}
        self.by_name: dict[str, set[str]] = {}
        for name, type_, scope in rows:
            self.by_key.setdefault((name, type_), set()).add(scope)
            self.by_name.setdefault(name, set()).add(scope)

    def scopes_of(self, op: str) -> set[str] | None:
        """``op`` is ``trace_reduce.short_name``'s output on a TPU
        (``fusion.10 s32[3670016] kCustom``: joined on name and type) and
        the bare ``hlo_op`` on a CPU backend (joined on the name alone)."""
        parts = op.split(" ")
        if len(parts) > 1 and _TYPE_RE.match(parts[1]):
            return self.by_key.get((parts[0], parts[1]))
        return self.by_name.get(parts[0])


def self_times(ops: list[tuple[str, float, float]], t0: float, t1: float) -> dict[str, float]:
    """Nanoseconds inside [t0, t1) by operation name, each instant given to
    the innermost (latest started) operation running in it: a ``while``
    holds its body's operations and an asynchronous copy runs beside others,
    so plain durations add up to more than the device was busy.  These add
    up to the union of the intervals exactly."""
    out: dict[str, float] = {}
    events = sorted(
        (max(s, t0), min(s + d, t1), name)
        for name, s, d in ops if s + d > t0 and s < t1 and d > 0
    )
    running: list[tuple[float, float, str]] = []  # max-heap on start

    def spend(frm: float, to: float) -> None:
        # [frm, to) goes to the latest-started operation still running,
        # then to the one under it
        cur = frm
        while running and cur < to:
            neg_start, end, name = running[0]
            if end <= cur:
                heapq.heappop(running)
                continue
            upto = min(end, to)
            out[name] = out.get(name, 0.0) + (upto - cur)
            cur = upto

    cursor = t0
    for s, e, name in events:
        spend(cursor, s)
        cursor = max(cursor, s)
        heapq.heappush(running, (-s, e, name))
    spend(cursor, t1)
    return out


def attribute(
    trace: trace_reduce.Trace,
    host_spans: list[HostSpan],
    window: trace_reduce.Interval,
    steps: int,
    rows: list[list[str]],
    top: int = 10,
) -> dict:
    """Seconds of device time inside ``window`` by scope (mean over
    devices, as ``reduce``'s ``busy_s``), what no scope covers, and device
    idle seconds by the ``xf.`` host span open meanwhile (worst device, as
    ``reduce``'s idle gaps)."""
    t0, t1 = window
    scope_map = ScopeMap(rows)
    ndev = len(trace.devices)
    by_scope: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    parts = {"no_scope": 0.0, "ambiguous": 0.0, "unmapped": 0.0}
    busy: dict[int, list] = {}
    for dev, ops in trace.devices.items():
        busy[dev] = trace_reduce.union(
            trace_reduce.clip([(s, s + d) for _, s, d in ops], t0, t1)
        )
        for name, ns in self_times(ops, t0, t1).items():
            sec = ns / 1e9 / ndev
            found = scope_map.scopes_of(name)
            if found is not None and len(found) == 1 and "" not in found:
                scope = next(iter(found))
                by_scope[scope] = by_scope.get(scope, 0.0) + sec
                continue
            why = (
                "unmapped" if found is None
                else "ambiguous" if len(found) > 1 else "no_scope"
            )
            parts[why] += sec
            unscoped[name] = unscoped.get(name, 0.0) + sec
    busy_s = sum(trace_reduce.length(b) for b in busy.values()) / 1e9 / ndev
    unscoped_s = sum(unscoped.values())

    worst = min(busy, key=lambda dev: trace_reduce.length(busy[dev]))
    idle = trace_reduce.subtract([(t0, t1)], busy[worst])
    idle_by_span: dict[str, float] = {}
    open_by_span: dict[str, float] = {}
    threads: dict[str, set] = {}
    for name in sorted({s[0] for s in host_spans}):
        mine = [s for s in host_spans if s[0] == name]
        opened = trace_reduce.union(
            trace_reduce.clip([(s, s + d) for _, _, s, d in mine], t0, t1)
        )
        if not opened:
            continue
        open_by_span[name] = trace_reduce.length(opened) / 1e9
        idle_by_span[name] = (
            trace_reduce.length(idle)
            - trace_reduce.length(trace_reduce.subtract(idle, opened))
        ) / 1e9
        threads[name] = {thread for _, thread, _, _ in mine}
    return {
        "source": trace.source,
        "window_s": (t1 - t0) / 1e9,
        "steps": steps,
        "scope_rows": len(rows),
        "busy_s": busy_s,
        "device_s_by_scope": dict(sorted(by_scope.items())),
        "unscoped_s": unscoped_s,
        "unscoped_parts_s": parts,
        "top_unscoped": [
            [name, sec]
            for name, sec in sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
        ],
        # the scope times and the unscoped part are exclusive times: they
        # add up to busy_s, the union, by construction
        "sum_over_busy": (sum(by_scope.values()) + unscoped_s) / busy_s
        if busy_s else None,
        "idle_s": trace_reduce.length(idle) / 1e9,
        "idle_s_by_span": idle_by_span,
        "open_s_by_span": open_by_span,
        "threads_by_span": {k: len(v) for k, v in threads.items()},
    }


def read_host_spans(path: str) -> list[HostSpan]:
    """What ``trace_reduce.load_xplane`` drops: the host events whose name
    starts with ``xf.``, with the thread (host line) each was on."""
    import jax

    spans: list[HostSpan] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((
                        ev.name, thread,
                        float(ev.start_ns), float(ev.duration_ns),
                    ))
    return spans


def find_xplane() -> str | None:
    """The profiler's file of the run in progress: a reader is handed only
    ``run``, and the harness keeps no path, but a run's work directory is
    the only place under ``.bench_cache`` with a ``trace`` directory (the
    harness moves the file out when the run ends)."""
    found = glob.glob(os.path.join(
        manifest.ROOT, ".bench_cache", "*", "trace", "plugins", "profile",
        "*", "*.xplane.pb",
    ))
    return found[0] if len(found) == 1 else None


def load(run: dict) -> dict | None:
    """``attribute`` over the traced epoch of ``run``; ``None`` where there
    is no trace to read (an untraced run, a serve cell)."""
    if "scope_times" in run:
        return run["scope_times"]
    run["scope_times"] = None
    reduced = run.get("trace")
    path = find_xplane() if reduced and reduced.get("steps") else None
    if path is None:
        return None
    trace = trace_reduce.load_xplane(path)
    try:
        window = trace_reduce.span_window(trace, "epoch")
    except ValueError:
        return None
    run["scope_times"] = attribute(
        trace, read_host_spans(path), window, reduced["steps"], scope_rows(run)
    )
    return run["scope_times"]


def on_device(run: dict) -> dict | None:
    """``load`` for a metric of the device: ``None`` unless the trace's
    operations come from device planes (a CPU backend's host threads are
    not a device)."""
    reduced = run.get("trace")
    if not reduced or reduced.get("source") != "device_planes":
        return None
    return load(run)


def scope_ms_per_step(run: dict, scope: str):
    """Device milliseconds a step under ``scope``; ``None`` where the
    program logged no scope map (a program from before the scopes)."""
    times = on_device(run)
    if not times or not times["scope_rows"]:
        return None
    return 1e3 * times["device_s_by_scope"].get(scope, 0.0) / times["steps"]

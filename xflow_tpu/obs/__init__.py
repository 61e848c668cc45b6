"""Observability subsystem (ISSUE 1): span tracing, pipeline-health
metrics, and the trace/summary toolchain.

Three layers, all off-by-default-cheap:

* ``trace`` — nested context-manager spans, ring-buffered, exported as
  Chrome trace-event JSON for Perfetto (Config.obs_trace_out);
* ``registry`` — counters/gauges/histograms: per-phase wall-second
  accounting (parse, pack, h2d, dispatch, input stall, checkpoint,
  device block), step-time percentiles, transfer-ahead occupancy;
* ``summary`` / ``__main__`` — ``python -m xflow_tpu.obs summarize
  run.jsonl`` turns metrics JSONL into phase/throughput tables;
  ``compare a.jsonl b.jsonl`` diffs two runs.

The ``Obs`` facade bundles one tracer and one registry and is threaded
through the hot path (Trainer, TrainStep.put_batch, ShardLoader).  When
disabled, ``NULL_OBS`` is a shared object whose ``phase()`` returns one
shared no-op context manager — no per-step allocation.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation

from xflow_tpu.obs.registry import (
    NULL_REGISTRY,
    MetricsRegistry,
    NullRegistry,
    Snapshot,
)
from xflow_tpu.obs.trace import NULL_SPAN, NULL_TRACER, NullTracer, SpanTracer

__all__ = [
    "Obs",
    "NULL_OBS",
    "make_obs",
    "profiler_span",
    "GcPauses",
    "SpanTracer",
    "NullTracer",
    "MetricsRegistry",
    "NullRegistry",
    "Snapshot",
]


# Every name the program gives to its own work appears on the JAX
# profiler's timeline under this prefix: host phases and spans through
# TraceAnnotation (here), device operations through jax.named_scope
# (parallel/step.py, ops/hot.py).  An annotation outside a profiler
# session is one flag check.  ``xfb:`` is the benchmark harness's.
PROFILER_PREFIX = "xf."


def profiler_span(name: str, **args: Any) -> TraceAnnotation:
    """An ``xf.<name>`` span on the JAX profiler's timeline and nothing
    else: for a caller that keeps its own clocks and may hold no
    ``Obs`` (the serve worker, whose fleet the caller loads bare).
    ``args`` ride as the event's arguments inside a session."""
    return TraceAnnotation(PROFILER_PREFIX + name, **args)


class GcPauses:
    """A ``gc.callbacks`` hook: every collection is an ``xf.gc`` span on
    whichever thread it ran, and its pause is booked, with the
    collection's generation, as ``<prefix>.gc_pause_seconds``.

    A collection can start inside ANY allocation, the registry's own
    under its lock among them, so the hook takes no lock: it appends
    to a deque (collections never nest, appends are atomic) and
    ``flush`` moves what has gathered into the registry from the
    caller's thread."""

    def __init__(self, registry: MetricsRegistry, prefix: str):
        self._registry = registry
        self._name = prefix + ".gc_pause_seconds"
        self._pauses: collections.deque = collections.deque()
        self._open: tuple[TraceAnnotation, float] | None = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            span = profiler_span("gc", generation=info["generation"])
            span.__enter__()
            self._open = (span, time.perf_counter())
        elif self._open is not None:
            span, t0 = self._open
            self._open = None
            self._pauses.append(
                (info["generation"], time.perf_counter() - t0)
            )
            span.__exit__(None, None, None)

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        """Idempotent; what gathered since the last flush stays
        flushable."""
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def flush(self) -> None:
        while self._pauses:
            generation, dt = self._pauses.popleft()
            self._registry.observe(self._name, dt)
            self._registry.counter_add(f"{self._name}.gen{generation}", dt)


class _Phase:
    """Times a block and books it as a ``phase.<name>`` counter
    (wall-second accounting), as a trace span, and as an ``xf.<name>``
    span on the JAX profiler's clock, where a device gap can be laid
    against it."""

    __slots__ = ("_obs", "_name", "_t0", "_annotation", "seconds")

    def __init__(self, obs: "Obs", name: str):
        self._obs = obs
        self._name = name
        self._annotation = profiler_span(name)

    def __enter__(self) -> "_Phase":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._obs.registry.counter_add("phase." + self._name, dt)
        self._obs.tracer.add_complete(self._name, self._t0, dt)
        return None


class _ProfiledSpan:
    """A tracer span that is also an ``xf.<name>`` span on the JAX
    profiler's timeline (no phase counter)."""

    __slots__ = ("_span", "_annotation")

    def __init__(self, span, name: str):
        self._span = span
        self._annotation = profiler_span(name)

    def __enter__(self) -> "_ProfiledSpan":
        self._annotation.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._span.__exit__(*exc)
        self._annotation.__exit__(*exc)
        return None


class Obs:
    """One tracer + one registry (and, when diagnosis is on, one
    flight recorder) shared by everything in a run.

    ``flight`` is the heartbeat sink (obs/flight.py): hot paths that
    hold an Obs — ShardLoader, PredictEngine — pulse it with
    ``note_loader``/``note_serve`` so the watchdog (obs/watchdog.py)
    can classify silence.  None when diagnosis is off: callers guard
    with ``if obs.flight is not None`` (one attribute read per beat
    site, nothing allocated).

    ``metrics_logger`` is the ``health``-row sink for the self-healing
    fabric (xflow_tpu/chaos/heal.py): a retried read, a quarantined
    record, a restarted worker must be LOUD whenever a metrics stream
    exists at all — not only when the flight recorder happens to be on
    (Trainer sets it alongside its MetricsLogger)."""

    __slots__ = ("tracer", "registry", "flight", "metrics_logger")
    enabled = True

    def __init__(self, tracer=None, registry=None, flight=None,
                 metrics_logger=None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.registry = (
            registry if registry is not None else MetricsRegistry()
        )
        self.flight = flight
        self.metrics_logger = metrics_logger

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def span(self, name: str, tags: dict | None = None):
        """Trace-only span (no phase counter) — for enclosing scopes
        like a whole epoch, where counting the seconds would double the
        inner phases."""
        return _ProfiledSpan(self.tracer.span(name, tags), name)

    def counter(self, name: str, v: float = 1.0) -> None:
        self.registry.counter_add(name, v)

    def gauge(self, name: str, v: float) -> None:
        self.registry.gauge_set(name, v)

    def observe(self, name: str, v: float) -> None:
        self.registry.observe(name, v)


class NullObs:
    """Disabled facade: every path is a no-op; ``phase``/``span`` return
    the one shared ``NULL_SPAN`` — zero per-step allocation."""

    __slots__ = ()
    enabled = False
    tracer = NULL_TRACER
    registry = NULL_REGISTRY
    flight = None
    metrics_logger = None

    def phase(self, name: str):
        return NULL_SPAN

    def span(self, name: str, tags: dict | None = None):
        return NULL_SPAN

    def counter(self, name: str, v: float = 1.0) -> None:
        pass

    def gauge(self, name: str, v: float) -> None:
        pass

    def observe(self, name: str, v: float) -> None:
        pass


NULL_OBS = NullObs()


def make_obs(
    trace: bool = False,
    trace_capacity: int = 65536,
    rank: int = 0,
    step_fn: Callable[[], int] | None = None,
) -> Obs:
    """Live Obs: registry always, tracer only when ``trace``."""
    tracer = (
        SpanTracer(capacity=trace_capacity, rank=rank, step_fn=step_fn)
        if trace
        else NULL_TRACER
    )
    return Obs(tracer=tracer, registry=MetricsRegistry())

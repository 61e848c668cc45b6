"""The process's start-up timeline and its compile watch (ISSUE 55).

What lies between a process's first line and its first steady step
belongs to the process, not to a ``Trainer`` or an engine, and happens
once: so this is ALWAYS on, like the serve worker's bare annotations,
needs no ``Obs``, and sits on no hot path (a few dozen clock reads a
process, one listener call a compile).

* ``phase(name)`` times a block on ``time.perf_counter()`` into one
  bounded list and is an ``xf.startup_<name>`` span on the JAX
  profiler's timeline.  Phases nest by time on one thread, as the
  serve spans do; a record carries its thread's name.
* ``watch_compiles()`` registers the process's ONE pair of
  ``jax.monitoring`` listeners (``utils/compile_cache.py`` and
  ``Trainer`` call it; idempotent): compile requests, which XLA either
  compiled or loaded from the persistent cache, their seconds, and the
  cache's hits, so ``compiled = requests - cache_hits``.
* ``snapshot()`` is what the carriers hand on: the first
  ``train_epoch`` of a trainer (``_startup`` -> the ``startup``
  metrics row) and the ``serve_stats`` row (``startup``).

docs/OBSERVABILITY.md "Start-up" has every phase with the code that
opens it and the benchmark metric that reads it.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any

from xflow_tpu.obs import NULL_OBS, profiler_span

# jax._src.dispatch.BACKEND_COMPILE_EVENT and jax._src.compiler's hit
# event, checked against the installed jax 0.9.0: the duration is
# recorded around compile_or_get_cached, so a request is a program
# compiled OR loaded; the hit is recorded inside it, on the same thread
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SPAN_PREFIX = "startup_"


class _StartupPhase:
    """One timed block of a ``Timeline``; ``seconds`` after exit."""

    __slots__ = ("_timeline", "_name", "_obs", "_annotation", "_t0", "seconds")

    def __init__(self, timeline: "Timeline", name: str, obs):
        self._timeline = timeline
        self._name = name
        self._obs = obs
        self._annotation = profiler_span(SPAN_PREFIX + name)

    def __enter__(self) -> "_StartupPhase":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._timeline._add_phase(self._name, self._t0, dt)
        if self._obs.enabled:
            name = SPAN_PREFIX + self._name
            self._obs.registry.counter_add("phase." + name, dt)
            self._obs.tracer.add_complete(name, self._t0, dt)
        return None


class Timeline:
    """Start-up phases and compile events of one process, both bounded
    to their newest entries (a test process builds many trainers)."""

    def __init__(self, max_phases: int = 256, max_events: int = 32):
        self.origin = time.perf_counter()
        self._lock = threading.Lock()
        self._phases: collections.deque = collections.deque(maxlen=max_phases)
        self._events: collections.deque = collections.deque(maxlen=max_events)
        self._requests = 0
        self._cache_hits = 0
        self._seconds = 0.0
        # the hit event comes before its request's duration event, on
        # the compiling thread
        self._thread = threading.local()
        self._watching = False

    # -- phases -------------------------------------------------------------

    def phase(self, name: str, obs=NULL_OBS) -> _StartupPhase:
        """Time a block as start-up phase ``name``.  With a live ``obs``
        the seconds are booked as ``phase.startup_<name>`` and traced
        too; the timeline needs none."""
        return _StartupPhase(self, name, obs)

    def _add_phase(self, name: str, start: float, seconds: float) -> None:
        record = {
            "name": name,
            "start": start,
            "seconds": seconds,
            "thread": threading.current_thread().name,
        }
        with self._lock:
            self._phases.append(record)

    # -- compile watch ------------------------------------------------------

    def watch_compiles(self) -> None:
        """Register this timeline with ``jax.monitoring``, once."""
        with self._lock:
            if self._watching:
                return
            self._watching = True
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_: Any) -> None:
        if event == CACHE_HIT_EVENT:
            self._thread.hit = True
            with self._lock:
                self._cache_hits += 1

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event != COMPILE_EVENT:
            return
        cached = getattr(self._thread, "hit", False)
        self._thread.hit = False
        record = {
            "t": time.perf_counter(),
            "seconds": secs,
            "cached": cached,
            "fun_name": str(kw.get("fun_name", "")),
        }
        with self._lock:
            self._requests += 1
            self._seconds += secs
            self._events.append(record)

    def compile_totals(self) -> dict:
        """Compile requests since ``watch_compiles``: how many, how many
        of them the persistent cache served, and their seconds."""
        with self._lock:
            return {
                "requests": self._requests,
                "cache_hits": self._cache_hits,
                "seconds": self._seconds,
            }

    # -- carriers -----------------------------------------------------------

    def snapshot(self) -> dict:
        """Everything so far, as JSON types.  ``origin`` and every
        ``start`` / ``t`` / ``at`` are ``time.perf_counter()`` readings
        of this process; ``process_age_at_origin_s`` (where /proc says)
        is how long the process had lived when this module was
        imported: interpreter start and the imports before it."""
        at = time.perf_counter()
        with self._lock:
            out = {
                "origin": self.origin,
                "at": at,
                "phases": [dict(p) for p in self._phases],
                "compiles": {
                    "requests": self._requests,
                    "cache_hits": self._cache_hits,
                    "compiled": self._requests - self._cache_hits,
                    "seconds": self._seconds,
                    "recent": [dict(e) for e in self._events],
                },
            }
        age = _process_age_s()
        if age is not None:
            out["process_age_at_origin_s"] = round(age - (at - self.origin), 3)
        return out


def _process_age_s() -> float | None:
    """Seconds since the kernel started this process, from
    /proc/self/stat's ``starttime`` and /proc/uptime; None elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces: fields 3.. follow its ")"
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def compile_delta(before: dict, after: dict) -> dict:
    """``compile_totals`` readings ``before`` and ``after`` a stretch,
    as the three keys an epoch row carries."""
    return {
        "compiles": after["requests"] - before["requests"],
        "compiles_cached": after["cache_hits"] - before["cache_hits"],
        "compile_seconds": round(after["seconds"] - before["seconds"], 6),
    }


# The process's own.  A caller that wants a timeline apart (a test)
# builds a ``Timeline``.
TIMELINE = Timeline()
phase = TIMELINE.phase
snapshot = TIMELINE.snapshot
watch_compiles = TIMELINE.watch_compiles
compile_totals = TIMELINE.compile_totals


def init_backend() -> None:
    """The backend's own start (plug-in discovery, the chip's runtime)
    as phase ``backend_init``, for an entry point to call before its
    first trainer or load: otherwise it hides in their first
    ``make_mesh``.  jax 0.9.0 records no monitoring event for it."""
    with phase("backend_init"):
        import jax

        jax.devices()

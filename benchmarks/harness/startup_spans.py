"""A run's ``setup_s`` by part, from the program's own start-up timeline.

The program keeps one timeline a process (``xflow_tpu/obs/startup.py``:
always on, with or without an ``Obs``) and hands a snapshot of it to whoever
holds its records: a trainer's FIRST ``train_epoch()`` carries ``_startup``,
which ``train_cell.run`` keeps whole in ``run["warmup"][0]``; a fleet's
``serve_stats`` row carries ``startup``, which the serve driver copies to
``run["window"]`` and ``run["warmup"]``.  A snapshot holds ``phases``
(``{name, start, seconds, thread}`` on ``time.perf_counter()``, the clock
``run["setup_s"]`` is read on) and ``compiles`` (the compile watch's totals:
``requests`` = compiled OR loaded, ``cache_hits``, ``compiled``, ``seconds``).

The program's top-level parts of a set-up, by kind of cell:

    train   trainer_init + the warm-up epochs (the first under the span
            ``first_epoch``, a later one by its record's ``seconds``)
    serve   trainer_init + export_artifact + fleet_load, and then
            ``warmup_s`` of traffic, which is the harness's

What is left of ``setup_s`` lies outside the program: the interpreter, the
imports, the backend's start, the corpus or its cache entry, the harness's
own jitted state.  A program from before the timeline has no snapshot: every
function here returns ``None`` for it and raises nothing.
"""

from __future__ import annotations


def snapshot(run: dict) -> dict | None:
    """The run's start-up snapshot: as the set-up ended."""
    warm = run.get("warmup")
    if isinstance(warm, list):  # a train cell: its epoch records
        return warm[0].get("_startup") if warm else None
    for part in (run.get("window"), warm):  # a serve cell: a constant
        if part and part["serve_stats"].get("startup"):
            return part["serve_stats"]["startup"]
    return None


def span_s(snap: dict, name: str) -> float | None:
    """Seconds of the newest phase ``name``; ``None`` where there is none."""
    for phase in reversed(snap["phases"]):
        if phase["name"] == name:
            return phase["seconds"]
    return None


def warmup_epochs_s(run: dict) -> float | None:
    """Seconds of a train cell's warm-up epochs, the first by the program's
    span around the whole call."""
    snap = snapshot(run)
    first = span_s(snap, "first_epoch") if snap else None
    if first is None:
        return None
    return first + sum(e["seconds"] for e in run["warmup"][1:])


def program_parts(run: dict) -> dict[str, float] | None:
    """The program's top-level parts of this run's set-up, in seconds;
    ``None`` unless every one is there."""
    snap = snapshot(run)
    if snap is None:
        return None
    if isinstance(run["warmup"], list):
        parts = {
            "trainer_init": span_s(snap, "trainer_init"),
            "warmup_epochs": warmup_epochs_s(run),
        }
    else:
        parts = {
            name: span_s(snap, name)
            for name in ("trainer_init", "export_artifact", "fleet_load")
        }
    return None if None in parts.values() else parts


def outside_program_s(run: dict) -> float | None:
    """``run["setup_s"]`` less ``program_parts`` less, in a serve cell, the
    warm-up traffic's seconds."""
    parts = program_parts(run)
    if parts is None or "setup_s" not in run:
        return None
    inside = sum(parts.values())
    if not isinstance(run["warmup"], list):
        inside += run["warmup"]["seconds"]
    return run["setup_s"] - inside

"""Seconds the process spent inside XLA's compile requests up to the end of
its set-up (``compiles.seconds`` of the start-up snapshot <- the program's
compile watch, ``xflow_tpu/obs/startup.py``: JAX's
``backend_compile_duration`` events, each a program compiled OR loaded from
the persistent cache).  Tens of seconds where ``setup_programs_compiled``
reads 1 or more; a few where every program was a cache hit (the loads)."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "s", "setup_s", "program_counter"


def read(run: dict):
    snap = startup_spans.snapshot(run)
    return snap["compiles"]["seconds"] if snap else None

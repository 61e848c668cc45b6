"""Programs XLA truly compiled during this run's set-up: compile requests
less persistent-cache hits up to the start-up snapshot (``compiles.compiled``
<- the program's compile watch, ``xflow_tpu/obs/startup.py``).  0 in a warm
run; 1 or more where a program was new to the cache (the first run of an
edited source, a seed whose plane lengths are new), which is what tells a
``setup_s`` that compiled from one that loaded."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "count", "setup_s", "program_counter"


def read(run: dict):
    snap = startup_spans.snapshot(run)
    return snap["compiles"]["compiled"] if snap else None

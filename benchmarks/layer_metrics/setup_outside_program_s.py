"""What of this run's ``setup_s`` lies outside the program: ``setup_s`` less
the program's top-level start-up spans (a train cell: ``trainer_init`` + the
warm-up epochs; the serve cell: ``trainer_init`` + ``export_artifact`` +
``fleet_load``) less, in the serve cell, the warm-up traffic's seconds.
Left over: the interpreter, the imports, the backend's start, the corpus or
its cache entry, the harness's own jitted state: what no change to
``xflow_tpu/`` can move.  By definition this and those parts add up to
``setup_s`` (``harness/startup_spans.py``)."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "s", "setup_s", "program_span"


def read(run: dict):
    return startup_spans.outside_program_s(run)

"""The window's longest collector pause (``gc_pause_max`` of the
``serve_stats`` row ← ``obs.GcPauses``, the ``gc.callbacks`` hook a
``ReplicaFleet`` installs; ``gc_pauses`` and ``gc_pause_total`` beside it in
``.last.json``): 0.0 where no collection ran.  A window whose goodput fell and
whose pause is short did not stall in the collector."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "ms", "serve_goodput_rows_per_s", "program_counter"


def read(run: dict):
    return serve_spans.stats_ms(run, "gc_pause_max")

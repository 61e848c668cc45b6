"""Median time of the worker's own bookkeeping after a batch's scores are
back: cache inserts, ``set_result`` of every member (the callers'
done-callbacks run here, on the worker's thread) and the registry's observes,
the program's span ``xf.serve_resolve`` (``resolve_p50`` of the ``serve_stats``
row, one observation a batch ← ``MicroBatcher._score_sealed``)."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    return serve_spans.stats_ms(run, "resolve_p50")

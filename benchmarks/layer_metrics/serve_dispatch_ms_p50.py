"""Median host time to enqueue one batch's program: the compiled executable's
call until it RETURNS, the program's span ``xf.serve_dispatch``
(``dispatch_p50`` of the ``serve_stats`` row, one observation a batch ←
``engine._put_dispatch_fetch``)."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    return serve_spans.stats_ms(run, "dispatch_p50")

"""Median time of one batch's transfer in: ``step.put_batch`` inside the
engine's bucketed call, the program's span ``xf.serve_h2d`` (``h2d_p50`` of the
``serve_stats`` row, one observation a batch ← ``engine._put_dispatch_fetch``).
With ``serve_dispatch_ms_p50`` and ``serve_fetch_ms_p50`` it splits
``serve_device_ms_p50``, which times the same call from outside."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    return serve_spans.stats_ms(run, "h2d_p50")

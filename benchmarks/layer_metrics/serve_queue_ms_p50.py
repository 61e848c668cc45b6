"""Median time a row waited in the micro-batcher's queue, enqueue to
dequeue (``queue_p50`` of the ``serve_stats`` row)."""

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    window = run.get("window")
    return 1e3 * window["serve_stats"]["queue_p50"] if window else None

"""Median host time to turn one coalesced batch of requests into a prepared
``Batch`` (``featurize_p50`` of the ``serve_stats`` row): Python per row."""

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    window = run.get("window")
    return 1e3 * window["serve_stats"]["featurize_p50"] if window else None

"""Median time of one bucketed device call, transfer in, execute and fetch
(``device_p50`` of the ``serve_stats`` row: a host clock around
``predict_prepared``, which returns fetched scores)."""

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    window = run.get("window")
    return 1e3 * window["serve_stats"]["device_p50"] if window else None

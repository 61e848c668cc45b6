"""99th percentile, over the window's answered rows, of the time from the
instant a row was DUE to its answer.  Half of it is the generator's own
lateness (``loadgen_late_ms_p99``) and the rest rows admitted at the edge of
the queue-age budget: it swings with both, which is why the tail the cell is
held to is the 90th percentile."""

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "ms", "serve_latency_p90_ms", "host_clock"


def read(run: dict):
    window = run.get("window")
    return window["latency_ms"].get("p99") if window else None

"""How far behind its due instant the benchmark's own generator submitted,
99th percentile over the window's rows.  Latency runs from the due instant, so
this is inside it: read it beside the latencies, a starved generator is
neither a fast server nor a slow one."""

LAYER, UNIT, MOVES, SOURCE = "load_generator", "ms", "serve_latency_p90_ms", "host_clock"


def read(run: dict):
    window = run.get("window")
    return window["late_ms"].get("p99") if window else None

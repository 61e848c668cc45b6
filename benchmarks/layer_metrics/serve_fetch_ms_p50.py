"""Median time of one batch's fetch: ``jax.device_get`` and ``np.asarray``,
the wait for the device and the copy out, the program's span
``xf.serve_fetch`` (``fetch_p50`` of the ``serve_stats`` row, one observation a
batch ← ``engine._put_dispatch_fetch``).  The device's own work lies inside
it: ``serve_fetch_device_busy_frac`` says how much of it that is."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    return serve_spans.stats_ms(run, "fetch_p50")

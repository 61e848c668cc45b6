"""The most of the program's ``xf.serve_fetch`` span that can have been a wait
for the chip: the device's busy seconds in the traced slice over the seconds
the span was open in it (``harness/serve_spans.py``; ``busy_s`` and
``open_s_by_span`` in ``.last.json``).  Near 1, the fetch waits for the chip;
near 0, for the runtime and the copy out.

Every program the slice runs was enqueued by an ``xf.serve_dispatch`` and had
finished when the ``xf.serve_fetch`` after it returned, so all of the device's
busy time belongs to the fetches but what ran before its dispatch had returned.
The plain intersection, device busy ∩ the span (``busy_s_by_span``), says less:
the device planes' clock leads the host planes' by more than a fetch lasts
(PERF.md, PR 36), so the busy time reads under another span altogether."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_engine", "frac", "serve_latency_p90_ms", "device_trace"
SPAN = "xf.serve_fetch"


def read(run: dict):
    times = serve_spans.on_device(run)
    if not times or not times["open_s_by_span"].get(SPAN):
        return None
    return times["busy_s"] / times["open_s_by_span"][SPAN]

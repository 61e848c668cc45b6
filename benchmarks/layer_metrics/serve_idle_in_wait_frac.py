"""Share of the traced slice's device idle time in which a micro-batcher
worker was inside the program's ``xf.serve_wait`` span, blocked on an empty
queue: the idle chip that is the worker having nothing to do, as against the
worker working on the host (``harness/serve_spans.py``; ``idle_s_by_span`` in
``.last.json`` has every span, ``longest_gaps`` the spans under each of the
five longest gaps)."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "frac", "serve_goodput_rows_per_s", "device_trace"
SPAN = "xf.serve_wait"


def read(run: dict):
    times = serve_spans.on_device(run)
    if not times or SPAN not in times["idle_s_by_span"] or not times["idle_s"]:
        return None
    return times["idle_s_by_span"][SPAN] / times["idle_s"]

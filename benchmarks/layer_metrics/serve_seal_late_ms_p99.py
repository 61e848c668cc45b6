"""How long after its coalescing deadline a batch was sealed, 99th percentile
over the window's batches (``seal_late_p99`` of the ``serve_stats`` row ←
``MicroBatcher._run_batch``; 0 for a batch that filled before its deadline):
the time a worker whose wait had run out was still not running the batch,
woken late or still draining what had queued.  ``seal_late_max`` beside it in
``.last.json`` is the window's worst: a stall of the worker reads here."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "ms", "serve_latency_p90_ms", "program_span"


def read(run: dict):
    return serve_spans.stats_ms(run, "seal_late_p99")

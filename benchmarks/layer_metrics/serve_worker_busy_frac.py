"""Share of the window a micro-batcher worker spent inside batches:
``worker_busy_s`` of the ``serve_stats`` row (the sum of the program's
``serve.batch_seconds``, the ``xf.serve_batch`` durations ←
``MicroBatcher._run_batch``) over the window's seconds and the row's
``workers``, one a replica.  The rest of a worker's time is the coalescing
hold and the wait for arrivals; a queue in front of a worker this busy is
``serve_queue_ms_p50``."""

from benchmarks.harness import serve_spans

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "frac", "serve_latency_p90_ms", "program_counter"


def read(run: dict):
    busy = serve_spans.stats_field(run, "worker_busy_s")
    if busy is None:
        return None
    window = run["window"]
    return busy / window["seconds"] / window["serve_stats"].get("workers", 1)

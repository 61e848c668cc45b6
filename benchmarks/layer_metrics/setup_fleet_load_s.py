"""Seconds of ``ReplicaFleet.load`` in the serve cell's set-up (span
``fleet_load`` of ``serve_stats.startup`` <- ``xflow_tpu/obs/startup.py``):
inside it ``engine_load`` = ``artifact_read`` + ``weights_put`` (the 1 GiB
of weights read through onto the device) + ``bucket_warm`` (a program
compiled or loaded for each bucket), then the replicas' clones; the inner
spans are in ``.last.json``."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "s", "setup_s", "program_span"


def read(run: dict):
    snap = startup_spans.snapshot(run)
    return startup_spans.span_s(snap, "fleet_load") if snap else None

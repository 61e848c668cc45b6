"""Share of the HBM roofline the step's row gathers reach: the bytes of
table rows a step's gathers read (``gather_row_bytes_per_step`` of the
``wire`` row; counter ``wire.gather_row_bytes``, ``TrainStep._book_wire``,
from shapes: every padded slot that indexes a ``[T, D]`` table, an opted-out
table's hot slots among them, times the row's bytes; the MXU head's own
traffic left out) over the chip's 819 GB/s, over the device time under
``xf.gather`` in the traced epoch (``harness/scope_times.py``).

The scope holds more than those rows' traffic (the head's one-hot scans, the
layout changes around a gather) and a padded slot moves its row like a live
one, so the share says how near the scope's time is to what its rows' bytes
alone would take: a few percent where a gather pays per INDEX (rows of 4-44
B: PERF.md section 6, PR 30), more where a row is wide enough to reach the
memory system; FFM's rows are 640 B.
"""

from benchmarks.harness import costs, scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def share(run: dict, field: str, scope: str):
    """Percent of the HBM roofline: the ``wire`` row's ``field`` bytes a step
    over the device time of ``scope``.  ``None`` where the program has no
    such counter (one older than PR 34) or the run no device trace."""
    rows = [
        e["_wire"][field] for e in run.get("epochs", [])
        if field in e.get("_wire", {})
    ]
    ms = scope_times.scope_ms_per_step(run, scope)
    peaks = run.get("peaks")
    if not rows or not ms or not peaks:
        return None
    return costs.roofline_share(sum(rows) / len(rows), ms / 1e3, peaks)


def read(run: dict):
    return share(run, "gather_row_bytes_per_step", "xf.gather")

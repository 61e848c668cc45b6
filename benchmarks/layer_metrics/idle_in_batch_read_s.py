"""Seconds of the traced ``train_epoch()`` call in which the device ran
nothing while at least one stream thread was inside the program's
``xf.batch_read`` span (``io/loader.py::_iter_packed``: the pull of one
record out of ``io/packed.py``, its mmap read and, for a padded consumer of
a v2 shard, ``CompactBatch.expand()``; not the consumer's time between
pulls).  Read beside ``epoch_boundary_idle_s`` and ``idle_in_shard_open_s``:
the part of the pipeline's fill that is reading records
(``harness/scope_times.py``; ``idle_s_by_span`` in ``.last.json`` has every
span)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "input", "s", "train_examples_per_s", "device_trace"


def read(run: dict):
    times = scope_times.on_device(run)
    if not times or "xf.batch_read" not in times["idle_s_by_span"]:
        return None
    return times["idle_s_by_span"]["xf.batch_read"]

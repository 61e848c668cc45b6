"""Seconds of the traced ``train_epoch()`` call in which the device ran
nothing while at least one stream thread was inside the program's
``xf.shard_open`` span (``io/loader.py::_iter_packed``: a packed shard's
header read and ``check_compat``; inside it ``xf.remap_digest`` is the open
obtaining the hot remap's sha256 from ``io/packed.py::RemapDigest``: the
trainer's one hash, the wait of a concurrent open for it, or a lookup).
Read beside ``epoch_boundary_idle_s``: close to it, the shard opens are the
boundary; well under it, something else is (``harness/scope_times.py``;
``idle_s_by_span`` in ``.last.json`` has every span)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "input", "s", "train_examples_per_s", "device_trace"


def read(run: dict):
    times = scope_times.on_device(run)
    if not times or "xf.shard_open" not in times["idle_s_by_span"]:
        return None
    return times["idle_s_by_span"]["xf.shard_open"]

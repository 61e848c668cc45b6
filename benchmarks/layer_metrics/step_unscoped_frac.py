"""Share of the traced epoch's device time that no ``xf.`` scope of the
program covers: operations whose instruction has no scope in the trainer's
``_scopes`` rows, that two programs place in different scopes, or that no
train program holds (``harness/scope_times.py``; ``top_unscoped`` in
``.last.json`` names them).  It guards the other scope metrics: if it grows
after an edit to the step, new work went in without a name."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "frac", "train_examples_per_s", "device_trace"


def read(run: dict):
    times = scope_times.on_device(run)
    if not times or not times["scope_rows"] or not times["busy_s"]:
        return None
    return times["unscoped_s"] / times["busy_s"]

"""The train program alone: chained calls on batches already on the device,
closed by a fetch, outside the window (``bench.py::run``'s method, taken by
``harness/train_cell.py::_step_alone``).  Host clock around work that ends
in a fetch, so it is device time per step plus what dispatch does not hide."""

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "host_clock"


def read(run: dict):
    probe = run.get("step_alone")
    return probe["ms_per_step"] if probe else None

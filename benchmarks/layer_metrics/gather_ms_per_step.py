"""Device milliseconds a step under the program's scope ``xf.gather``
(``parallel/step.py::_gather_model_rows``: the cold rows' gather from the
table; ``ops/hot.py::hot_gather``: the hot head's one-hot matmuls) in the
traced epoch (``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.gather")

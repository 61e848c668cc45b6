"""Device milliseconds a step under the program's scope ``xf.scatter``
(``parallel/step.py``: the zeroed gradient buffer, ``_scatter_grads``,
``_cold_accumulate``; ``ops/hot.py::hot_scatter``) in the traced epoch
(``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.scatter")

"""Device milliseconds a step under the program's scope ``xf.scatter``
(``parallel/step.py``: ``_zero_gbufs``, ``_scatter_grads`` and on a mesh
``_push_grads``, both through ``_cold_accumulate``, the one form of the cold
scatter-add; ``ops/hot.py::hot_scatter``) in the traced epoch
(``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.scatter")

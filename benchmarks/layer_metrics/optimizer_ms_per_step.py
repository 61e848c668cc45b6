"""Device milliseconds a step under the program's scope ``xf.optimizer``
(``parallel/step.py``: the FTRL recurrence over the whole [T, D] state, or
the touched-rows update of the sparse modes) in the traced epoch
(``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.optimizer")

"""Device milliseconds a step under the program's scope
``xf.forward_backward`` (``parallel/step.py::grads_from_rows``, ``_logit``:
the model's ``logit`` and ``grad_logit`` on the gathered rows) in the traced
epoch (``harness/scope_times.py``).  Next to nothing for LR and FM; for a
family that reads field ids it holds the one-hot field contraction
(``models/blocks.py::field_contract``) and, for MVM, the backward's pick of
each entry's own field factor."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.forward_backward")

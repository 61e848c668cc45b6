"""Device milliseconds a step under the program's scope ``xf.dense``
(``models/blocks.py::DENSE_SCOPE``: what a family with replicated dense
parameters runs over them, forward and backward; for DCN the cross stack,
the hidden layers and the output product, with the concatenation and the
ReLUs between) in the traced epoch (``harness/scope_times.py``).  The scope is
opened inside ``xf.forward_backward``, whose own time is then what is left of
the model: the field contraction and the linear term.  A program that opens
no such scope (one older than PR 39, or a family without dense parameters)
has nothing to read."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"
SCOPE = "xf.dense"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, SCOPE) or None

"""Device milliseconds a step under the program's scope ``xf.cin``
(``models/blocks.py::CIN_SCOPE``: xDeepFM's Compressed Interaction Network,
forward, its slices' pairs multiplied again and backward: the tower laid out
by slice, the loop over the slices of the batch, every layer's pair product
and contraction, the pooling) in the traced epoch
(``harness/scope_times.py``).  The scope is opened inside
``xf.forward_backward`` beside ``xf.dense``, which then holds the DNN and the
output product alone.  A program that opens no such scope (one older than PR
43, or a family without a CIN) has nothing to read."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"
SCOPE = "xf.cin"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, SCOPE) or None

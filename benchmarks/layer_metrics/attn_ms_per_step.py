"""Device milliseconds a step under the program's scope ``xf.attn``
(``models/blocks.py::ATTN_SCOPE``: AutoInt's interacting layers, forward, each
slice's forward computed again for its backward, and backward: the loop over
the slices of the batch, every layer's projections, per-example scores,
softmax over the present fields and weighted sum) in the traced epoch
(``harness/scope_times.py``).  The scope is opened inside
``xf.forward_backward`` beside ``xf.dense``, which then holds the output
product alone.  A program that opens no such scope (one older than PR 47, or a
family without interacting layers) has nothing to read."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"
SCOPE = "xf.attn"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, SCOPE) or None

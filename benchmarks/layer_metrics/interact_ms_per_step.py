"""Device milliseconds a step under the program's scope ``xf.interact``
(``models/blocks.py::INTERACT_SCOPE``: DLRM's dot interaction, forward and
backward: the product of every pair of an example's 27 vectors of 128, the
pick of the 351 pairs i > j, and the relayouts around them) in the traced
epoch (``harness/scope_times.py``).  The scope is opened inside
``xf.forward_backward`` beside ``xf.dense``, which holds both ReLU stacks and
the output product; the forming of the 27 vectors (the field sums of the
embeddings, the concatenation with the bottom stack's output) stays
``xf.forward_backward``'s own.  A program that opens no such scope (one older
than PR 58, or a family without a dot interaction) has nothing to read."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"
SCOPE = "xf.interact"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, SCOPE) or None

"""Device milliseconds a step under the program's scope ``xf.bilinear``
(``models/blocks.py::BILINEAR_SCOPE``: FiBiNET's interaction, forward, forward
again and backward: the SENET squeeze, excitation and re-weighting, every
field's product with the matrices of its pairs on the plain and on the gated
tower, the multiply by the pairs' second fields and the forming of the pair
tensor c) in the traced epoch (``harness/scope_times.py``).  The scope is
opened inside ``xf.forward_backward`` beside ``xf.dense``, which holds the
three hidden layers over c and the output product.  A program that opens no
such scope (one older than PR 52, or a family without a bilinear interaction)
has nothing to read."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"
SCOPE = "xf.bilinear"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, SCOPE) or None

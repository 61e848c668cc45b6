"""Share of the MXU's peak the bilinear interaction's LEARNED products reach:
the operations FiBiNET's block has to do in a step, over the chip's 197
TFLOP/s, over the device time under ``xf.bilinear`` in the traced epoch
(``layer_metrics/bilinear_ms_per_step.py``).

The operations are counted here, from the configuration's fields and nothing of
the program's.  An example, with m = ``max_fields`` fields of D = ``emb_dim``,
P = m (m - 1) / 2 pairs and reduction r = ``senet_reduction``: a ``[D] x [D,
D]`` product a pair on the plain and on the gated tower, ``2 P D D``
multiply-adds, and the excitation's two products ``[m] x [m, m // r]`` and
``[m // r] x [m // r, m]``, ``2 m (m // r)``; 2 operations each forward and
twice that backward.  The three hidden layers and the output product run under
``xf.dense`` and are not counted; nor is the forward computed again for the
backward, nor what a layout multiplies beside (a product's K of 10 padded to
a tile).  At the paper's Criteo sizes (m = 40, D = 10, r = 3) 157 040
multiply-adds an example forward, 1.54e10 operations a step at B = 16384.

The pair tensor's bytes (c ``[B, 2 P D]``, 1.02 GB written and its cotangent
read) are NOT counted: a later fused form that hands the first hidden layer
its operand a slice at a time need not write it, so only the operations set
the roofline and the share can never pass 100; the peak is the published
bfloat16 one and the program's products are float32 (Precision.HIGHEST: six
bfloat16 passes), so it cannot read over about a sixth.  Expect well under 1 %:
a number that says the block is layout and elementwise work, not MXU work (a
field's product has K = 10 where the MXU wants 128, and the scope's time is the
writing and reading of c and of its cotangent).  A run without ``xf.bilinear``
time, or a configuration without ``senet_reduction``, reports nothing."""

from benchmarks.harness import costs
from benchmarks.layer_metrics import bilinear_ms_per_step

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def bilinear_macs_per_example(fields: dict) -> int:
    """2 P D D + 2 m (m // r), from a configuration's fields."""
    m, d = fields["max_fields"], fields["emb_dim"]
    pairs, squeezed = m * (m - 1) // 2, max(m // fields["senet_reduction"], 1)
    return 2 * pairs * d * d + 2 * m * squeezed


def bilinear_flops(fields: dict) -> float:
    """6 B (2 P D D + 2 m (m // r))."""
    return 6.0 * fields["batch_size"] * bilinear_macs_per_example(fields)


def read(run: dict):
    ms = bilinear_ms_per_step.read(run)
    fields, peaks = run.get("fields") or {}, run.get("peaks")
    if not ms or not peaks or "senet_reduction" not in fields:
        return None
    return costs.roofline_share(0.0, ms / 1e3, peaks, flops=bilinear_flops(fields))

"""Share of the MXU's peak the dot interaction's products reach: the
operations DLRM's pairwise dots have to do in a step, over the chip's 197
TFLOP/s, over the device time under ``xf.interact`` in the traced epoch
(``layer_metrics/interact_ms_per_step.py``).

The operations are counted here, from the configuration's fields and nothing
of the program's.  An example, with n = ``max_fields - numeric_fields``
vectors of d = ``emb_dim`` (the bottom stack's output and one a categorical
field): the P = n (n - 1) / 2 pairs i > j that HAVE to be multiplied, d
multiply-adds each; 2 operations each forward and twice that backward (the
gradient of either vector of a pair): ``6 B P d``.  The other half of ``T
T^T`` and its diagonal, which a batched product computes beside them, are
not counted; nor are the two stacks and the output product, which run under
``xf.dense``.  At the Criteo-Terabyte sizes (n = 27, d = 128) 44 928
multiply-adds an example forward, 8.83e9 operations a step at B = 32768.

The bytes the block has to move (the 27 vectors in and their cotangent out,
``2 * 4 B n d``: 0.91 GB a step, 1.1 ms at 819 GB/s) are NOT counted, as
``bilinear_mxu_roofline`` leaves its pair tensor's out: a form that takes the
vectors as the stack and the field sums leave them need not write them, so
only the operations set the roofline and the share can never pass 100; the
peak is the published bfloat16 one and the program's products are float32
(Precision.HIGHEST: six bfloat16 passes), so it cannot read over about a
sixth.  Expect well under 1 %: a per-example ``[27, 128] x [128, 27]`` product
fills a twentieth of the MXU's 128 x 128, and the scope's time is layout and
small batched products.  A run without ``xf.interact`` time, or a
configuration without ``numeric_fields``, reports nothing."""

from benchmarks.harness import costs
from benchmarks.layer_metrics import interact_ms_per_step

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def interact_macs_per_example(fields: dict) -> int:
    """P d for the P pairs of n = max_fields - numeric_fields vectors of d."""
    n = fields["max_fields"] - fields["numeric_fields"]
    return n * (n - 1) // 2 * fields["emb_dim"]


def interact_flops(fields: dict) -> float:
    """6 B P d."""
    return 6.0 * fields["batch_size"] * interact_macs_per_example(fields)


def read(run: dict):
    ms = interact_ms_per_step.read(run)
    fields, peaks = run.get("fields") or {}, run.get("peaks")
    if not ms or not peaks or "numeric_fields" not in fields:
        return None
    return costs.roofline_share(0.0, ms / 1e3, peaks, flops=interact_flops(fields))

"""Share of the MXU's peak the interacting layers' products reach: the
operations the layers of a step have to do, over the chip's 197 TFLOP/s, over
the device time under ``xf.attn`` in the traced epoch
(``layer_metrics/attn_ms_per_step.py``).

The operations are counted here, from the configuration's fields and nothing
of the program's.  Layer l, an example, with M = ``max_fields`` fields, H =
``attn_heads`` heads of d' = ``attn_dim`` and inputs of ``d_l`` (``emb_dim``
for the first layer, ``H d'`` after): four projections ``[M, d_l] x [d_l, H
d']`` (query, key, value, residual), the scores ``[M, d'] x [d', M]`` and the
weighted sum ``[M, M] x [M, d']`` a head: ``4 M d_l H d' + 2 H M M d'``
multiply-adds, 2 operations each forward and twice that backward, over
``cross_layers`` layers.  The output product (``M H d'`` multiply-adds an
example) runs under ``xf.dense`` and is not counted; nor is a slice's forward
computed again for its backward.  At the paper's Criteo sizes (M = 40, d = 16,
3 layers of 2 heads of 32) 2 088 960 multiply-adds an example forward, 2.05e11
operations a step at B = 16384.

The peak is the published bfloat16 one and the program's products are float32
(Precision.HIGHEST: six bfloat16 passes), so the share cannot read over about
a sixth, and nothing can read over 100.  The scope holds more than the
products (the softmax, the relayouts between the products, the loop, the
forward done again), so the share says how much of the scope's time the MXU's
work alone would take.  The bytes the block has to move (the tower in, the
fields' vectors out, both ways: ``2 * 4 B M (d + H d')``, 0.4 GB a step, 0.5
ms) are under the operations' 1.04 ms, so operations set the roofline.  A run
without ``xf.attn`` time, or a configuration without ``attn_heads``, reports
nothing."""

from benchmarks.harness import costs
from benchmarks.layer_metrics import attn_ms_per_step

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def attn_macs_per_example(fields: dict) -> int:
    """sum_l [4 M d_l H d' + 2 H M M d'], from a configuration's fields."""
    m, heads, head = fields["max_fields"], fields["attn_heads"], fields["attn_dim"]
    inputs = [fields["emb_dim"]] + [heads * head] * (fields["cross_layers"] - 1)
    return sum(4 * m * d * heads * head + 2 * heads * m * m * head for d in inputs)


def attn_flops(fields: dict) -> float:
    """6 B sum_l [4 M d_l H d' + 2 H M M d']."""
    return 6.0 * fields["batch_size"] * attn_macs_per_example(fields)


def read(run: dict):
    ms = attn_ms_per_step.read(run)
    fields, peaks = run.get("fields") or {}, run.get("peaks")
    if not ms or not peaks or "attn_heads" not in fields:
        return None
    return costs.roofline_share(0.0, ms / 1e3, peaks, flops=attn_flops(fields))

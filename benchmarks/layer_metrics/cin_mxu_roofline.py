"""Share of the MXU's peak the CIN's contractions reach: the operations the
layers of a step have to do, over the chip's 197 TFLOP/s, over the device
time under ``xf.cin`` in the traced epoch
(``layer_metrics/cin_ms_per_step.py``).

The operations are counted here, from the configuration's fields and nothing
of the program's: layer k contracts, for each of ``B * D`` (example,
embedding column) rows, ``H_{k-1} * m`` pair products into ``H_k`` maps: ``2 B
D m H_{k-1} H_k`` forward and twice that backward (the gradient of the
weights and of the pairs), with ``H_0 = m`` (``max_fields``), ``H_k =
cin_maps`` and ``cross_layers`` layers.  The pairs multiplied again in a
rematerialised backward do not count, and are no product.  At the paper's
Criteo sizes and B = 16384: 3.46e12 a step.

The peak is the published bfloat16 one and the program's contractions are
float32 (Precision.HIGHEST: six bfloat16 passes), so the share cannot read
over about a sixth (a third in three passes), and nothing can read over 100.
The scope holds more than the contractions (the pair products, the pooling,
the loop), so the share says how much of the scope's time the MXU's work
alone would take: the mechanism's share of its roofline, which is set by
operations (the bytes it has to move, the tower and the pooled maps, are a
thousandth of what 3.46e12 operations take).  A run without ``xf.cin`` time,
or a configuration without ``cin_maps``, reports nothing."""

from benchmarks.harness import costs
from benchmarks.layer_metrics import cin_ms_per_step

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def cin_flops(fields: dict) -> float:
    """6 B D m sum_k H_{k-1} H_k, from a configuration's fields."""
    m, maps = fields["max_fields"], fields["cin_maps"]
    widths = [m] + [maps] * fields["cross_layers"]
    pairs = sum(a * b for a, b in zip(widths[:-1], widths[1:]))
    return 6.0 * fields["batch_size"] * fields["emb_dim"] * m * pairs


def read(run: dict):
    ms = cin_ms_per_step.read(run)
    fields, peaks = run.get("fields") or {}, run.get("peaks")
    if not ms or not peaks or "cin_maps" not in fields:
        return None
    return costs.roofline_share(0.0, ms / 1e3, peaks, flops=cin_flops(fields))

"""Share of the MXU's peak the dense half's matmuls reach: the operations a
step's products with dense parameters have to do (``run["costs"]["flops"]``,
the benchmark's own count: ``harness/costs.py`` over the products that
``reference/<family>.py::matmuls`` declares, 2 B k n forward and twice that
backward) over the chip's 197 TFLOP/s, over the device time under
``xf.dense`` in the traced epoch (``layer_metrics/dense_ms_per_step.py``).

The peak is the published bfloat16 one and the program's products are float32
(``models/blocks.py::dense_dot``, Precision.HIGHEST: six bfloat16 passes), so
the share cannot read over about a sixth, and nothing can read over 100.  The
scope holds more than the products (DCN's cross layers are elementwise passes
over ``[B, P]`` planes), so the share says how much of the scope's time the
MXU's work alone would take.  No kernel is behind it: it stands where a
kernel's roofline share would.  A run without ``xf.dense`` time, or a family
whose reference declares no matmul, reports nothing.
"""

from benchmarks.harness import costs
from benchmarks.layer_metrics import dense_ms_per_step

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def read(run: dict):
    ms = dense_ms_per_step.read(run)
    flops = run.get("costs", {}).get("flops")
    peaks = run.get("peaks")
    if not ms or not flops or not peaks:
        return None
    return costs.roofline_share(0.0, ms / 1e3, peaks, flops=flops)

"""Share of the roofline the train program reaches: the bytes one step has to
move (``harness/costs.py``: gathered and scattered rows, the dense FTRL pass
over state and gradient buffer) over 819 GB/s a chip, or, where that takes
longer, the matmul operations a family with dense parameters has to do over
197 TFLOP/s, over the device's busy time per step in the traced epoch.  For a
family whose parameters are all table rows the operations are 0 and the
bound is HBM bytes; the descriptor-issue rate that ``docs/PERF.md`` found
to be the real floor of the gathers and scatters has no published peak."""

from benchmarks.harness import costs

LAYER, UNIT, MOVES, SOURCE = "step", "%", "train_examples_per_s", "device_trace"


def read(run: dict):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not peaks or "busy_s_per_step" not in trace:
        return None
    if trace.get("source") != "device_planes":
        return None  # a CPU backend's host threads are not a device
    c = run["costs"]
    return costs.roofline_share(
        c["hbm_bytes"], trace["busy_s_per_step"], peaks, c["devices"],
        c.get("flops", 0.0),
    )

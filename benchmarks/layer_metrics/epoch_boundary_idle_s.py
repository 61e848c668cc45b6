"""Seconds of the traced ``train_epoch()`` call in which the device ran
nothing while the host was outside the span from the first call of the train
program to the last one's return: the shard opens before the first batch
(at 2^28 every open re-hashes the 1 GiB remap) and the tail after the last
step.  An epoch of N steps takes about this plus N x ``step_device_ms``; a
production epoch of thousands of steps amortises it, the cell's 16 do not,
so a gain in ``train_examples_per_s`` is read beside this and the step."""

LAYER, UNIT, MOVES, SOURCE = "input", "s", "train_examples_per_s", "device_trace"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("source") != "device_planes":
        return None
    return trace["idle_s_by_label"].get("epoch_boundary", 0.0)

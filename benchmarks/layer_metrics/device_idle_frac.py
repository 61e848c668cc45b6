"""Share of the traced window in which no operation ran on the device (the
worst device of several): 1 - union of its operations' intervals over the
window.  Train cells trace one whole ``train_epoch()`` call, serve cells a
slice of the window under load."""

LAYER, UNIT, MOVES, SOURCE = "device", "frac", "train_examples_per_s", "device_trace"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("source") != "device_planes":
        return None
    return trace["device_idle_frac"]

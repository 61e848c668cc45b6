"""Device milliseconds a step inside collective operations (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute) on the first
device of the traced epoch: the union of their intervals
(``harness/trace_reduce.py::reduce``: ``collective_s``) over the epoch's
steps.  On a mesh of more than one chip these are the program's pull and
push between the batch's shards and the table's row blocks (scope
``xf.exchange``) and whatever the partitioner inserted beside them."""

LAYER, UNIT, MOVES, SOURCE = "collectives", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("source") != "device_planes" or not trace.get("steps"):
        return None
    return trace["collective_s"] / trace["steps"] * 1e3

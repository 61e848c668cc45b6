"""Share of the traced epoch's collective time during which nothing else ran
on that device (``harness/trace_reduce.py::reduce``:
``collective_exposed_s`` over ``collective_s``, first device): 1 means no
collective overlaps any compute, 0 that all of them hide behind it.  Nothing
to read where the trace holds no collective (one chip)."""

LAYER, UNIT, MOVES, SOURCE = "collectives", "frac", "train_examples_per_s", "device_trace"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("source") != "device_planes":
        return None
    if not trace.get("collective_s"):
        return None
    return trace["collective_exposed_s"] / trace["collective_s"]

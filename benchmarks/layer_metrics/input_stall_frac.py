"""Share of an epoch's wall time the training loop spent waiting for its next
batch (``Trainer._epoch_stats()["input_stall_frac"]``: parse, pack and h2d
hide behind this wait; what they do not hide shows here).  Mean over the
window's epochs."""

LAYER, UNIT, MOVES, SOURCE = "input", "frac", "train_examples_per_s", "program_span"


def read(run: dict):
    epochs = [e for e in run.get("epochs", []) if e.get("phases")]
    if not epochs:
        return None
    return sum(e["input_stall_frac"] for e in epochs) / len(epochs)

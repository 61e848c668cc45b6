"""Bytes that crossed host->device per real example, an exact count from the
``wire`` row of ``Trainer._epoch_stats()`` (``TrainStep._book_wire``), under
whatever wire format ``auto`` chose."""

LAYER, UNIT, MOVES, SOURCE = "wire", "B/example", "train_examples_per_s", "program_counter"


def read(run: dict):
    rows = [e["_wire"] for e in run.get("epochs", []) if "_wire" in e]
    if not rows:
        return None
    return sum(r["wire_bytes_per_example"] for r in rows) / len(rows)

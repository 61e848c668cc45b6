"""Bytes of field ids that crossed host->device per real example: the part
of ``wire_bytes_per_example`` that a family which reads field ids pays and LR
and FM do not, from the ``wire`` row of ``Trainer._epoch_stats()`` (counter
``wire.slots_bytes``, ``TrainStep._book_wire``: the dictionary wire's
``cw_cs`` / ``cw_hs`` planes, or ``slots_u8`` / ``hot_slots_u8``).  A program
older than the counter has no such field: nothing to read."""

LAYER, UNIT, MOVES, SOURCE = "wire", "B/example", "train_examples_per_s", "program_counter"


def read(run: dict):
    rows = [
        e["_wire"]["slots_bytes_per_example"] for e in run.get("epochs", [])
        if "slots_bytes_per_example" in e.get("_wire", {})
    ]
    if not rows:
        return None
    return sum(rows) / len(rows)

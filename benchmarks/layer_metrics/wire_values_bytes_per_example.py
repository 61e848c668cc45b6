"""Of ``wire_bytes_per_example``, the bytes that are the plane of the numeric
fields' VALUES (``values_bytes_per_example`` of the ``wire`` row; counter
``wire.values_bytes``, ``TrainStep._book_wire``: ``nvals`` on the compact wire,
``cw_nv`` on the dictionary wire, one float32 a numeric field and example: 52
at 13 fields).  Every other entry's value is 1 and never ships.  A program
older than the plane, and every configuration without ``numeric_fields``,
writes no such field: nothing to read."""

LAYER, UNIT, MOVES, SOURCE = "wire", "B/example", "train_examples_per_s", "program_counter"


def read(run: dict):
    rows = [
        e["_wire"]["values_bytes_per_example"] for e in run.get("epochs", [])
        if "values_bytes_per_example" in e.get("_wire", {})
    ]
    if not rows:
        return None
    return sum(rows) / len(rows)

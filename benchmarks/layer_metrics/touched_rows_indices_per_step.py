"""Indices a step's dense update hands the touched-rows application in place
of a [T, D] gradient buffer and a pass over the table, summed over the tables
the rule selects, from the ``wire`` row of ``Trainer._epoch_stats()`` (counter
``wire.touched_rows_indices``, ``TrainStep._book_wire``; the rule is
``xflow_tpu/parallel/step.py::touched_rows_selects``: a table of 2 to 64
columns, large enough for its index count, under a whole dictionary-wire
batch with an empty tail).  55 296 in the three B = 16 384 cells, whose
``emb`` it selects; 0 where no table is selected: anything else there says the
mechanism did not engage as priced, before any time is read.  A program older
than the counter has no such field: nothing to read."""

LAYER, UNIT, MOVES, SOURCE = "step", "count", "train_examples_per_s", "program_counter"


def read(run: dict):
    rows = [
        e["_wire"]["touched_rows_indices_per_step"] for e in run.get("epochs", [])
        if "touched_rows_indices_per_step" in e.get("_wire", {})
    ]
    if not rows:
        return None
    return sum(rows) / len(rows)

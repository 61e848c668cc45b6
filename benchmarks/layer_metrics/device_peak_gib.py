"""The result line's ``memory_peak_bytes`` in GiB
(``harness/device.py::memory_peak_bytes``): the larger of the allocator's
peak in use over the process's life and what the chip held, program
temporaries included, when the window ended with the trainer alive.  It
limits the table and the batch a chip can take."""

LAYER, UNIT, MOVES, SOURCE = "device", "GiB", "train_examples_per_s", "program_counter"


def read(run: dict):
    peak = run.get("memory_peak_bytes")
    return peak / float(1 << 30) if peak else None

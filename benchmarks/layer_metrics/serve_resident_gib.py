"""What the scoring tier itself holds on the chip, in GiB: the allocator's
bytes in use and reserved when the window ended.  The result line's
``memory_peak_bytes`` of a serve cell is its set-up's (the training state
the artifact is exported from), not this."""

LAYER, UNIT, MOVES, SOURCE = "device", "GiB", "serve_goodput_rows_per_s", "program_counter"


def read(run: dict):
    held = run.get("held_bytes")
    return held / float(1 << 30) if held else None

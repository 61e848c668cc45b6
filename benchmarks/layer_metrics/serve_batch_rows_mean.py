"""Mean rows the micro-batcher coalesced into one device call
(``batch_fill_mean`` of the ``serve_stats`` row)."""

LAYER, UNIT, MOVES, SOURCE = "serve_batcher", "rows", "serve_goodput_rows_per_s", "program_counter"


def read(run: dict):
    window = run.get("window")
    return window["serve_stats"]["batch_fill_mean"] if window else None

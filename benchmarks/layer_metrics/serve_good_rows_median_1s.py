"""Median over the window's whole seconds of the good rows due in each.
Beside goodput it tells a tier that is slower throughout from one stall of
the host: the stall moves goodput and not this."""

import statistics

LAYER, UNIT, MOVES, SOURCE = "serve_admission", "rows/s", "serve_goodput_rows_per_s", "host_clock"


def read(run: dict):
    per_second = (run.get("window") or {}).get("good_per_second")
    if not per_second:
        return None
    return float(statistics.median(per_second))

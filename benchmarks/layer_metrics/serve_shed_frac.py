"""Share of the window's offered rows that admission control refused at the
door (``serve_shed`` row of ``ReplicaFleet.emit_stats()``).  Above the knee
it is what keeps the queue short; it is lost goodput, not failure."""

LAYER, UNIT, MOVES, SOURCE = "serve_admission", "frac", "serve_goodput_rows_per_s", "program_counter"


def read(run: dict):
    window = run.get("window")
    return window["serve_shed"]["shed_frac"] if window else None

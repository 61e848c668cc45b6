"""Seconds of ``Trainer.__init__`` in this run's set-up: the model and
``TrainStep`` built (``step_build``), the tables made on the device
(``state_init``: ``parallel/step.py::init_state``, 3 to 12 GiB), the hot
remap loaded or measured (``remap_init``) (span ``trainer_init`` of the first
epoch's ``_startup`` <- ``xflow_tpu/obs/startup.py``, always on; the inner
spans are in ``.last.json``).  Read beside ``setup_s``: a move of ``setup_s``
that is not here, in ``setup_first_epoch_s`` or in ``setup_compile_s`` lies
outside the program (``setup_outside_program_s``)."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "s", "setup_s", "program_span"


def read(run: dict):
    snap = startup_spans.snapshot(run)
    return startup_spans.span_s(snap, "trainer_init") if snap else None

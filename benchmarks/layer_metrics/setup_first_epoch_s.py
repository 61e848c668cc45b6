"""Seconds of the warm-up epochs in this run's set-up: the trainer's FIRST
``train_epoch()`` whole, under the program's span ``first_epoch`` (the train
program compiled or loaded, the pipeline's first fill, ``op_scopes`` in a
traced run), and any later warm-up epoch by its record's ``seconds`` (the
cells warm up one).  Where ``setup_programs_compiled`` reads 0 this is a
load from the persistent cache plus one epoch; where it reads 1 or more,
``setup_compile_s`` of it was the compiler."""

from benchmarks.harness import startup_spans

LAYER, UNIT, MOVES, SOURCE = "setup", "s", "setup_s", "program_span"


def read(run: dict):
    return startup_spans.warmup_epochs_s(run)

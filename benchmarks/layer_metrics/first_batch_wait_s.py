"""Seconds the training loop waited for an epoch's first batch: the first
``input_stall`` of ``Trainer.train_epoch()``, which the epoch record carries
as ``first_batch_wait_s`` (shard opens and the pipeline's fill; the device
has nothing to do meanwhile).  Mean over the window's epochs."""

LAYER, UNIT, MOVES, SOURCE = "input", "s", "train_examples_per_s", "program_span"


def read(run: dict):
    waits = [
        e["first_batch_wait_s"] for e in run.get("epochs", [])
        if "first_batch_wait_s" in e
    ]
    if not waits:
        return None
    return sum(waits) / len(waits)

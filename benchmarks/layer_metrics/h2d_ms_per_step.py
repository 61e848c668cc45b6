"""Host seconds inside ``TrainStep.put_batch`` per step (plane collection,
any compaction left to it, and the transfer), from the staging ring's
workers: ``overlapped["h2d"]`` of the epoch record over its steps.  It hides
behind the device as long as the ring stays ahead."""

LAYER, UNIT, MOVES, SOURCE = "wire", "ms", "train_examples_per_s", "program_span"


def read(run: dict):
    epochs = [e for e in run.get("epochs", []) if "h2d" in e.get("overlapped", {})]
    if not epochs:
        return None
    return 1e3 * sum(e["overlapped"]["h2d"] for e in epochs) / sum(
        e["steps"] for e in epochs
    )

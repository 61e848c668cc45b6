"""Device milliseconds a step under the program's scope ``xf.gather``
(``parallel/step.py::_gather_model_rows``, on a mesh ``_pull_model_rows``:
``_cold_rows`` is ``param[keys]`` or, on a dictionary-wire batch,
``dict_cold_rows``, the table's rows per dictionary and tail entry and the
occurrence resolve of the ROWS; ``ops/hot.py::hot_gather``: the hot head's
one-hot matmuls) in the traced epoch (``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "step", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.gather")

"""Device milliseconds a step under the program's scope ``xf.wire_decode``
(``parallel/step.py``: ``_expand_wire``, which on a dictionary-wire batch is
``expand_dict_wire``, the rebuild of the padded planes from the wire's flat
streams with the occurrence resolve of the KEYS, ``wide_take(cu, ci)``; and
``_model_view``) in the traced epoch: exclusive operation times joined with
the trainer's ``_scopes`` rows (``harness/scope_times.py``)."""

from benchmarks.harness import scope_times

LAYER, UNIT, MOVES, SOURCE = "wire", "ms", "train_examples_per_s", "device_trace"


def read(run: dict):
    return scope_times.scope_ms_per_step(run, "xf.wire_decode")

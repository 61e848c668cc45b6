"""What the harness hands a driver, and what a driver hands back."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Ctx:
    workload: str
    cell: dict  # the ``workloads`` entry
    config_doc: dict  # the configuration file (rehearsal block applied)
    fields: dict  # its Config fields
    traffic: dict  # the traffic mix (rehearsal block applied)
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    work: str  # a directory of this run's own, inside the checkout
    device: dict  # platform, kind, count
    peaks: dict | None  # the peaks of this device kind (None in a rehearsal)
    meter: Any  # compiles.CompileMeter
    log: Callable[[str], None]


@dataclasses.dataclass
class Outcome:
    checks: dict[str, bool]  # every one must hold for ``correct``
    attempted: int
    failed: int
    end_to_end: dict[str, float]  # the driver's own host-clock metrics
    window_start: float  # time.perf_counter() at the window's first instant
    run: dict  # what per-layer metric readers read
    counts: dict  # what a rehearsal may print: counts, never rates
    # each number ``checks`` compared, beside its limit and the sense of the
    # comparison: the line's last key and the run's last lines on standard error
    compared: dict[str, dict] = dataclasses.field(default_factory=dict)


def beside(value, limit, op: str = "<=") -> dict:
    """One entry of ``Outcome.compared``: the check holds where
    ``value op limit`` does."""
    return {"value": value, "op": op, "limit": limit}

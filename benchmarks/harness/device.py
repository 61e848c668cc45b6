"""The device a run is on: found or refused, its peaks, its memory."""

from __future__ import annotations

import json
import os
import sys


def require(chips: int, rehearsal: bool) -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them.  Without a TPU,
    or with fewer chips than the cell asks for, the process exits non-zero
    and prints no result; a rehearsal takes whatever backend there is."""
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if rehearsal:
        return device
    if device["platform"] != "tpu" or device["count"] < chips:
        print(
            f"benchmark: this cell needs {chips} TPU chip(s) and JAX reports "
            f"{device}; there is no CPU fallback (--rehearsal runs toy sizes "
            "on any backend and prints no result)",
            file=sys.stderr,
        )
        sys.exit(1)
    return device


def peaks(kind: str) -> dict:
    """The table of peaks for ``kind``; a device that is not in it is an
    error, not a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(
            f"device kind {kind!r} is not in {path}: add it with its source"
        )
    return table[kind]


def held_bytes() -> int:
    """What the fullest local device holds at this instant: the allocator's
    ``bytes_in_use`` and ``bytes_reserved`` of one reading.  The TPU's
    allocator books what a loaded program needs for its temporaries (for the
    LR train program, the 1 GiB gradient buffer) as reserved and never as in
    use: with that program loaded, in use peaked at 3.55e9 where the
    compiler's ``memory_analysis()`` says the program alone needs 4.31e9
    (PR 22).  0 where the backend reports nothing, as on the CPU."""
    import jax

    held = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        held = max(held, int(
            stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)
        ))
    return held


def memory_peak_bytes(held: int) -> int:
    """The result line's ``memory_peak_bytes``: the larger of the allocator's
    own peak over the process's life, set-up included (``peak_bytes_in_use``
    of the fullest local device), and ``held``, what a driver saw a device
    hold at one instant of its window (``held_bytes()``).  Each is a lower
    bound of the true peak; peaks of different instants are never added."""
    import jax

    return max([held] + [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices()
    ])


def memory_stats() -> list[dict]:
    """Every local device's raw ``memory_stats()``, for the run's log."""
    import jax

    return [dict(d.memory_stats() or {}) for d in jax.local_devices()]

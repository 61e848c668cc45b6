"""Bytes and slices a train step has to move, from shapes and counts: the
numerator of a roofline share.  Kept with the benchmark so that no PR that
claims a gain can change what "has to" means.

The step as designed (``update_mode="dense"``): gather one row per cold
feature entry, read the hot head once, scatter-add one gradient row per cold
entry into a [T, D] buffer, then one elementwise FTRL pass over the whole
table.  Recomputed or padded traffic does not count.
"""

from __future__ import annotations

F32 = 4
# the FTRL pass reads param, n, z and the gradient buffer and writes param,
# n, z; the buffer is written once more when it is zeroed
DENSE_PASS_ARRAYS = 8


def train_step(
    fields: dict, tables: dict[str, int], entries_per_step: float,
    hot_share: float,
) -> dict:
    """``tables`` maps table name to row width; ``entries_per_step`` is the
    real feature entries of a batch, ``hot_share`` the part of them the hot
    head serves."""
    rows = 1 << fields["table_size_log2"]
    hot_rows = (1 << fields["hot_size_log2"]) if fields.get("hot_size_log2") else 0
    width = sum(tables.values())
    cold = entries_per_step * (1.0 - hot_share)
    gather = cold * width * F32 + hot_rows * width * F32
    scatter = 2 * cold * width * F32  # read-modify-write of the buffer row
    dense = DENSE_PASS_ARRAYS * rows * width * F32
    return {
        "hbm_bytes": gather + scatter + dense,
        "hbm_bytes_gather": gather,
        "hbm_bytes_scatter": scatter,
        "hbm_bytes_dense_pass": dense,
        "gather_slices": cold * len(tables),
        "scatter_slices": cold * len(tables),
        "devices": fields.get("num_devices", 1) or 1,
    }


def roofline_share(bytes_moved: float, seconds: float, peaks: dict, devices: int = 1) -> float:
    """Percent of the HBM roofline: the least time ``devices`` chips could
    take to move ``bytes_moved`` over the time they took."""
    return 100.0 * bytes_moved / (peaks["hbm_bytes_per_s"] * devices) / seconds

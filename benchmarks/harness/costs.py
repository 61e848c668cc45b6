"""Bytes and slices a train step has to move and the matmul operations it
has to do, from shapes and counts: the numerator of a roofline share.  Kept
with the benchmark so that no PR that claims a gain can change what "has to"
means.

The step as designed (``update_mode="dense"``): gather one row per cold
feature entry, read the hot head once, scatter-add one gradient row per cold
entry into a [T, D] buffer, then one elementwise FTRL pass over the whole
table.  Recomputed or padded traffic does not count.  A family with dense
parameters declares its forward's ``[B, k] x [k, n]`` products
(``reference/<family>.py::matmuls``); each costs ``2 B k n`` operations
forward and twice that backward (the gradient of either operand).  The
one-hot field contraction is not among them: a sum by field needs no
multiplication.
"""

from __future__ import annotations

from collections.abc import Sequence

F32 = 4
# the FTRL pass reads param, n, z and the gradient buffer and writes param,
# n, z; the buffer is written once more when it is zeroed
DENSE_PASS_ARRAYS = 8


def train_step(
    fields: dict, tables: dict[str, int], entries_per_step: float,
    hot_share: float, matmuls: Sequence[tuple[int, int]] = (),
) -> dict:
    """``tables`` maps table name to row width; ``entries_per_step`` is the
    real feature entries of a batch, ``hot_share`` the part of them the hot
    head serves; ``matmuls`` the ``(k, n)`` of each product of a forward
    pass over the batch (none for a family whose parameters are all table
    rows)."""
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
        "flops": 6.0 * fields["batch_size"] * sum(k * n for k, n in matmuls),
        "gather_slices": cold * len(tables),
        "scatter_slices": cold * len(tables),
        "devices": fields.get("num_devices", 1) or 1,
    }


def roofline_share(
    bytes_moved: float, seconds: float, peaks: dict, devices: int = 1,
    flops: float = 0.0,
) -> float:
    """Percent of the roofline: the least time ``devices`` chips could take,
    the slower of moving ``bytes_moved`` through HBM and doing ``flops`` on
    the MXU (the published bfloat16 peak, so a float32 product can never read
    over 100), over the time they took."""
    share = 100.0 * bytes_moved / (peaks["hbm_bytes_per_s"] * devices) / seconds
    if flops:
        by_flops = 100.0 * flops / (peaks["flops_per_s_bf16"] * devices) / seconds
        share = max(share, by_flops)
    return share

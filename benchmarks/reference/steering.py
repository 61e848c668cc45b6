"""Which features of a row the hot/cold steering keeps: the benchmark's own
statement of the rule ``io/batch.py::split_hot`` and ``native/parser.cc``
implement, used to count what a geometry drops and to give the serving
reference the truncation the engine applies.

A row's entries are taken in order, and only as many as the two sections
have room for together.  Of those, one whose table row is below ``hot_size``
goes to the hot section while that has room (``hot_nnz``); every other
entry, hot overflow included, goes to the cold section while that has room
(``max_nnz``); what is left is dropped.
"""

from __future__ import annotations

import numpy as np


def kept(rows: np.ndarray, hot_size: int, hot_nnz: int, max_nnz: int) -> np.ndarray:
    """bool [n, k]: the entries of remapped table rows ``rows`` [n, k] that
    survive steering."""
    if not hot_size:
        hot_nnz = 0
    fits = np.arange(rows.shape[1]) < hot_nnz + max_nnz
    is_hot = fits & (rows < hot_size)
    to_hot = is_hot & (np.cumsum(is_hot, axis=1) <= hot_nnz)
    rest = fits & ~to_hot
    return to_hot | (rest & (np.cumsum(rest, axis=1) <= max_nnz))


def dropped_share(rows: np.ndarray, hot_size: int, hot_nnz: int, max_nnz: int) -> float:
    """Share of the entries of ``rows`` that steering drops."""
    return 1.0 - float(kept(rows, hot_size, hot_nnz, max_nnz).mean())

"""Padded static-shape minibatch representation.

The reference's minibatch is ``Data{fea_matrix: vector<vector<kv>>,
label: vector<int>}`` with ``kv = {fgid, fid, val}`` (io.h:18-22,61-65) —
ragged rows of sparse features.  XLA wants static shapes, so a batch is
a padded COO block: ``[B, K]`` arrays of table keys, field ids (slots),
values, and a validity mask, plus per-example labels and weights.  Pad
feature entries carry ``mask=0`` and key 0; pad examples (tail of the
last batch of a shard) carry ``weight=0`` so the mean-over-batch
gradient (reference: lr_worker.cc:116-118 divides by row count) uses
the true example count.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def narrow_keys_i32(keys: np.ndarray) -> np.ndarray:
    """THE sanctioned uint64→int32 key narrowing (analysis rule XF011).

    Batch key planes are int32 (XLA gather/scatter indices), but the
    feature key space is uint64 (hashed fids, io/hashing.py) — every
    narrowing is only safe AFTER reduction mod ``table_size``
    (table_size_log2 <= 30, config.py).  Ad-hoc ``.astype(np.int32)``
    casts scattered through the host path would silently WRAP if a
    future table-size bump (or an unreduced 64-bit key) ever reached
    one; this helper is the single audited choke point: already-int32
    input passes through free, anything wider is range-checked before
    the cast (the same reject-never-wrap contract as pack_batch and
    the native parser's -2 return).
    """
    a = np.asarray(keys)
    if a.dtype == np.int32:
        return a
    if a.size and (
        int(a.min()) < np.iinfo(np.int32).min
        or int(a.max()) > np.iinfo(np.int32).max
    ):
        raise ValueError(
            "narrow_keys_i32: key exceeds int32 — reduce full 64-bit "
            "keys mod table_size before narrowing (reject, never wrap)"
        )
    return a.astype(np.int32)


@dataclasses.dataclass
class Batch:
    keys: np.ndarray  # int32 [B, K] — row index into the hashed weight table
    slots: np.ndarray  # int32 [B, K] — field/group id (reference fgid)
    vals: np.ndarray  # float32 [B, K] — feature value (all-1 in hash mode)
    mask: np.ndarray  # float32 [B, K] — 1 for real feature entries
    labels: np.ndarray  # float32 [B] — binary labels
    weights: np.ndarray  # float32 [B] — 1 for real examples, 0 for padding
    # Optional hot section (frequency-head keys < hot_size, served by the
    # MXU path — ops/hot.py): [B, Kh] arrays, Kh = 0 when disabled.  The
    # main arrays above then form the "cold" DMA-path section; a sample's
    # logical feature list is the concatenation of both sections.
    hot_keys: np.ndarray | None = None
    hot_slots: np.ndarray | None = None
    hot_vals: np.ndarray | None = None
    hot_mask: np.ndarray | None = None

    def __post_init__(self) -> None:
        if self.hot_keys is None:
            b = self.keys.shape[0]
            self.hot_keys = np.zeros((b, 0), np.int32)
            self.hot_slots = np.zeros((b, 0), np.int32)
            self.hot_vals = np.zeros((b, 0), np.float32)
            self.hot_mask = np.zeros((b, 0), np.float32)

    @property
    def batch_size(self) -> int:
        return int(self.keys.shape[0])

    @property
    def max_nnz(self) -> int:
        return int(self.keys.shape[1])

    @property
    def hot_nnz(self) -> int:
        return int(self.hot_keys.shape[1])

    def num_real(self) -> int:
        return int(self.weights.sum())


def numeric_plane(batch: "Batch", numeric_fields: int) -> np.ndarray:
    """float32 ``[B, numeric_fields]``: the value of the row's entry of each
    numeric field (a field id below ``numeric_fields``; libffm's
    ``field:index:value`` with one index a field), 0 where the row has
    none.  What the compact and the dictionary wire and a packed-v2
    record ship of a batch's values (io/compact.py, parallel/step.py):
    every other entry's value is 1 there, and ``values_from_plane``
    rebuilds the ``[B, K]`` values from the plane and the field ids."""
    plane = np.zeros((batch.batch_size, numeric_fields), np.float32)
    for slots, vals, mask in (
        (batch.hot_slots, batch.hot_vals, batch.hot_mask),
        (batch.slots, batch.vals, batch.mask),
    ):
        r, c = np.nonzero((mask > 0) & (slots >= 0) & (slots < numeric_fields))
        plane[r, slots[r, c]] = vals[r, c]
    return plane


def values_from_plane(
    slots: np.ndarray, mask: np.ndarray, plane: np.ndarray | None
) -> np.ndarray:
    """``numeric_plane``'s inverse for one section: float32 ``[B, K]``
    values, the plane's number for a real entry of a numeric field, 1 for
    every other real entry, 0 for padding.  ``plane`` None: no numeric
    field, the hash mode's binary values."""
    if plane is None or not plane.shape[1]:
        return mask.astype(np.float32)
    numeric = (slots >= 0) & (slots < plane.shape[1])
    picked = np.take_along_axis(plane, np.where(numeric, slots, 0), axis=1)
    return np.where(numeric, picked, np.float32(1.0)) * mask


def values_fit_plane(batch: "Batch", numeric_fields: int) -> bool:
    """Whether a ``[B, numeric_fields]`` plane holds every value of the
    batch: each real entry outside the numeric fields has value 1, and the
    entries a row has of one numeric field agree (``numeric_fields`` 0: the
    hash mode's invariant, value 1 wherever mask 1)."""
    plane = numeric_plane(batch, numeric_fields) if numeric_fields else None
    return all(
        np.array_equal(values_from_plane(slots, mask, plane), vals * mask)
        for slots, vals, mask in (
            (batch.hot_slots, batch.hot_vals, batch.hot_mask),
            (batch.slots, batch.vals, batch.mask),
        )
    )


@dataclasses.dataclass
class ParsedBlock:
    """CSR view of one parsed text block (pre-padding)."""

    labels: np.ndarray  # float32 [n]
    row_ptr: np.ndarray  # int64 [n+1]
    keys: np.ndarray  # int64 [nnz] — already reduced mod table_size
    slots: np.ndarray  # int32 [nnz]
    vals: np.ndarray  # float32 [nnz]

    @property
    def num_samples(self) -> int:
        return int(self.labels.shape[0])


def split_hot(
    keys: np.ndarray,
    slots: np.ndarray,
    vals: np.ndarray,
    mask: np.ndarray,
    hot_size: int,
    hot_nnz: int,
) -> dict[str, np.ndarray]:
    """Steer padded [B, Ktot] feature entries into a hot section
    ([B, hot_nnz], keys < hot_size) and a cold section ([B, Ktot -
    hot_nnz], everything else).

    Per row, the first ``hot_nnz`` hot entries (in original order) go to
    the hot section; hot overflow spills into the cold section — which
    is always correct, since the cold DMA path addresses the full table
    including rows [0, hot_size).  Cold entries beyond the cold
    capacity are truncated, the same semantics as the overall max_nnz
    cap.  All O(B*Ktot) vectorized numpy; no per-row loops.
    """
    b, ktot = keys.shape
    kh = hot_nnz
    kc = ktot - kh
    valid = mask > 0
    is_hot = valid & (keys < hot_size)
    hot_rank = np.cumsum(is_hot, axis=1) - 1
    to_hot = is_hot & (hot_rank < kh)
    eff_cold = valid & ~to_hot
    cold_rank = np.cumsum(eff_cold, axis=1) - 1
    to_cold = eff_cold & (cold_rank < kc)

    def compact(arr, sel, rank, width, dtype):
        """Left-compact arr[sel] into [b, width] rows; arr=None writes the
        constant 1.0 (the mask) without materializing a ones array."""
        out = np.zeros((b, width), dtype=dtype)
        r, c = np.nonzero(sel)
        out[r, rank[sel]] = 1.0 if arr is None else arr[r, c]
        return out

    return {
        "hot_keys": compact(keys, to_hot, hot_rank, kh, np.int32),
        "hot_slots": compact(slots, to_hot, hot_rank, kh, np.int32),
        "hot_vals": compact(vals, to_hot, hot_rank, kh, np.float32),
        "hot_mask": compact(None, to_hot, hot_rank, kh, np.float32),
        "keys": compact(keys, to_cold, cold_rank, kc, np.int32),
        "slots": compact(slots, to_cold, cold_rank, kc, np.int32),
        "vals": compact(vals, to_cold, cold_rank, kc, np.float32),
        "mask": compact(None, to_cold, cold_rank, kc, np.float32),
    }


def make_batch(
    keys: np.ndarray,
    slots: np.ndarray,
    vals: np.ndarray,
    mask: np.ndarray,
    labels: np.ndarray,
    weights: np.ndarray,
    hot_size: int = 0,
    hot_nnz: int = 0,
) -> Batch:
    """Build a Batch from padded [B, Ktot] feature arrays, steering
    entries into hot/cold sections when ``hot_size > 0`` (the single
    construction point shared by pack_batch, prepare_batch, and the
    bench/driver synthetic-batch builders)."""
    if not hot_size:
        return Batch(
            keys=keys, slots=slots, vals=vals, mask=mask,
            labels=labels, weights=weights,
        )
    return Batch(
        labels=labels,
        weights=weights,
        **split_hot(keys, slots, vals, mask, hot_size, hot_nnz),
    )


def remap_batch(
    batch: Batch,
    remap: np.ndarray | None,
    hot_size: int,
    hot_nnz: int,
    cold_nnz: int | None = None,
) -> Batch:
    """Bring an externally built Batch (raw hash-space keys) into a
    hot-table model's key space: apply the frequency remap (io/freq.py)
    and re-steer the hot/cold sections.  Loader-produced batches are
    already remapped at parse/pack time; this is for user-supplied
    batches (api.XFlow.predict_batch, serve.PredictEngine).  The ONE
    copy of the remap-and-steer rule, shared by Trainer.prepare_batch
    and the serving engine so the two paths cannot drift.

    No-op when ``remap`` is None (model trained without a hot table).

    ``cold_nnz`` fixes the cold capacity after the re-steer: a batch of
    total width ``cold_nnz + hot_nnz`` then comes out exactly as the
    loader's ``pack_batch`` would have built it, cold overflow truncated
    the same way (the serving engine's canonical path).  None keeps the
    full incoming width as cold capacity, so nothing is truncated.
    """
    if remap is None:
        return batch
    # merge any existing hot section back, remap, then re-steer (a
    # remapped key may cross the hot/cold boundary in either direction);
    # by default pad by hot_nnz columns so the post-split cold capacity
    # equals the full incoming width — even if every incoming entry
    # lands cold, nothing is truncated on re-steer
    b = batch.batch_size
    width = batch.hot_nnz + batch.max_nnz
    pad = hot_nnz if cold_nnz is None else cold_nnz + hot_nnz - width
    if pad < 0:
        raise ValueError(
            f"remap_batch: batch width {width} exceeds cold_nnz + "
            f"hot_nnz = {cold_nnz + hot_nnz}"
        )
    pad_i = np.zeros((b, pad), np.int32)
    pad_f = np.zeros((b, pad), np.float32)
    keys = np.concatenate([batch.hot_keys, batch.keys, pad_i], axis=1)
    slots = np.concatenate([batch.hot_slots, batch.slots, pad_i], axis=1)
    vals = np.concatenate([batch.hot_vals, batch.vals, pad_f], axis=1)
    mask = np.concatenate([batch.hot_mask, batch.mask, pad_f], axis=1)
    keys = narrow_keys_i32(np.where(mask > 0, remap[keys], 0))
    return make_batch(
        keys, slots, vals, mask, batch.labels, batch.weights,
        hot_size, hot_nnz,
    )


def pad_batch_rows(batch: Batch, to: int) -> Batch:
    """Extend a Batch to ``to`` rows with zero-weight padding examples
    (mask/weights 0 — no-ops through predict and training alike).  Used
    by the serving engine to snap request batches onto its fixed
    compile-shape buckets."""
    extra = to - batch.batch_size
    if extra < 0:
        raise ValueError(
            f"pad_batch_rows: batch has {batch.batch_size} rows, "
            f"cannot shrink to {to}"
        )
    if extra == 0:
        return batch

    def pad(a: np.ndarray) -> np.ndarray:
        return np.concatenate(
            [a, np.zeros((extra,) + a.shape[1:], a.dtype)]
        )

    return Batch(
        keys=pad(batch.keys),
        slots=pad(batch.slots),
        vals=pad(batch.vals),
        mask=pad(batch.mask),
        labels=pad(batch.labels),
        weights=pad(batch.weights),
        hot_keys=pad(batch.hot_keys),
        hot_slots=pad(batch.hot_slots),
        hot_vals=pad(batch.hot_vals),
        hot_mask=pad(batch.hot_mask),
    )


def pack_batch(
    block: ParsedBlock,
    start: int,
    end: int,
    batch_size: int,
    max_nnz: int,
    hot_size: int = 0,
    hot_nnz: int = 0,
) -> Batch:
    """Pack samples [start, end) of a CSR block into one padded Batch.

    Rows with more than ``max_nnz`` features are truncated (the reference
    has no per-sample feature cap; SURVEY §7 hard part (b)).  With
    ``hot_size > 0``, each row gets ``hot_nnz`` extra slots of hot-key
    capacity and its entries are steered by ``split_hot``.
    """
    n = end - start
    assert 0 < n <= batch_size
    # Keys narrow to int32 batch arrays; reject, never wrap — the same
    # guard the native pack enforces (parser.cc returns -2).  Scoped to
    # the packed slice so the check is O(slice nnz).
    lo, hi = int(block.row_ptr[start]), int(block.row_ptr[end])
    if hi > lo:
        kslice = block.keys[lo:hi]
        if kslice.min() < 0 or kslice.max() > np.iinfo(np.int32).max:
            raise ValueError(
                "pack_batch: a key exceeds int32 — table_size too large "
                "for the int32 batch arrays (full 64-bit keys must be "
                "reduced before packing)"
            )
    ktot = max_nnz + (hot_nnz if hot_size else 0)
    labels = np.zeros(batch_size, dtype=np.float32)
    weights = np.zeros(batch_size, dtype=np.float32)
    labels[:n] = block.labels[start:end]
    weights[:n] = 1.0

    starts = block.row_ptr[start:end]
    ends = block.row_ptr[start + 1 : end + 1]
    counts = np.minimum(ends - starts, ktot)
    # vectorized ragged→padded gather: position j of row i reads CSR slot
    # starts[i]+j while j < counts[i]
    j = np.arange(ktot, dtype=np.int64)[None, :]
    valid = j < counts[:, None]  # [n, K]
    src = np.where(valid, starts[:, None] + j, 0)

    def pad_gather(flat: np.ndarray, dtype) -> np.ndarray:
        out = np.zeros((batch_size, ktot), dtype=dtype)
        if len(flat):
            out[:n] = np.where(valid, flat[src], 0)
        return out

    keys = pad_gather(block.keys, np.int32)
    slots = pad_gather(block.slots, np.int32)
    vals = pad_gather(block.vals, np.float32)
    mask = np.concatenate(
        [
            valid.astype(np.float32),
            np.zeros((batch_size - n, ktot), np.float32),
        ]
    )
    return make_batch(
        keys, slots, vals, mask, labels, weights, hot_size, hot_nnz
    )

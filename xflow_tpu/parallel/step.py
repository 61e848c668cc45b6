"""The pjit'd train/predict step — workers, server, and optimizer fused
into one XLA program.

One reference minibatch costs: per-thread sort+unique
(lr_worker.cc:147-166), a blocking Pull RPC, the loss/gradient joins
(lr_worker.cc:100-143), a blocking Push RPC, and the server-side FTRL
loop (ftrl.h:54-79).  Here the whole round trip is a single jitted
function over sharded arrays:

    gather rows → logit → clamped sigmoid → residual
    → per-occurrence grads → consolidate per unique key
    → gather state rows → optimizer recurrence → scatter back

Gradient scaling matches the reference: the per-key gradient is the sum
of (sigma(logit)-y) contributions over the minibatch divided by the
real example count (lr_worker.cc:116-118).
"""

from __future__ import annotations

import functools
import re
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from xflow_tpu.config import Config
from xflow_tpu.io.batch import Batch
from xflow_tpu.models.base import BatchArrays, Model
from xflow_tpu.obs import NULL_OBS
from xflow_tpu.ops.hot import gather_form, scatter_form
from xflow_tpu.ops.sparse import (
    consolidate_apply,
    consolidate_plan,
    gather_rows,
    scatter_rows,
)
from xflow_tpu.ops.window import (
    lane_select_tpu,
    lane_select_xla,
    monotone_take,
    wide_take,
)
from xflow_tpu.optim.base import Optimizer
from xflow_tpu.parallel import exchange
from xflow_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    replicated,
    table_sharding,
)
from xflow_tpu.utils.compile_cache import key_by_source
from xflow_tpu.utils.metrics import logloss, logloss_sum, sigmoid_ref

# One instruction of a compiled module's text: its name and, as
# benchmarks/harness/trace_reduce.py::short_name prints it, its (first)
# result type without layout — the pair a profiler's operation event
# carries on a TPU.
_HLO_INSTRUCTION_RE = re.compile(
    r"^\s+(?:ROOT )?%?(?P<op>[^ ]+) = \(?(?P<type>[a-z0-9]+\[[0-9,]*\])?"
)
_HLO_NEVER_RUNS_RE = re.compile(
    r" (?:parameter|constant|get-tuple-element|tuple)\("
)
_HLO_FUSED_RE = re.compile(r"\bfusion\(.*\bcalls=%?([^ ,)]+)")
_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([^ ]+) \(.*\{$")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS_RE = re.compile(r"\bcalls=%?([^ ,)]+)")
_HLO_COLLECTIVE_RE = re.compile(
    rf" (?:{exchange.COLLECTIVE_OPS})(?:-start|-done)?\("
)
_SCOPE_RE = re.compile(r"xf\.[A-Za-z0-9_]+")


def scope_of(op_name: str) -> str:
    """The ``xf.*`` scope of an instruction's ``op_name`` path: the
    INNERMOST one, the last ``xf.<name>`` anywhere in the path (autodiff
    and scan wrap path components: ``transpose(jvp(xf.dense))``), ``""``
    where the path has none.  A scope opened inside another is the more
    specific name for its operations: the dense half ``xf.dense``, the
    CIN ``xf.cin``, the interacting layers ``xf.attn`` and the
    gate-and-bilinear block ``xf.bilinear`` (models/blocks.py) run
    inside ``xf.forward_backward`` and are read apart from it, the
    CIN's and the attention's also through their loop, and all three
    through their rematerialised backward
    (``transpose(jvp(xf.cin))/while/body/closed_call/checkpoint/...``).
    Until PR 39 the first name won; no program without a dense half
    nests two different scopes, so theirs map as they did
    (tests/test_tpu_compile.py holds the benchmark's to that)."""
    found = _SCOPE_RE.findall(op_name)
    return found[-1] if found else ""


# The planes of field ids a batch can ship, by wire: the full wire's,
# the compact wire's (compact_wire_np), the dictionary wire's
# (io/compact.py::CompactBatch.wire).  The compact and dictionary wires
# ship them only for a model that reads them (TrainStep._ship_slots).
_SLOT_PLANES = frozenset({
    "slots", "hot_slots", "slots_u8", "hot_slots_u8", "cw_cs", "cw_hs",
})
# The plane of the numeric fields' values (Config.numeric_fields), by wire:
# the compact wire's (compact_wire_np) and the dictionary wire's
# (io/compact.py::CompactBatch.wire).  The full wire ships every value in
# its vals planes and has no such plane.
_VALUE_PLANES = frozenset({"nvals", "cw_nv"})
# The dictionary wire's planes whose length is a plane_cap capacity (the
# others count the batch's rows): TrainStep._settle_planes.
_CAPACITY_PLANES = frozenset({
    "cw_cu", "cw_ci", "cw_ct", "cw_cf", "cw_cs",
    "cw_h8", "cw_hx", "cw_hxh", "cw_hf", "cw_hs",
})

# State pytree:
# {"tables": {name: {"param": [T,D], <aux>: [T,D]...}},
#  "dense": {name: array} (replicated; {} for table-only models),
#  "step": int32 scalar}
State = dict[str, Any]


def abstract_like(tree):
    """``tree`` with every array replaced by its shape, dtype and
    sharding: what a program can be lowered from without holding (or
    donating) a buffer."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree,
    )


@jax.named_scope("xf.forward_backward")
def grads_from_rows(model, rows: dict, dense: dict, mbatch: BatchArrays,
                    num_real: jax.Array):
    """pctr + per-occurrence gradients, rows already gathered: the ONE
    forward/backward shared by TrainStep (all update modes) and the
    tiered store's hot+miss step (store/hot.py) so the two cannot
    drift.  ``mbatch`` is the model view (hot/cold sections already
    concatenated where applicable).  Returns (pctr, occ_grads,
    grad_dense_or_None); occ_grads are residual-scaled and divided by
    ``num_real``, the reference's mean-gradient semantics
    (lr_worker.cc:116-118)."""
    autodiff = getattr(model, "autodiff", False)
    if autodiff:
        # Autodiff arm (FFM, wide&deep, DCN, two-tower: no reference
        # gradient quirks): the logit's pullback, handed the same
        # residual as the written-out arm below.
        logit, pullback = jax.vjp(
            lambda rows_, dense_: model.logit(rows_, mbatch, dense_),
            rows, dense,
        )
    else:
        logit = model.logit(rows, mbatch)
    pctr = sigmoid_ref(logit)
    # Residual "loss" exactly as the reference names it
    # (lr_worker.cc:121-143): sigma(wx) - y, zeroed for pad
    # examples, pre-divided by batch size for the mean-gradient
    # semantics.  ONE expression for both arms: the autodiff arm used
    # to take it as the derivative of a softplus loss, which the TPU
    # computes to 7e-5 of the sigmoid (rms 1.4e-5 over logits in
    # [-3, 3]; this expression: 1.2e-6, rms 2.7e-7), so every gradient
    # of the benchmark's FFM cell missed the reference by 1e-5 of its
    # terms (PERF.md section 6, PR 34).
    residual = (pctr - mbatch["labels"]) * mbatch["weights"] / num_real
    if autodiff:
        grad_rows, grad_dense = pullback(residual)
        return pctr, grad_rows, (grad_dense or None)
    grad_logit = model.grad_logit(rows, mbatch)
    occ_grads = {
        name: g * residual[:, None, None]
        for name, g in grad_logit.items()
    }
    return pctr, occ_grads, None


@jax.named_scope("xf.optimizer")
def apply_dense_sgd(dense: dict, grad_dense, lr: float) -> dict:
    """Dense (MLP) params take plain SGD regardless of the table
    optimizer (models/wide_deep.py rationale) — the ONE copy of that
    rule, shared by TrainStep (per-dispatch and per-slice application)
    and the tiered store's step (store/hot.py)."""
    if not dense or grad_dense is None:
        return dense
    return jax.tree.map(lambda p, g: p - lr * g, dense, grad_dense)


def init_state(model: Model, optimizer: Optimizer, cfg: Config, mesh) -> State:
    """Create sharded zero/random-initialized tables (plus replicated
    dense params for models that have them).

    v-table random init reproduces the reference's lazy server-side
    N(0,1)*1e-2 (ftrl.h:113-120) eagerly; see optim/ftrl.py.
    """
    # On one device the step hands the tables back replicated: the
    # partitioner drops an axis of size 1.  Placed like that from the
    # start (as the scalar below is), a train program's text, and with
    # it its compile-cache key, is the same whether its shapes are the
    # process's first or a later one; rows over "data" here made the
    # first program of every run one that no other run's order of
    # shapes could have cached (PERF.md section 6, PR 32).
    sharding = (
        table_sharding(mesh) if mesh.devices.size > 1 else replicated(mesh)
    )
    rng = jax.random.PRNGKey(cfg.seed)
    tables: dict[str, dict[str, jax.Array]] = {}
    for i, spec in enumerate(model.tables()):
        shape = (cfg.table_size, spec.dim)
        # once-per-Trainer-construction table init, one jit per table
        # spec by design — not a hot-loop retrace (xf: ignore[XF001])
        init_fn = jax.jit(
            functools.partial(spec.init, shape=shape), out_shardings=sharding
        )
        param = init_fn(jax.random.fold_in(rng, i))
        entry = {"param": param}
        for aux_name, aux in optimizer.init_aux(param).items():
            entry[aux_name] = jax.device_put(aux, sharding)
        tables[spec.name] = entry
    dense = {}
    if hasattr(model, "dense_init"):
        dense = jax.tree.map(
            lambda a: jax.device_put(a, replicated(mesh)),
            model.dense_init(jax.random.fold_in(rng, 1000)),
        )
    # placed like the step's own output (replicated over the mesh): an
    # uncommitted scalar here made the second train call a jit cache
    # miss, compiling the first shape bucket twice
    step = jax.device_put(jnp.zeros((), jnp.int32), replicated(mesh))
    return {"tables": tables, "dense": dense, "step": step}


def batch_to_arrays(batch: Batch) -> BatchArrays:
    out = {
        "keys": jnp.asarray(batch.keys),
        "slots": jnp.asarray(batch.slots),
        "vals": jnp.asarray(batch.vals),
        "mask": jnp.asarray(batch.mask),
        "labels": jnp.asarray(batch.labels),
        "weights": jnp.asarray(batch.weights),
    }
    if batch.hot_nnz:
        out["hot_keys"] = jnp.asarray(batch.hot_keys)
        out["hot_slots"] = jnp.asarray(batch.hot_slots)
        out["hot_vals"] = jnp.asarray(batch.hot_vals)
        out["hot_mask"] = jnp.asarray(batch.hot_mask)
    return out


def validate_compact_batch(batch: Batch, numeric_fields: int = 0) -> None:
    """Compact-wire invariants: binary features (val 1 wherever mask 1;
    with ``numeric_fields`` > 0 an entry of a field below it may hold any
    value, one a row and field: the ``nvals`` plane ships it) and 0/1
    labels/weights.  Loader-produced hash-mode batches satisfy
    them by construction, so put_batch validates only the FIRST batch
    per TrainStep — full [B,K] scans on every batch would burn the host
    CPU the compact format exists to relieve."""
    import numpy as np

    from xflow_tpu.io.batch import values_fit_plane

    if not values_fit_plane(batch, numeric_fields):
        raise ValueError(
            "compact wire requires binary features (val 1 wherever "
            "mask 1) outside the numeric fields and one value a row and "
            "numeric field; set wire_mode='full' for other "
            "value-carrying batches"
        )
    for arr in (batch.labels, batch.weights):
        if not np.isin(arr, (0.0, 1.0)).all():
            raise ValueError(
                "compact wire requires 0/1 labels and weights; set "
                "wire_mode='full'"
            )


def compact_wire_np(
    batch: Batch, ship_slots: bool = False, hot_u16: bool = False,
    numeric_fields: int = 0,
) -> dict:
    """The numpy (host) half of the compact wire: sentinel-coded int32
    keys + uint8 labels/weights, plus a uint8 slots plane for models
    that read field ids.  Shared by batch_to_compact and the bench's
    host-feed measurement so the measured per-batch work is by
    construction exactly the work the training feed performs.

    hot_u16: ship the hot section's keys as uint16 (sentinel 0xFFFF)
    instead of int32 — hot row ids are < H by construction
    (io/batch.py::split_hot), so with H <= 2^15 the plane halves with
    no id/sentinel collision possible.  At the lr flagship geometry
    (cold 16 + hot 32) this takes the wire from 194 to 130
    bytes/example — a direct multiplier on the link-bound e2e path.

    The u8 slot clamp (min(slot, 255)) is lossless under the models'
    shared out-of-range semantics: every slot consumer drops fields >=
    max_fields via a one-hot row of zeros (mvm.py:76, ffm.py:11,
    wide_deep.py:73), so with max_fields <= 255 (enforced at TrainStep
    init) a clamped slot lands in the ignored range either way.

    numeric_fields > 0 (Config.numeric_fields): one float32 plane
    ``nvals [B, numeric_fields]``, the value of the row's entry of each
    numeric field (io/batch.py::numeric_plane), from which and the field
    ids _expand_wire rebuilds the values; absent at 0, where the wire is
    what it always was."""
    import numpy as np

    from xflow_tpu.io.batch import narrow_keys_i32, numeric_plane

    def sentinel(keys, mask):
        # narrow THROUGH the audited choke point (XF011): loader-built
        # batches are int32 already (free pass-through); an external
        # 64-bit-key batch is range-checked, never wrapped.  Masked
        # lanes are zeroed in the WIDE dtype first — padding may carry
        # unreduced garbage, and only live keys owe the range contract
        # — then the -1 sentinel is applied in int32 space.
        live = narrow_keys_i32(np.where(mask > 0, keys, 0))
        return np.where(mask > 0, live, np.int32(-1))

    def slots_u8(slots):
        # anything outside [0, 255] maps to 255 (>= max_fields → the
        # models ignore it, like the full wire does for negative or
        # oversized slots) — a plain uint8 cast would WRAP negatives
        # into the live field range
        return np.where(
            (slots < 0) | (slots > 255), 255, slots
        ).astype(np.uint8)

    out = {
        "ckeys": sentinel(batch.keys, batch.mask),
        "labels_u8": batch.labels.astype(np.uint8),
        "weights_u8": batch.weights.astype(np.uint8),
    }
    if numeric_fields:
        out["nvals"] = numeric_plane(batch, numeric_fields)
    if ship_slots:
        out["slots_u8"] = slots_u8(batch.slots)
    if batch.hot_nnz:
        if hot_u16:
            out["hot_ckeys_u16"] = np.where(
                batch.hot_mask > 0, batch.hot_keys, 0xFFFF
            ).astype(np.uint16)
        else:
            out["hot_ckeys"] = sentinel(batch.hot_keys, batch.hot_mask)
        if ship_slots:
            out["hot_slots_u8"] = slots_u8(batch.hot_slots)
    return out


def _checked(batch: Batch, check: bool, numeric_fields: int = 0) -> Batch:
    if check:
        validate_compact_batch(batch, numeric_fields)
    return batch


def values_from_numeric(
    plane: jax.Array, slots: jax.Array, mask: jax.Array
) -> jax.Array:
    """The ``[B, K]`` values of one section of a batch whose wire shipped
    a values plane ``[B, numeric_fields]`` (``nvals`` / ``cw_nv``): the
    plane's number for a real entry of a numeric field, 1 for every other
    real entry, 0 for padding; io/batch.py::values_from_plane on the
    device.  One select a numeric field over the ``[B, K]`` plane, each
    example's number broadcast along its entries: exact, and no gather
    with an index an entry (the TPU prices a gather per index)."""
    vals = mask
    for field in range(plane.shape[1]):
        vals = jnp.where(slots == field, plane[:, field:field + 1] * mask, vals)
    return vals


def batch_to_compact(
    batch: Batch,
    check: bool = True,
    ship_slots: bool = False,
    hot_u16: bool = False,
    numeric_fields: int = 0,
) -> BatchArrays:
    """Compact wire (Config.wire_mode): sentinel-coded keys + uint8
    labels/weights — ~16x fewer bytes/entry than the full format for
    slot-free models (lr, fm); slot-reading models (mvm, ffm,
    wide_deep) add a uint8 slots plane (~3x).  Only valid when vals
    are identically 1 for real entries (hash mode); _expand_wire
    reconstructs vals/mask (and zero slots when none shipped) on
    device."""
    if check:
        validate_compact_batch(batch, numeric_fields)
    return {
        k: jnp.asarray(v)
        for k, v in compact_wire_np(
            batch, ship_slots, hot_u16, numeric_fields
        ).items()
    }


# One plane's place in a packed wire buffer (pack_wire_np): its name,
# dtype, trailing shape and first byte in a row.  Hashable: a static
# argument of the program that unpacks it.
WireLayout = tuple[tuple[str, str, tuple[int, ...], int], ...]


def pack_wire_np(wire: dict) -> tuple[np.ndarray, WireLayout]:
    """The planes of a wire whose every plane leads with the batch axis
    (the compact and full wires of ``TrainStep.host_wire_np``; not the
    dictionary wire's flat planes), laid side by side as the bytes of
    ONE ``uint8[B, row_bytes]`` buffer, widest element first so that no
    element straddles its own alignment, with the layout
    ``unpack_wire`` inverts.  The layout follows from the planes' shapes
    and dtypes alone.  A request batch is a few KB and every
    host->device call has a fixed price, so the serving engine ships
    this buffer in one transfer (serve/engine.py) where the trainer
    ships large planes one by one on worker threads (put_batch)."""
    planes = sorted(wire.items(), key=lambda kv: -kv[1].dtype.itemsize)
    rows = len(planes[0][1])
    parts = [
        np.ascontiguousarray(v).view(np.uint8).reshape(rows, -1)
        for _, v in planes
    ]
    offsets = np.cumsum([0] + [p.shape[1] for p in parts])
    layout = tuple(
        (k, v.dtype.str, v.shape[1:], int(off))
        for (k, v), off in zip(planes, offsets)
    )
    return np.concatenate(parts, axis=1), layout


def unpack_wire(buf: jax.Array, layout: WireLayout) -> BatchArrays:
    """``pack_wire_np``'s planes back out of its buffer, inside a jitted
    program: each plane's columns sliced and bitcast to its dtype, bit
    for bit what the host packed."""
    out = {}
    for name, dtype, trail, off in layout:
        dt = np.dtype(dtype)
        width = int(np.prod(trail, dtype=np.int64)) * dt.itemsize
        cols = buf[:, off:off + width]
        if dt != np.uint8:
            # [B, n, itemsize] bytes -> [B, n] elements
            cols = jax.lax.bitcast_convert_type(
                cols.reshape(len(buf), -1, dt.itemsize), dt
            )
        out[name] = cols.reshape(len(buf), *trail)
    return out


def _interleaved_slices(batch: BatchArrays, s: int) -> BatchArrays:
    """Split the batch dim into s scan slices with INTERLEAVED example
    assignment (example i → slice i % s): each slice stays evenly
    spread over the batch-sharded mesh axis, so GSPMD sees a local
    strided view per slice instead of the reshard/all-to-all a
    contiguous split would force (slice 0 = first B/s rows = one
    device's shard).  Both scan modes are composition-insensitive:
    accumulate is order-independent, and sequential's slice sequence
    is an arbitrary partition of the dispatch window by design.

    A dictionary-wire batch's ``cold_plan`` (expand_dict_wire) indexes
    the WHOLE batch's flat streams and has no batch axis to split: a
    slice goes without it, and gathers a row per slot of its keys."""
    return {
        k: v.reshape((v.shape[0] // s, s) + v.shape[1:]).swapaxes(0, 1)
        for k, v in batch.items()
        if k != "cold_plan"
    }


def expand_dict_wire(cfg, lane_select, w: BatchArrays) -> BatchArrays:
    """Inverse of CompactBatch.wire (io/compact.py), inside the
    jitted step: rebuild the padded [B, K] planes from the flat
    tiered streams.  Beside the padded planes it hands on
    ``cold_plan``: what the host's dedup knows about the batch's cold
    keys, for the cold row gather (dict_cold_rows) and the cold
    gradients' way back (dict_scatter_plan) and nothing else.
    The dictionary ``cu`` and the raw tail ``ct`` (int32 table rows,
    at their plane capacities) are between them every distinct table
    row the cold section reads; ``ci`` is each dictionary occurrence's
    index into ``cu``; ``is_dict``/``is_tail`` and the running counts
    ``di_idx``/``tail_idx`` say, per padded position, which flat
    stream it reads and where.

    ``cfg`` gives max_nnz and hot_nnz; ``lane_select`` is
    the in-window shuffle of ops/window.py that the caller's platform
    runs (TrainStep picks it from its mesh).

    No padded ([B, K]) plane is rebuilt by gathering single elements
    (a gather costs a DMA descriptor per slice on the TPU, so the
    first form of this decode, five scalar gathers per plane, was 335
    of the flagship's 407 ms step: PERF.md section 6, PR 25).  Every
    index the rebuild needs is a running count over the padded
    positions in row-major order: entries before this one (``pos``),
    tier-A entries before it, tier-B entries before it.  Running
    counts are non-decreasing with steps of 0 or 1, so each plane is
    one ops/window.py::monotone_take: a row gather per 128 outputs
    and a lane shuffle inside the window.

    ONE gather with a free index is left, by name: the cold dictionary
    resolve ``cu[ci]``, the decode's only random access.  It runs over
    the flat occurrence plane ``cw_ci``, whose length is the plane_cap
    bucket of the batch's dictionary occurrences: at most B * max_nnz
    by plane_cap's ceiling and as much as that in a batch whose cold
    entries are nearly all dictionary hits, so it is smaller than a
    padded plane by the data, not by construction (flagship: 1 228 800
    of 1 572 864).  It is an ops/window.py::wide_take: as a gather of
    single elements it was 8.8 of the decode's 12.3 ms, as one of
    two-word rows 3.1 (PERF.md section 6, PR 30).

    Every plane capacity is static (plane_cap bucketing), so one
    steady batch geometry is one compiled program; the per-batch
    real counts arrive as the cc/hc count planes.

    A batch with numeric fields (Config.numeric_fields) also ships
    ``cw_nv [B, numeric_fields]``, their values: ``vals`` / ``hot_vals``
    are then rebuilt from it and the field ids (values_from_numeric);
    without the plane they are the masks, as they always were."""
    kc = cfg.max_nnz
    b = w["cw_cc"].shape[0]
    take = functools.partial(monotone_take, lane_select=lane_select)

    def bits(plane: jax.Array, n: int) -> jax.Array:
        p = plane.astype(jnp.int32)[:, None]
        shifts = jnp.arange(8, dtype=jnp.int32)[None, :]
        return ((p >> shifts) & 1).reshape(-1)[:n]

    def keys_plane(plane: jax.Array) -> jax.Array:
        if plane.ndim == 1:  # u32
            return plane.astype(jnp.int32)
        p = plane.astype(jnp.int32)  # [n, 3] u24 little-endian
        return p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16)

    def section(counts_plane, flags_plane, width):
        """Where each padded position of a [B, width] section reads
        the flat streams.  All flat over B*width, row-major:
        ``valid``; ``pos``, the real entries before this position
        (its own index in the flat occurrence stream where valid);
        ``is_a``/``is_b``, valid and flagged tier A (bit 1) / tier
        B; ``a_idx``/``b_idx``, the tier's entries before it."""
        counts = counts_plane.astype(jnp.int32)[:, None]
        colj = jnp.arange(width, dtype=jnp.int32)[None, :]
        valid = (colj < counts).reshape(-1)
        row_start = jnp.cumsum(counts, axis=0) - counts
        pos = (row_start + jnp.minimum(colj, counts)).reshape(-1)
        # the flag BYTE index pos >> 3 is a running count too
        fbyte = take(pos >> 3, flags_plane.astype(jnp.int32))
        flag = (fbyte >> (pos & 7)) & 1
        is_a = valid & (flag == 1)
        is_b = valid & (flag == 0)
        a = is_a.astype(jnp.int32)
        a_idx = jnp.cumsum(a) - a
        return valid, pos, is_a, is_b, a_idx, pos - a_idx

    def slots_plane(plane, pos, valid, width):
        s = take(pos, plane.astype(jnp.int32))
        return jnp.where(valid, s, 0).reshape(b, width)

    # cold: tier A = dictionary indices (resolved through cw_cu on
    # the flat occurrence stream), tier B = raw tail keys
    cvalid, cpos, is_dict, is_tail, di_idx, tail_idx = section(
        w["cw_cc"], w["cw_cf"], kc
    )
    cu = keys_plane(w["cw_cu"])
    ci = w["cw_ci"].astype(jnp.int32)
    dict_key = take(di_idx, wide_take(cu, ci))
    ct = keys_plane(w["cw_ct"])
    tail = take(tail_idx, ct)
    cmask = cvalid.astype(jnp.float32).reshape(b, kc)
    keys2d = jnp.where(
        is_dict, dict_key, jnp.where(is_tail, tail, 0)
    ).reshape(b, kc)
    out = {
        "keys": keys2d,
        "slots": (
            slots_plane(w["cw_cs"], cpos, cvalid, kc)
            if "cw_cs" in w
            else jnp.zeros_like(keys2d)
        ),
        "vals": cmask,
        "mask": cmask,
        "labels": bits(w["cw_lb"], b).astype(jnp.float32),
        "weights": bits(w["cw_wb"], b).astype(jnp.float32),
        "cold_plan": {
            "cu": cu, "ct": ct, "ci": ci,
            "is_dict": is_dict, "is_tail": is_tail,
            "di_idx": di_idx, "tail_idx": tail_idx,
            "n_dict": w["cw_cun"].astype(jnp.int32)[0],
        },
    }
    if "cw_hc" in w:
        kh = cfg.hot_nnz
        hvalid, hpos, is_h8, is_hx, h8_idx, hx_idx = section(
            w["cw_hc"], w["cw_hf"], kh
        )
        hx_vals = w["cw_hx"].astype(jnp.int32)
        if w["cw_hxh"].shape[0]:  # u12 tier: u8 lows + nibble highs
            hib = w["cw_hxh"].astype(jnp.int32)
            hi = jnp.stack(
                [hib & 0xF, hib >> 4], axis=1
            ).reshape(-1)[: hx_vals.shape[0]]
            hx_vals = hx_vals | (hi << 8)
        h8 = take(h8_idx, w["cw_h8"].astype(jnp.int32))
        hx = take(hx_idx, hx_vals)
        hot2d = jnp.where(
            is_h8, h8, jnp.where(is_hx, hx, 0)
        ).reshape(b, kh)
        hmask = hvalid.astype(jnp.float32).reshape(b, kh)
        out["hot_keys"] = hot2d
        out["hot_slots"] = (
            slots_plane(w["cw_hs"], hpos, hvalid, kh)
            if "cw_hs" in w
            else jnp.zeros_like(hot2d)
        )
        out["hot_vals"] = hmask
        out["hot_mask"] = hmask
    if "cw_nv" in w:  # a batch with numeric fields: their values
        out["vals"] = values_from_numeric(w["cw_nv"], out["slots"], cmask)
        if "cw_hc" in w:
            out["hot_vals"] = values_from_numeric(
                w["cw_nv"], out["hot_slots"], out["hot_mask"]
            )
    return out


# The narrowest table row that dict_cold_rows lays out by row gathers:
# of the widths measured (its docstring) the narrowest at which the rows
# win at both geometries.
ROW_LAYOUT_MIN_COLUMNS = 64


def resident_pass_selects(columns: int) -> bool:
    """Whether the dense update's whole-table optimizer pass over a
    float32 [T, columns] table is held to the layout the TPU keeps the
    state in (TrainStep._optimizer_pass's third arm), from the width
    alone: the state is kept rows-minor while the step reads and writes
    it by rows.

    The TPU keeps a table-sized f32[T, D] whichever way (8,128) tiles
    pad it less.  Rows-minor (``{0,1:T(8,128)}``: the T rows on the
    lanes, D rounded up to 8 sublanes, padded_columns) unless rounding D
    up to 128 lanes costs no more, then columns-minor
    (``{1,0:T(8,128)}``, a row contiguous).  Compiled for a described
    v5e, f32[2^21, D] as a program's donated argument (PR 59): D = 8,
    10, 26, 64, 96, 120, 129, 160, 192, 320 rows-minor; 127, 128, 250,
    256, 384 columns-minor.  A table of ROW_LAYOUT_MIN_COLUMNS or more
    is gathered by whole rows and scattered into by whole rows, which
    XLA runs columns-minor, and it then moves the elementwise pass
    between the scatter's buffer and the program's outputs onto that
    layout too and relays ``param``, ``n`` and ``z`` in and out: FFM's
    v [2^21, 160], 160 -> 256 columns and 2 GiB an array for 1.25, six
    table-sized copies a step (31.6 of 114.5 ms, ledger, PR 58;
    _optimizer_pass has what the compiler makes of the step either way).
    A narrower table goes column by column and is rows-minor throughout
    (MVM's [2^25, 10]: one fusion, no copy); a width the lanes do not
    pad (DLRM's 128) is kept columns-minor and its pass reads the state
    as it lies (no table-sized copy on its ledger line): neither is
    selected, and a constraint there would CAUSE the copies."""
    return (
        columns >= ROW_LAYOUT_MIN_COLUMNS
        and padded_columns(columns) < -(-columns // 128) * 128
    )


def dict_cold_rows(
    plan: dict, params: dict[str, jax.Array], lane_select
) -> dict[str, jax.Array]:
    """``{name: param[keys]}`` for a dictionary-wire batch's cold keys,
    [B * max_nnz, D] flat, with the [T, D] tables read once per entry
    of ``plan``'s dictionary and tail instead of once per padded slot:
    ``plan`` is expand_dict_wire's ``cold_plan``.

    A gather pays per index on the TPU, and an index into the big
    table pays double one into a batch-sized array (PERF.md section
    6, PR 30), while most padded slots repeat a dictionary key or are
    padding.  So: one row per dictionary entry and per tail entry out
    of the table; each dictionary occurrence's row out of THOSE
    (``rows_u[ci]``, the route's one per-occurrence gather, an
    ops/window.py::wide_take over a source of at most DICT_CAP rows);
    and the padded layout from the two flat row streams by the same
    running counts that lay out the key plane.

    How the layout reads the streams depends on the row's width, which
    is all that differs between the tables.  A narrow row goes column
    by column: one ops/window.py::monotone_take per stream and column,
    no gather by index at all, but a [B * max_nnz] plane a column,
    which inside the step becomes a [B, max_nnz, 1] plane that (8,128)
    tiles pad 128 x.  A row of ROW_LAYOUT_MIN_COLUMNS or more goes
    whole: the running counts index ROWS of the two streams, two
    wide_takes a table, an index a padded slot and stream whatever the
    width.  Measured on a v5e (scripts/probe_cold_gather.py, the whole
    route; PERF.md section 6, PR 35), by columns / by rows.  D = 10 at
    the geometry of lr_tb.train_packed (1 572 864 slots out of
    1 228 800 occurrences and a tail of 294 912): 21.3 / 47.5 ms.
    D = 160 at that of ffm_tb.train_packed (131 072 slots out of
    118 784 occurrences, no tail): 22.9 / 8.2 ms alone, and in the
    step, where the planes are padded, the whole of xf.gather reads
    92.0 / 10.3 ms.  Between them the two geometries cross at
    different widths (the small one from D = 8 on, 0.98 / 0.80 ms; the
    large one between D = 32, 51 / 128 ms, and D = 64, 94 / 53 ms; at
    D = 128 its column form no longer compiles), so the line stands at
    the narrowest width measured at which the rows win at both.

    Unmasked slots hold ``param[key]`` bit for bit; padding slots hold
    0 where ``param[keys]`` reads row 0 (masked in every reduction
    either way)."""
    take = functools.partial(monotone_take, lane_select=lane_select)
    cu, ct, ci = plan["cu"], plan["ct"], plan["ci"]
    is_dict, is_tail = plan["is_dict"], plan["is_tail"]
    di_idx, tail_idx = plan["di_idx"], plan["tail_idx"]
    out = {}
    for name, param in params.items():
        rows_t = param[ct]
        rows_occ = wide_take(param[cu], ci)
        if param.shape[-1] >= ROW_LAYOUT_MIN_COLUMNS:
            out[name] = jnp.where(
                is_dict[:, None],
                wide_take(rows_occ, di_idx),
                jnp.where(
                    is_tail[:, None], wide_take(rows_t, tail_idx), 0
                ),
            )
        else:
            out[name] = jnp.stack([
                jnp.where(
                    is_dict,
                    take(di_idx, rows_occ[:, j]),
                    jnp.where(is_tail, take(tail_idx, rows_t[:, j]), 0),
                )
                for j in range(param.shape[-1])
            ], axis=1)
    return out


# The table rows, by their columns, whose cold gradients leave a
# dictionary-wire batch through its dictionary (dict_cold_grads): the
# widths at which the route beat the scatter-add per padded slot at both
# geometries of scripts/probe_cold_scatter.py's sweep (dict_cold_grads'
# docstring has the table).  One column stays per slot (9 ns a slot at
# the table already), and so does a row wider than a lane tile (FFM's
# 160: the route won at FFM's batch and lost at MVM's).
DICT_SCATTER_COLUMNS = range(2, 65)

# The fewest padded table elements (rows x the width as the TPU pads a
# row, padded_columns) per index handed to the table at which the dense
# update of a dictionary-only batch runs the optimizer on the
# dictionary's rows alone (touched_rows_selects) instead of over a
# zeroed [T, D] gradient buffer.  From scripts/probe_touched_rows.py on
# a v5e (PR 56; one real batch of each cell, alone; ms), (a) zero + table
# write + pass / (b) 3 gathers + FTRL on the rows + 3 sets:
#   FiBiNET, xDeepFM [2^25, 10], 55 296 + 0 indices:  31.42 / 20.45
#   MVM [2^25, 10], 43 008 + 294 912 (rows repeat):   59.95 / 123.96
#   DCN [2^24, 26], 40 960 + 131 072:                 47.43 / 82.61
# A set costs what an add costs (100 ns an index at D = 10, 123 at 26)
# and a row gather 23-37 ns, so (b) is ~370 ns an index where (a) is
# ~103 ns an index + 25.7 ms for 2^29 padded elements (0.048 ns each):
# the two meet near 5 600 padded elements an index.  The three
# B = 16 384 cells stand at 9 709; MVM (1 589) and DCN (3 121) are ruled
# out by their tails before the size is asked.  The line is there for a
# SMALL table under a dictionary-only batch, where the pass is cheaper.
TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX = 5600


def padded_columns(columns: int) -> int:
    """The columns a float32 table row takes in the TPU's memory: the
    width rounded up to a multiple of 8 (10 -> 16, 26 -> 32)."""
    return -(-columns // 8) * 8


def touched_rows_selects(
    table_rows: int, columns: int, cap_u: int, cap_t: int
) -> bool:
    """Whether the dense update of a whole dictionary-wire batch whose
    dictionary and tail planes hold ``cap_u`` and ``cap_t`` entries runs
    the optimizer of a [table_rows, columns] table on the dictionary's
    rows alone (TrainStep._touched_rows_pass), from shapes: a width on
    the dictionary route (DICT_SCATTER_COLUMNS; one column loses: six
    arrays at 14.8 ns an index beside a flat pass, PERF.md section 7);
    an EMPTY tail plane (a tail repeats rows, which a set cannot take,
    and is what makes the index count large); and a table large enough
    for its index count (TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX).  What the
    step adds of its own: the plan's presence and the table's place on
    the MXU head (TrainStep._touched_rows_tables)."""
    return (
        columns in DICT_SCATTER_COLUMNS
        and cap_t == 0
        and table_rows * padded_columns(columns)
        >= TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX * cap_u
    )


def dict_scatter_plan(plan: dict, table_size: int, lane_select) -> dict:
    """What dict_cold_grads needs of a dictionary-wire batch's
    ``cold_plan`` (expand_dict_wire), computed once a batch and shared
    by its tables, as the sentinel-coded key plane is for the per-slot
    form.  Over the M = B * max_nnz padded positions, flat:

    ``seg`` [M]: the dictionary entry a position's gradient is summed
    into, ``ci`` at the position's running count of dictionary
    occurrences (one more monotone_take beside the one that builds the
    key plane), and cap(cu), out of range, for every other position.

    ``order`` [cap(ct)]: the padded position of the j-th tail entry.
    ``tail_idx`` rises with the position, so that is the j-th smallest
    position under ``is_tail``: one single-operand sort, no key is
    sorted.  Beyond the real tail it reads M.

    ``rows`` [cap(cu) + cap(ct)]: the table row of every dictionary
    entry, then of every tail entry, with the capacity padding of both
    planes (beyond ``n_dict``; beyond the count of tail positions)
    coded as ``table_size``, the cold sentinel that a drop-mode scatter
    leaves out (TrainStep._cold_keys_eff)."""
    take = functools.partial(monotone_take, lane_select=lane_select)
    cu, ct = plan["cu"], plan["ct"]
    is_dict, is_tail = plan["is_dict"], plan["is_tail"]
    m, cap_u, cap_t = is_dict.shape[0], cu.shape[0], ct.shape[0]
    seg = jnp.where(is_dict, take(plan["di_idx"], plan["ci"]), cap_u)
    position = jnp.arange(m, dtype=jnp.int32)
    order = jnp.sort(jnp.where(is_tail, position, m))[:cap_t]
    n_tail = jnp.sum(is_tail.astype(jnp.int32))
    sentinel = jnp.int32(table_size)
    rows = jnp.concatenate([
        jnp.where(
            jnp.arange(cap_u, dtype=jnp.int32) < plan["n_dict"], cu, sentinel
        ),
        jnp.where(
            jnp.arange(cap_t, dtype=jnp.int32) < n_tail, ct, sentinel
        ),
    ])
    return {"seg": seg, "order": order, "rows": rows}


def dict_cold_grads(splan: dict, occ: jax.Array) -> jax.Array:
    """The cold occurrence gradients ``occ`` [M, D] of a dictionary-wire
    batch as one row per dictionary entry and per tail entry,
    [cap(cu) + cap(ct), D], in the order of ``splan["rows"]``
    (dict_scatter_plan): what the [T, D] gradient buffer is handed in
    place of a row per padded slot.

    A scatter-add pays per INDEX handed to the table on the TPU, 100 ns
    at D = 10 and 123 at D = 26 whether the slot is live or masked
    (PERF.md section 5), and a batch's dictionary and tail are a third
    of its padded slots.  So the occurrences of a dictionary key are
    summed first, into a buffer of cap(cu) rows (at most DICT_CAP: the
    compiler sorts the slots' indices itself and keeps MVM's
    [43008, 10] in VMEM), the tail's rows are picked out of ``occ`` in
    stream order by ONE row gather, and the table takes
    cap(cu) + cap(ct) indices.  Same float32 adds of the same numbers
    in another order: a dictionary entry's occurrences meet each other
    before they meet the buffer.

    Measured on a v5e (scripts/probe_cold_scatter.py, one real batch of
    each cell, alone; PERF.md section 6, PR 48), ms per padded slot /
    by this route, and the route's parts (table write + dictionary sum
    + plan + tail rows):
    MVM   [2^25, 10],  1 048 576 slots, 43 008 + 294 912 entries:
          105.4 / 52.9 (34.4 + 16.3 + 1.3 + 1.9);
    DCN   [2^24, 26],  524 288 slots, 40 960 + 131 072:
          64.1 / 30.8 (21.5 + 8.1 + 0.6 + 1.4);
    AutoInt [2^25, 16], 131 072 slots, 55 296 + 0:  13.2 / 7.1;
    FFM   [2^21, 160], 131 072 slots, 53 248 + 0:   22.1 / 17.9;
    LR    [2^28, 1],   1 572 864 slots, 53 248 + 294 912: 17.8 / 20.6.
    The table write keeps the table's price an index (101, 125, 103,
    288, 17 ns); the sum costs 15.6 ns a slot at D = 10 and 26, 11 at
    16, 21 at 160, 6.7 at D = 1.  Forms that lost, same batches: the
    sum 32 768 slots at a time under a scan (MVM 21.8, DCN 6.4 against
    8.1: no one winner), into a [D, cap(cu)] buffer (the same to
    0.1 ms: the compiler picks the layout either way), as ops/hot.py's
    one-hot scan at H = 65536 (MVM 26.1, DCN 34.9, AutoInt 7.4); the
    tail's order by a one-column scatter of positions (4.9 ms against
    the sort's 1.2); the dictionary's and the tail's rows as two table
    writes (the same).  Over a sweep of widths on a [2^21, D] table,
    per slot / route, at MVM's batch: D = 1 7.2 / 12.4, 2 82.5 / 44.9,
    4 85.3 / 45.9, 8 90.7 / 46.3, 16 109.1 / 53.2, 32 127.9 / 71.5,
    64 188.0 / 85.1, 160 38.6 / 50.6; at FFM's: 1 0.9 / 1.3,
    2 10.2 / 5.6, 4 10.7 / 5.8, 8 10.9 / 5.8, 16 12.9 / 6.7,
    32 16.1 / 8.0, 64 23.9 / 11.6.  From two columns on a slot costs
    the table 78-180 ns and the route wins by half at every width up
    to 64; one column is cheaper per slot than any route, and a
    160-column row goes either way by the batch: DICT_SCATTER_COLUMNS.
    On the probe's N(0, 1) gradients both forms stand 0.5e-6 to 1.2e-5
    of the largest sum from the sums in float64 (per slot at most
    1.2e-5, the route 5.3e-6; at the five cells' own shapes the two
    read the same to every digit printed)."""
    d = occ.shape[-1]
    cap_t = splan["order"].shape[0]
    cap_u = splan["rows"].shape[0] - cap_t
    parts = []
    if cap_u:
        parts.append(
            jnp.zeros((cap_u, d), occ.dtype)
            .at[splan["seg"]].add(occ, mode="drop")
        )
    if cap_t:
        # a position of M (capacity padding) clips to a live row, which
        # the sentinel in splan["rows"] then drops
        parts.append(jnp.take(occ, splan["order"], axis=0, mode="clip"))
    return jnp.concatenate(parts) if parts else jnp.zeros((0, d), occ.dtype)


class TrainStep:
    """Holds the compiled train/predict functions for one (model,
    optimizer, config, mesh) combination."""

    def __init__(self, model: Model, optimizer: Optimizer, cfg: Config, mesh):
        self.model = model
        self.optimizer = optimizer
        self.cfg = cfg
        self.mesh = mesh
        self._bsharding = batch_sharding(mesh)
        # More than one device: the step writes its own pull and push
        # between the batch's shards and the table's row blocks
        # (parallel/exchange.py).  Observed, like the platform below;
        # no Config field chooses it.
        self._sharded = mesh.devices.size > 1
        # Compact wire eligibility (Config.wire_mode): requires binary
        # vals (hash mode).  Slot-reading models additionally need
        # max_fields <= 255 so the u8 slots plane's clamp stays inside
        # the models' ignored range (compact_wire_np docstring).
        # A batch with numeric fields ships its field ids whatever the
        # model reads: the values are rebuilt from them
        # (values_from_numeric).
        self._ship_slots = bool(
            getattr(model, "uses_slots", True) or cfg.numeric_fields
        )
        # hot ids fit u16 with the 0xFFFF sentinel only below 2^15
        # rows (compact_wire_np docstring)
        self._hot_u16 = bool(
            cfg.hot_size_log2 and cfg.hot_size_log2 <= 15
        )
        # Per-table MXU hot opt-out (TableSpec.hot): opted-out tables
        # keep their hot-plane occurrences on plain DMA gather/scatter.
        self._mxu_hot = {spec.name: spec.hot for spec in model.tables()}
        # Bytes of one row of every table, and of one row of every
        # opted-out table, of which there are _plain_hot_tables: what a
        # slot moves (_book_wire).
        self._row_bytes = sum(4 * spec.dim for spec in model.tables())
        plain = [spec for spec in model.tables() if not spec.hot]
        self._plain_hot_tables = len(plain)
        self._plain_hot_row_bytes = sum(4 * spec.dim for spec in plain)
        # tables whose cold rows dict_cold_rows lays out by row gathers
        self._row_layout_tables = sum(
            spec.dim >= ROW_LAYOUT_MIN_COLUMNS for spec in model.tables()
        )
        # The hot-inner/opt-out conflict only exists when the hot inner
        # actually RUNS — update_mode must be 'sequential'.  In dense or
        # sparse mode sequential_inner is an unused knob (ffm + dense +
        # inner='hot' is a legal Config), so rejecting it at build was a
        # false failure (ADVICE round-5 low #2).
        if cfg.update_mode == "sequential" and (
            cfg.sequential_inner == "hot"
        ) and not all(
            self._mxu_hot.values()
        ):
            opted_out = [n for n, v in self._mxu_hot.items() if not v]
            raise ValueError(
                "sequential_inner='hot' carries every table's head in "
                f"the scan; model {model.name!r} opts table(s) "
                f"{opted_out} out of the MXU hot path (TableSpec.hot)"
            )
        compact_ok = cfg.hash_mode and not (
            self._ship_slots and cfg.max_fields > 255
        )
        if cfg.wire_mode == "compact" and not compact_ok:
            raise ValueError(
                "wire_mode='compact' requires hash_mode (binary vals) "
                "and, for slot-reading models, max_fields <= 255; model "
                f"{model.name!r} / hash_mode={cfg.hash_mode} / "
                f"max_fields={cfg.max_fields} does not qualify"
            )
        self.compact_wire = cfg.wire_mode != "full" and compact_ok
        self._compact_validated = False
        # the longest each flat plane of the dictionary wire has been
        # shipped at (_settle_planes)
        self._plane_lengths: dict[tuple[int, str], int] = {}
        self._plane_lengths_lock = threading.Lock()
        # Hot-path implementation (ops/hot.py; Config.hot_impl).  On the
        # TPU "auto" stays "auto": each direction takes the cheaper form
        # for the table's width, the one-hot MXU scan under
        # hot.PLAIN_GATHER_MIN_COLUMNS columns (hot.gather_form) and
        # under hot.PLAIN_SCATTER_MIN_COLUMNS (hot.scatter_form), plain
        # indexing of the [H, D] slice from there up.  Elsewhere gather +
        # scatter-add: the MXU trick measured 3.3x SLOWER than the
        # gather on the CPU backend (docs/PERF.md "Wire format and
        # compaction").
        platform = str(self.mesh.devices.ravel()[0].platform)
        self._hot_impl = (
            cfg.hot_impl
            if cfg.hot_impl != "auto" or platform == "tpu"
            else "seg"
        )
        # tables on the head whose hot slots hot_gather reads by plain
        # indexing, and by the scan; and the same of hot_scatter's sums
        # (_book_wire)
        head = [spec.dim for spec in model.tables() if spec.hot]
        forms = [gather_form(d, self._hot_impl) for d in head]
        self._hot_plain_tables = forms.count("seg")
        self._hot_scan_tables = forms.count("mxu")
        forms = [scatter_form(d, self._hot_impl) for d in head]
        self._hot_scatter_plain_tables = forms.count("seg")
        self._hot_scatter_scan_tables = forms.count("mxu")
        # In-window lane shuffle of the dictionary-wire decode
        # (ops/window.py): Mosaic's one-vreg dynamic_gather on the TPU,
        # the plain minor-axis gather elsewhere.
        self._lane_select = (
            lane_select_tpu if platform == "tpu" else lane_select_xla
        )
        # Window-end form for the hot sequential inner
        # (Config.hot_windowend): the dense [T, D] cold-tail pass is
        # fine while tables are small; from table_size_log2 >= 24 the
        # transient would dwarf the update itself, so auto routes
        # through the consolidated touched-rows update.
        self._windowend = (
            cfg.hot_windowend
            if cfg.hot_windowend != "auto"
            else ("sparse" if cfg.table_size_log2 >= 24 else "dense")
        )
        # Dictionary-wire eligibility (Config.wire_dedup; io/compact.py):
        # host-side batch compaction needs the compact-wire invariants
        # PLUS single process + single-device mesh (the dictionary and
        # flat occurrence streams have no batch-axis sharding), u8
        # per-row counts, and hot ids that fit the tiered encoding.
        kh = cfg.hot_nnz if cfg.hot_size else 0
        dict_ok = (
            compact_ok
            and jax.process_count() == 1
            and self.mesh.devices.size == 1
            and cfg.max_nnz <= 255
            and kh <= 255
            and (not cfg.hot_size_log2 or cfg.hot_size_log2 <= 16)
        )
        if cfg.wire_dedup == "on" and not dict_ok:
            raise ValueError(
                "wire_dedup='on' requires the compact-wire invariants "
                "(hash_mode; max_fields <= 255 for slot models), a "
                "single-process single-device mesh, max_nnz/hot_nnz "
                "<= 255, and hot_size_log2 <= 16"
            )
        self.dict_wire = (
            cfg.wire_mode != "full"
            and cfg.wire_dedup != "off"
            and dict_ok
        )
        # Whether the train program gathers the cold rows of a WHOLE
        # batch (and so reads a dictionary-wire batch's cold_plan,
        # _cold_rows) or of scan slices, which go without it
        # (_interleaved_slices): sparse mode ignores microbatch, the hot
        # sequential inner gathers at its window's start.
        self._whole_batch_gather = (
            cfg.microbatch == 1
            or cfg.update_mode == "sparse"
            or (
                cfg.update_mode == "sequential"
                and cfg.sequential_inner == "hot"
            )
        )
        # Tables whose cold gradients leave a dictionary-wire batch
        # through its dictionary (_scatter_local_grads reads the plan of
        # a WHOLE batch of the dense update, as above; the touched-rows
        # updates and the hot inner's window end have scatters of their
        # own): those of DICT_SCATTER_COLUMNS columns (_book_wire)
        whole_batch_scatter = cfg.microbatch == 1 and not (
            cfg.update_mode == "sparse"
            or (
                cfg.update_mode == "sequential"
                and cfg.sequential_inner == "sparse"
            )
        )
        self._dict_scatter_tables = whole_batch_scatter * sum(
            spec.dim in DICT_SCATTER_COLUMNS for spec in model.tables()
        )
        # elements a step's whole-array optimizer passes run on the flat
        # view (_optimizer_pass: the tables of one column)
        self._flat_pass_elements = self._count_flat_pass_elements()
        # and on the layout the chip keeps the state in (_optimizer_pass:
        # the dense update's pass over a table resident_pass_selects
        # names, on one device), a table: its elements
        one_pass_a_step = not self._sharded and not (
            cfg.update_mode == "sparse"
            or (
                cfg.update_mode == "sequential"
                and (cfg.microbatch > 1 or cfg.sequential_inner == "sparse")
            )
        )
        self._resident_pass_tables = {
            spec.name: cfg.table_size * spec.dim
            for spec in model.tables()
            if one_pass_a_step and resident_pass_selects(spec.dim)
        }
        # what a family with replicated dense parameters holds and does
        # a step, from shapes (_book_wire): the bytes of its dense
        # arrays, and the operations of its products with them, forward
        # 2 B k n each and twice that backward (0 and 0 for a family
        # whose parameters are all table rows)
        owns_dense = hasattr(model, "dense_init")
        dense_shapes = (
            jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
            if owns_dense else {}
        )
        self._dense_param_bytes = sum(
            a.size * a.dtype.itemsize for a in dense_shapes.values()
        )
        self._dense_matmul_flops = 6 * cfg.batch_size * sum(
            k * n for k, n in (model.dense_matmuls() if owns_dense else [])
        )
        # what else the family wants booked of its dense half, from
        # shapes (Model.dense_counters: xDeepFM's slice of the CIN, AutoInt's
        # attention operations, score bytes and slice)
        self._dense_counters: dict[str, int] = (
            model.dense_counters(cfg.batch_size) if owns_dense else {}
        )
        # Hierarchical parameter store (Config.store_mode; store/):
        # under 'tiered' the table state is the store's hot tier + host
        # cold rows, the wire is the store's refs/miss format (the
        # compact/dict wires encode raw table keys, which the tiered
        # step never sees), and train/predict dispatch through the
        # store's hot+miss jits (store/hot.py).
        self.store = None
        if cfg.store_mode == "tiered":
            if jax.process_count() > 1:
                raise ValueError(
                    "store_mode='tiered' is single-process for now: the "
                    "cold row store is host-local (multi-host would "
                    "need a sharded cold tier — docs/STORE.md)"
                )
            from xflow_tpu.store.tiered import TieredStore

            self.store = TieredStore(model, optimizer, cfg, mesh)
            self.compact_wire = False
            self.dict_wire = False
        # Observability hook (obs/__init__.py): the trainer swaps in a
        # live Obs; the default NULL_OBS makes every span a shared no-op
        # object, so direct users (serve/engine.py, chip_smoke.py, the
        # probes under scripts/) pay nothing.
        self.obs = NULL_OBS
        self.train = jax.jit(self._train_impl, donate_argnums=0)
        self.predict = jax.jit(self._predict_impl)
        if self.store is not None:
            # tiered predict consumes the refs/miss wire, not key
            # planes — rebind AFTER the plain jit binding above so the
            # analysis pass still discovers _predict_impl as an entry
            self.predict = self.store.hot.predict

    # -- helpers -----------------------------------------------------------

    def put_batch(self, batch, predict: bool = False) -> BatchArrays:
        """Host->device transfer, booked as the 'h2d' phase; accepts a
        Batch or a pre-compacted CompactBatch (packed-cache v2
        records).  Under trainer._transfer_ahead this runs on a worker
        thread and the seconds land in the epoch record's overlapped
        dict; called inline (multi-host, eval) they are
        main-thread-exclusive.  ``predict`` (eval/serving callers)
        matters only to the tiered store: predict misses ship the
        param plane alone — the optimizer slots never score, and the
        staging ring is off there, so the saved fetch+transfer is
        serial time (store/tiered.py).  The dense wire ignores it."""
        with self.obs.phase("h2d"):
            return self._put_batch_impl(batch, predict=predict)

    @property
    def wire_format(self) -> str:
        return (
            "tiered" if self.store is not None
            else "dict" if self.dict_wire
            else "compact" if self.compact_wire
            else "full"
        )

    def _count_flat_pass_elements(self) -> int:
        """Elements that one train step's whole-array optimizer passes
        (_optimizer_pass) run on the flat view, from shapes: every table
        of one column, once per pass the update mode makes over it.
        Dense mode makes one pass a step; the sequential dense inner one
        a slice; the hot sequential inner one a slice over the [H, 1]
        heads and, where the window ends dense, one over the tables; the
        touched-rows modes none."""
        cfg = self.cfg
        one_column = sum(spec.dim == 1 for spec in self.model.tables())
        sequential = cfg.update_mode == "sequential"
        if cfg.update_mode == "sparse" or (
            sequential and cfg.sequential_inner == "sparse"
        ):
            return 0
        slices = cfg.microbatch if sequential else 1
        if slices > 1 and cfg.sequential_inner == "hot":
            return one_column * (
                cfg.hot_size * slices
                + cfg.table_size * (self._windowend == "dense")
            )
        return one_column * cfg.table_size * slices

    def _book_wire(
        self, nbytes: int, examples: int, cb=None, cold_slots: int = 0,
        slots_bytes: int = 0, hot_slots: int = 0,
        plane_caps: tuple[int, int] | None = None,
        values_bytes: int = 0,
    ) -> None:
        """Wire accounting counters behind the trainer's per-epoch
        ``wire`` metrics row (obs/schema.py): bytes that crossed the
        link, examples they carried, how many of the bytes were the
        planes of field ids (``slots_bytes``: _SLOT_PLANES, shipped for
        a model that reads them), how many the plane of the numeric
        fields' values (``values_bytes``: _VALUE_PLANES; booked only where
        one shipped, so the row of a batch without numeric fields is what
        it was), and — dict wire — the cold
        occurrence/unique-touch compaction the host performed.  Beside
        them, from shapes, what the batch asks of the [T, D] tables:
        its ``cold_slots`` padded cold slots (B * max_nnz) and the
        indices its cold gather hands the table, which are the
        capacities of the dictionary and the tail where the step reads
        a dictionary-wire batch's ``cold_plan`` (_cold_rows) and the
        padded slots everywhere else.  (The batch's OWN capacities: a
        batch that _settle_planes lengthened ships, and gathers, up to
        a granule more of padding a plane.)  ``table_scatter_indices``
        is the way back, summed over the tables: the indices the cold
        scatter-adds hand the [T, D] gradient buffers, the same two
        capacities for a table whose gradients leave through the
        dictionary (dict_cold_grads: a whole dictionary-wire batch of
        the dense update, a table whose width is in DICT_SCATTER_COLUMNS)
        and the padded slots for every other; a table that gets no
        buffer is left out of it and counted by ``touched_rows_indices``:
        the indices the dense update hands _apply_touched_rows, summed
        over the tables of _touched_rows_tables, from ``plane_caps``, the
        dictionary's and the tail's capacities AS SHIPPED (what the
        traced program sees: _settle_planes may have lengthened them);
        0 where no table is selected.  And what those slots move,
        in bytes of table rows: ``gather_row_bytes``, every index the
        step's gathers hand a [T, D] table times that table's row, and
        ``scatter_row_bytes``, a row read and a row written for every
        padded slot whose gradient its scatter-adds owe a [T, D] gradient
        buffer (the work asked, whichever form does it).
        Both count the ``hot_slots`` (B * hot_nnz) of a table that opted
        out of the MXU head (TableSpec.hot=False), whose hot occurrences
        are plain table rows, and leave the head's own traffic out;
        ``plain_hot_slots`` is those slots, a table: 0 where every table
        rides the head.  The slots that DO ride it are booked by the form
        hot_gather reads them in, a table (ops/hot.py::gather_form, from
        the table's width): ``hot_plain_slots`` by plain indexing of the
        [H, D] slice, ``hot_scan_slots`` by the one-hot scan; and by the
        form hot_scatter sums their gradients in (scatter_form, its own
        constant): ``hot_scatter_plain_slots`` / ``hot_scatter_scan_slots``.
        A padded slot counts like a live one (the gather reads row 0 for
        it; the scatter-add drops it).  Where the
        step reads the ``cold_plan``, ``cold_row_layout_slots`` is the
        padded cold slots of every table wide enough for dict_cold_rows
        to lay its rows out by row gathers: 0 where every table goes
        column by column.  ``flat_pass_elements`` is what the step's
        whole-array optimizer passes ran on the flat view
        (_count_flat_pass_elements): 0 where no table has one column;
        ``resident_pass_elements`` the elements of the tables whose
        dense pass is held to the layout the chip keeps them in
        (resident_pass_selects; a table the touched-rows update took
        makes no pass): 335 544 320 for FFM's v [2^21, 160], 0 for
        every other measured configuration.
        A family that owns replicated dense parameters also books, from
        shapes, ``dense.param_bytes`` (the bytes of its dense arrays)
        and ``dense.matmul_flops`` (6 B k n for every [B, k] x [k, n]
        product with one of them, Model.dense_matmuls: forward and the
        two backward products); a family without books neither.  What
        else a family hands the step (Model.dense_counters) is booked
        beside them under the family's own names: xDeepFM's
        ``dense.cin_slice_rows``, the examples a slice of its CIN holds
        the pair tensor for; AutoInt's ``dense.attn_flops``,
        ``dense.attn_score_bytes`` and ``dense.attn_slice_rows``."""
        if self._dense_param_bytes:
            self.obs.counter("dense.param_bytes", self._dense_param_bytes)
            self.obs.counter("dense.matmul_flops", self._dense_matmul_flops)
        for name, value in self._dense_counters.items():
            self.obs.counter(name, value)
        self.obs.counter("wire.bytes", nbytes)
        self.obs.counter("wire.examples", examples)
        self.obs.counter("wire.batches")
        self.obs.counter("wire.slots_bytes", slots_bytes)
        if values_bytes:
            self.obs.counter("wire.values_bytes", values_bytes)
        if cb is not None:
            self.obs.counter("wire.cold_occ", cb.n_cold)
            self.obs.counter("wire.cold_touched", cb.cold_touched)
        if cold_slots:
            through_dict = cb is not None and self._whole_batch_gather
            indices = len(cb.cu) + len(cb.ct) if through_dict else cold_slots
            self.obs.counter("wire.cold_slots", cold_slots)
            self.obs.counter("wire.table_gather_indices", indices)
            on_route = self._dict_scatter_tables if through_dict else 0
            touched_names = self._touched_rows_names(
                *plane_caps, hot_slots > 0
            ) if on_route and plane_caps else frozenset()
            touched = len(touched_names)
            self.obs.counter(
                "wire.table_scatter_indices",
                (on_route - touched) * indices
                + (len(self._mxu_hot) - on_route) * cold_slots,
            )
            self.obs.counter(
                "wire.touched_rows_indices", touched * sum(plane_caps or ())
            )
            if through_dict:
                self.obs.counter(
                    "wire.cold_row_layout_slots",
                    cold_slots * self._row_layout_tables,
                )
            self.obs.counter(
                "wire.flat_pass_elements", self._flat_pass_elements
            )
            self.obs.counter(
                "wire.resident_pass_elements",
                sum(
                    elements
                    for name, elements in self._resident_pass_tables.items()
                    if name not in touched_names
                ),
            )
            plain = hot_slots * self._plain_hot_row_bytes
            self.obs.counter(
                "wire.plain_hot_slots", hot_slots * self._plain_hot_tables
            )
            self.obs.counter(
                "wire.hot_plain_slots", hot_slots * self._hot_plain_tables
            )
            self.obs.counter(
                "wire.hot_scan_slots", hot_slots * self._hot_scan_tables
            )
            self.obs.counter(
                "wire.hot_scatter_plain_slots",
                hot_slots * self._hot_scatter_plain_tables,
            )
            self.obs.counter(
                "wire.hot_scatter_scan_slots",
                hot_slots * self._hot_scatter_scan_tables,
            )
            self.obs.counter(
                "wire.gather_row_bytes", indices * self._row_bytes + plain
            )
            self.obs.counter(
                "wire.scatter_row_bytes",
                2 * (cold_slots * self._row_bytes + plain),
            )

    def _settle_planes(self, wire: dict) -> dict:
        """Zero-pad each flat plane of a dictionary-wire batch up to the
        longest this step has shipped it at.  A plane's length is its
        content rounded up to a granule (io/compact.py::plane_cap), and
        a count that sits on a granule's edge flips between two lengths
        from batch to batch: two train programs, each minutes of
        compiling at a wide table (the tail plane of the benchmark's MVM
        cell: 262 700 +- 900 entries on a 262 144 edge; PERF.md section
        6, PR 32).  Entries beyond a plane's count are never used (the
        decode walks the per-row counts, expand_dict_wire; the cold-row
        gather reads the table's row 0 for a padded dictionary or tail
        entry and no occurrence points at it), so a longer plane is the
        same batch; once the longer form has been met every batch takes
        it, and the step compiles for it alone."""
        rows = len(wire["cw_cc"])  # a batch of another size: shapes of its own
        with self._plane_lengths_lock:
            for name in _CAPACITY_PLANES & wire.keys():
                plane = wire[name]
                longest = self._plane_lengths.get((rows, name), 0)
                if len(plane) < longest:
                    wire[name] = np.pad(
                        plane,
                        [(0, longest - len(plane))] + [(0, 0)] * (plane.ndim - 1),
                    )
                else:
                    self._plane_lengths[rows, name] = len(plane)
        return wire

    def _dict_geometry_ok(self, batch) -> bool:
        """A batch rides the dict wire only at the loader geometry the
        decode is traced for; other widths (external predict batches)
        keep the plain wire."""
        cfg = self.cfg
        kh = cfg.hot_nnz if cfg.hot_size else 0
        return batch.max_nnz == cfg.max_nnz and batch.hot_nnz == kh

    def precompact(self, batch):
        """Host dictionary compaction off the consumer thread: the
        CompactBatch ``put_batch`` would otherwise build inline, or the
        batch unchanged when the dict wire (or this batch's geometry)
        doesn't apply.  The input fan-out's stream workers
        (io/fanout.py) run this per batch so compaction parallelizes
        across N streams instead of serializing on the staging ring —
        put_batch on the result is a plane collection plus the h2d
        transfer.  Deterministic: the compacted planes are exactly the
        inline path's, so fan-out training stays bitwise-identical."""
        from xflow_tpu.io.compact import CompactBatch

        if (
            isinstance(batch, CompactBatch)
            or not self.dict_wire
            or self.store is not None
            or not self._dict_geometry_ok(batch)
        ):
            return batch
        cb = CompactBatch.from_batch(
            batch, self.cfg.table_size, self.cfg.hot_size,
            # the put_batch latch: racing streams at worst BOTH validate
            # their first batch — extra checking (xf: ignore[XF008])
            check=not self._compact_validated,
            numeric_fields=self.cfg.numeric_fields,
        )
        self._compact_validated = True  # same latch; xf: ignore[XF008]
        return cb

    def host_wire_np(self, batch, check: bool = False):
        """The host half of put_batch: the numpy planes that cross the
        link for ``batch`` under this step's wire format, plus the
        CompactBatch when the dict wire ran (None otherwise).  Shared
        with the serving put (serve/engine.py::_put_packed) and with
        benchmarks/aot_memory.py, so the planes they ship or size are by
        construction exactly the training feed's."""
        from xflow_tpu.io.compact import CompactBatch

        if isinstance(batch, CompactBatch):
            # pre-compacted (packed-cache v2 records): plane collection
            # only — zero per-batch host work
            if self.dict_wire and self._dict_geometry_ok(batch):
                wire = batch.wire(self._ship_slots)
                return self._settle_planes(wire), batch
            batch = batch.expand()
        if self.dict_wire and self._dict_geometry_ok(batch):
            cb = CompactBatch.from_batch(
                batch, self.cfg.table_size, self.cfg.hot_size,
                check=check, numeric_fields=self.cfg.numeric_fields,
            )
            return self._settle_planes(cb.wire(self._ship_slots)), cb
        if self.compact_wire:
            return compact_wire_np(
                _checked(batch, check, self.cfg.numeric_fields),
                ship_slots=self._ship_slots,
                hot_u16=self._hot_u16,
                numeric_fields=self.cfg.numeric_fields,
            ), None
        wire = {
            "keys": batch.keys, "slots": batch.slots,
            "vals": batch.vals, "mask": batch.mask,
            "labels": batch.labels, "weights": batch.weights,
        }
        if batch.hot_nnz:
            wire.update({
                "hot_keys": batch.hot_keys,
                "hot_slots": batch.hot_slots,
                "hot_vals": batch.hot_vals,
                "hot_mask": batch.hot_mask,
            })
        return wire, None

    def _put_batch_tiered(self, batch, predict: bool = False) -> BatchArrays:
        """Tiered-store staging (Config.store_mode): flush the previous
        step's miss write-back (read-your-writes — the next plan's
        cold-fetch must see it), resolve this batch's keys through the
        hot map, fetch miss rows from the host cold store, and ship
        refs + miss blocks.  The plan stays armed on the store until
        dispatch_train pairs it with the step's miss output."""
        from xflow_tpu.io.compact import CompactBatch

        if isinstance(batch, CompactBatch):
            batch = batch.expand()
        store = self.store
        store.complete_pending()
        wire, plan = store.plan_batch(
            batch, obs=self.obs, param_only=predict
        )
        self._book_wire(
            sum(int(v.nbytes) for v in wire.values())
            + plan.miss_nbytes,
            batch.num_real(),
        )
        # one direct host->device transfer per plane (a jnp.asarray
        # hop first would commit to the default device and pay a
        # second device-to-device reshard — on a path where the
        # staging ring is pinned off, that cost is fully serial)
        arrays = {
            k: jax.device_put(v, self._bsharding)
            for k, v in wire.items()
        }
        rep = replicated(self.mesh)
        arrays["miss"] = {
            tname: {
                aname: jax.device_put(a, rep)
                for aname, a in arrs.items()
            }
            for tname, arrs in plan.miss_rows.items()
        }
        store.stage(arrays, plan)
        return arrays

    def _put_batch_impl(self, batch, predict: bool = False) -> BatchArrays:
        if self.store is not None:
            return self._put_batch_tiered(batch, predict=predict)
        wire, cb = self.host_wire_np(
            # one-way idempotent latch: racing transfer-ahead workers
            # can at worst BOTH run the first-batch validation — extra
            # checking, never missed checking (xf: ignore[XF008])
            batch, check=not self._compact_validated
        )
        self._compact_validated = True  # same latch; xf: ignore[XF008]
        self._book_wire(
            sum(int(v.nbytes) for v in wire.values()),
            batch.num_real(),
            cb=cb,
            plane_caps=(
                (len(wire["cw_cu"]), len(wire["cw_ct"]))
                if cb is not None else None
            ),
            cold_slots=batch.batch_size * batch.max_nnz,
            hot_slots=batch.batch_size * batch.hot_nnz,
            slots_bytes=sum(
                int(v.nbytes) for k, v in wire.items() if k in _SLOT_PLANES
            ),
            values_bytes=sum(
                int(v.nbytes) for k, v in wire.items() if k in _VALUE_PLANES
            ),
        )
        arrays = {k: jnp.asarray(v) for k, v in wire.items()}
        if jax.process_count() > 1:
            # Each host loaded its own shard subset (trainer._my_shards);
            # assemble a global array from per-process local batches.
            from jax.experimental import multihost_utils

            return {
                k: multihost_utils.host_local_array_to_global_array(
                    v, self.mesh, self._bsharding.spec
                )
                for k, v in arrays.items()
            }
        return {
            k: jax.device_put(v, self._bsharding) for k, v in arrays.items()
        }

    def dispatch_train(
        self, state: State, arrays: BatchArrays
    ) -> tuple[State, dict[str, jax.Array]]:
        """The jitted train call under the 'dispatch' phase.  Dispatch
        returns as soon as XLA enqueues the program; time the device
        spends actually computing surfaces later as 'device_block' (the
        epoch-end metrics fetch) — the dispatch/block split is what
        tells an input-bound run from a compute-bound one."""
        with self.obs.phase("dispatch"):
            if self.store is not None:
                return self._dispatch_tiered(state, arrays)
            if self._sharded and self.obs.enabled:
                rows = next(iter(arrays.values())).shape[0]
                self.obs.counter("exchange.bytes", self.exchange_bytes(rows))
            return self.train(state, arrays)

    def exchange_bytes(self, batch_rows: int) -> int:
        """Bytes the exchange's collectives hand over in one dense train
        step of ``batch_rows`` examples at the loader's geometry, from
        shapes (parallel/exchange.py): the all-gathered key planes,
        once for the pull and once for the push; per table the pulled
        rows (the reduce-scatter's operand) and the pushed gradient
        rows (the all-gather's result); the head's rows from every chip
        and its summed gradient.  Payload, not link traffic: a chip
        sends and receives about (n - 1) / n of it.  0 on one device."""
        if not self._sharded:
            return 0
        cfg = self.cfg
        n = self.mesh.devices.size
        kh = cfg.hot_nnz if cfg.hot_size else 0
        # a table out of the MXU head (TableSpec.hot=False) moves its
        # hot slots, and their key plane, like cold ones
        dma_hot = kh if not all(self._mxu_hot.values()) else 0
        total = 2 * batch_rows * (cfg.max_nnz + dma_hot) * 4
        for spec in self.model.tables():
            if kh and self._mxu_hot[spec.name]:
                slots = batch_rows * cfg.max_nnz
                total += (n + 1) * cfg.hot_size * spec.dim * 4
            else:
                slots = batch_rows * (cfg.max_nnz + kh)
            total += 2 * slots * spec.dim * 4
        return total

    def op_scopes(self, state: State, arrays: BatchArrays) -> list[list[str]]:
        """``[name, type, scope]`` for every instruction of the train
        program compiled for these shapes: the map from what a profiler
        calls a device operation to the ``xf.*`` scope the source gave
        it (docs/OBSERVABILITY.md "Scopes and spans").  ``scope`` is the
        innermost ``xf.<name>`` of the instruction's ``op_name``
        (``scope_of``), ``""`` where the path has none; on a mesh of
        more than one device a collective that the compiler left
        without one is ``xf.exchange``'s (the rule is in the loop
        below).  Instructions inside a fusion, parameters,
        constants and tuple plumbing never run on their own and are
        left out.

        The text is the running program's own: lowered from abstract
        shapes through ``self.train``, whose cache hands back the
        executable that these shapes already run: nothing is held or
        donated, and nothing compiles unless the shapes have not run
        yet.  JAX's persistent compilation
        cache leaves source metadata out of its key by default, so a
        loaded program could carry an older source's scopes, or none:
        from here on this process keys its compiles WITH their metadata
        (``compile_cache.key_by_source``; the trainer calls it before
        its first compile whenever its Obs is live).  A program this
        process compiled or loaded before that call keeps the scopes it
        came with."""
        key_by_source()
        text = self.train.lower(
            abstract_like(state), abstract_like(arrays)
        ).compile().as_text()
        fused = set(_HLO_FUSED_RE.findall(text))
        # computations that hold a collective (see the exchange's rule
        # below)
        holds_collective = set()
        for line in text.splitlines():
            head = _HLO_COMPUTATION_RE.match(line)
            if head:
                current = head.group(1)
            elif _HLO_COLLECTIVE_RE.search(line):
                holds_collective.add(current)
        rows = []
        inside_fusion = False
        for line in text.splitlines():
            head = _HLO_COMPUTATION_RE.match(line)
            if head:
                inside_fusion = head.group(1) in fused
                continue
            m = None if inside_fusion else _HLO_INSTRUCTION_RE.match(line)
            if not m or _HLO_NEVER_RUNS_RE.search(line):
                continue
            path = _HLO_OP_NAME_RE.search(line)
            name = scope_of(path.group(1)) if path else ""
            if not name and self._sharded:
                # On a mesh every collective of the step is the
                # exchange's, bar the all-reduces of a few scalars that
                # the partitioner adds, and the TPU's compiler rewrites
                # them without their metadata (a reduce-scatter becomes
                # an all-reduce and a slice, or a fusion of its own; an
                # asynchronous one a start/done pair).  So a collective
                # with no scope, or an instruction that calls a
                # computation holding one, is booked to the exchange.
                callee = _HLO_CALLS_RE.search(line)
                if _HLO_COLLECTIVE_RE.search(line) or (
                    callee and callee.group(1) in holds_collective
                ):
                    name = exchange.SCOPE
            rows.append([m.group("op"), m.group("type") or "", name])
        return rows

    def _dispatch_tiered(
        self, state: State, arrays: BatchArrays
    ) -> tuple[State, dict[str, jax.Array]]:
        """Tiered dispatch: pair THESE arrays' staged plan (identity-
        keyed — a foreign arrays dict raises) with the hot+miss jit and
        defer the miss write-back (completed before the next plan —
        store/tiered.py ordering)."""
        plan = self.store.take_staged(arrays)
        new_state, miss_out, metrics = self.store.hot.train(state, arrays)
        self.store.defer_complete(plan, miss_out)
        return new_state, metrics

    @jax.named_scope("xf.wire_decode")
    def _expand_wire(self, batch: BatchArrays) -> BatchArrays:
        """Inverse of batch_to_compact, inside the jitted step: padding
        is key == -1; real entries have val = mask = 1 (hash mode; with a
        values plane ``nvals`` the numeric fields' entries take its
        numbers);
        slots widen from the u8 plane when the model reads them, else
        reconstruct as zeros.  Dictionary-wire batches (cw_* planes,
        Config.wire_dedup) decode through expand_dict_wire instead."""
        if "cw_cc" in batch:
            return expand_dict_wire(self.cfg, self._lane_select, batch)
        if "ckeys" not in batch:
            return batch
        ckeys = batch["ckeys"]
        mask = (ckeys >= 0).astype(jnp.float32)
        out = {
            "keys": jnp.maximum(ckeys, 0),
            "slots": (
                batch["slots_u8"].astype(jnp.int32)
                if "slots_u8" in batch
                else jnp.zeros_like(ckeys)
            ),
            "vals": mask,
            "mask": mask,
            "labels": batch["labels_u8"].astype(jnp.float32),
            "weights": batch["weights_u8"].astype(jnp.float32),
        }
        if "hot_ckeys_u16" in batch:
            # u16 plane: 0xFFFF is the pad sentinel (compact_wire_np;
            # legal only for H <= 2^15, where ids cannot reach it) —
            # normalize to the int32 -1 convention and share the tail
            h16 = batch["hot_ckeys_u16"].astype(jnp.int32)
            hot = jnp.where(h16 == 0xFFFF, -1, h16)
        elif "hot_ckeys" in batch:
            hot = batch["hot_ckeys"]
        else:
            hot = None
        if hot is not None:
            hmask = (hot >= 0).astype(jnp.float32)
            out["hot_keys"] = jnp.maximum(hot, 0)
            out["hot_slots"] = (
                batch["hot_slots_u8"].astype(jnp.int32)
                if "hot_slots_u8" in batch
                else jnp.zeros_like(hot)
            )
            out["hot_vals"] = hmask
            out["hot_mask"] = hmask
        if "nvals" in batch:  # numeric fields: their values (compact_wire_np)
            out["vals"] = values_from_numeric(batch["nvals"], out["slots"], mask)
            if hot is not None:
                out["hot_vals"] = values_from_numeric(
                    batch["nvals"], out["hot_slots"], hmask
                )
        return out

    def _gather_model_rows(
        self, tables: dict[str, dict[str, jax.Array]], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        """[B, K, D] parameter rows of the batch's keys, per table, hot
        section first: a local gather on one device, the exchange's
        pull on a mesh."""
        if self._sharded:
            return self._pull_model_rows(tables, batch)
        return self._gather_local_rows(tables, batch)

    def _pull_model_rows(
        self, tables: dict[str, dict[str, jax.Array]], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        """_gather_local_rows across a mesh (parallel/exchange.py): per
        table ONE all-gather of the batch's cold keys (shared by the
        tables), a gather of the whole batch's slots from this chip's
        own row block with out-of-block keys reading zeros, and ONE
        reduce-scatter that hands each chip its batch shard's rows (a
        row lives in one block, so the sum adds exact zeros to it).
        The head [0, H) is read once as a replicated block and the
        one-hot scans of hot_gather run on the chip's batch shard: no
        collective inside them."""
        from xflow_tpu.ops.hot import hot_gather

        h = self.cfg.hot_size

        def pull(block: jax.Array, idx: jax.Array) -> jax.Array:
            with jax.named_scope("xf.gather"):
                rows = block.at[idx].get(mode="fill", fill_value=0.0)
            return exchange.to_batch_shards(rows)

        def body(params: dict, keys: jax.Array, hot_keys):
            block_rows = next(iter(params.values())).shape[0]
            idx = exchange.block_index(exchange.all_batch(keys), block_rows)
            if hot_keys is None:
                return {n: pull(p, idx) for n, p in params.items()}
            b, kh = hot_keys.shape
            hot_idx = None
            out = {}
            for name, block in params.items():
                if self._mxu_hot[name]:
                    head = exchange.read_head(block, h)
                    with jax.named_scope("xf.gather"):
                        hot = hot_gather(
                            head,
                            hot_keys.reshape(-1),
                            impl=self._hot_impl,
                        ).reshape(b, kh, block.shape[-1])
                else:
                    # opted-out table (TableSpec.hot=False): its hot
                    # occurrences are ordinary table rows, pulled like
                    # the cold ones
                    if hot_idx is None:
                        hot_idx = exchange.block_index(
                            exchange.all_batch(hot_keys), block_rows
                        )
                    hot = pull(block, hot_idx)
                cold = pull(block, idx)
                with jax.named_scope("xf.gather"):
                    out[name] = jnp.concatenate([hot, cold], axis=1)
            return out

        return self._per_chip(body)(
            {name: t["param"] for name, t in tables.items()},
            batch["keys"],
            batch.get("hot_keys"),
        )

    def _per_chip(self, body):
        """``body`` run on every chip of the mesh over that chip's block
        of each argument's leading axis (the table's rows, the batch's
        examples), its results blocks of the same.  The varying-axis
        check is off: the one-hot scans of ops/hot.py start their
        carries from constants, which it refuses inside a manual
        region, and nothing differentiates through an exchange."""
        return jax.shard_map(
            body, mesh=self.mesh, in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS), check_vma=False,
        )

    @jax.named_scope("xf.gather")
    def _cold_rows(
        self, tables: dict[str, dict[str, jax.Array]], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        """[B, max_nnz, D] parameter rows of the batch's cold keys, per
        table.  A row per index either way; a whole dictionary-wire
        batch brings the shorter index list (its ``cold_plan``: the
        table is read per dictionary and tail entry, dict_cold_rows),
        every other batch has one index per padded slot.  Padding slots
        are masked out of every reduction by batch["mask"]: they read
        row 0 here and hold 0 there."""
        params = {name: t["param"] for name, t in tables.items()}
        if "cold_plan" not in batch:
            return {n: p[batch["keys"]] for n, p in params.items()}
        shape = batch["keys"].shape
        return {
            n: r.reshape(shape + r.shape[1:])
            for n, r in dict_cold_rows(
                batch["cold_plan"], params, self._lane_select
            ).items()
        }

    @jax.named_scope("xf.gather")
    def _gather_local_rows(
        self, tables: dict[str, dict[str, jax.Array]], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        cold = self._cold_rows(tables, batch)
        if "hot_keys" not in batch:
            return cold
        # Hot section: two-level one-hot MXU gather over table rows
        # [0, H) (ops/hot.py); rows for the two sections are concatenated
        # feature-axis-first so the model sees one [B, Kh+Kc, D] block
        # aligned with _model_view's concatenated slots/vals/mask.
        from xflow_tpu.ops.hot import hot_gather

        h = self.cfg.hot_size
        b, kh = batch["hot_keys"].shape
        out = {}
        for name, t in tables.items():
            d = t["param"].shape[-1]
            if self._mxu_hot[name]:
                hot = hot_gather(
                    t["param"][:h],
                    batch["hot_keys"].reshape(-1),
                    impl=self._hot_impl,
                ).reshape(b, kh, d)
            else:
                # opted-out table (TableSpec.hot=False): hot rows are
                # ordinary table rows [0, H) — plain gather; padding
                # reads row 0, masked downstream like the cold plane
                hot = t["param"][batch["hot_keys"]]
            out[name] = jnp.concatenate([hot, cold[name]], axis=1)
        return out

    @jax.named_scope("xf.wire_decode")
    def _model_view(self, batch: BatchArrays) -> BatchArrays:
        """Batch as the model sees it: hot + cold sections concatenated
        along the feature axis (models are permutation-invariant over a
        sample's features — they reduce over the feature axis)."""
        if "hot_keys" not in batch:
            return batch
        view = dict(batch)
        view["keys"] = jnp.concatenate(
            [batch["hot_keys"], batch["keys"]], axis=1
        )
        view["slots"] = jnp.concatenate(
            [batch["hot_slots"], batch["slots"]], axis=1
        )
        view["vals"] = jnp.concatenate(
            [batch["hot_vals"], batch["vals"]], axis=1
        )
        view["mask"] = jnp.concatenate(
            [batch["hot_mask"], batch["mask"]], axis=1
        )
        return view

    # -- compiled bodies ---------------------------------------------------

    @jax.named_scope("xf.forward_backward")
    def _logit(
        self, rows: dict[str, jax.Array], batch: BatchArrays, dense: dict
    ) -> jax.Array:
        if getattr(self.model, "autodiff", False):
            return self.model.logit(rows, batch, dense)
        return self.model.logit(rows, batch)

    def _forward_grads(
        self,
        tables: dict,
        dense: dict,
        batch: BatchArrays,
        num_real: jax.Array,
    ):
        """pctr + per-occurrence gradients for one (micro)batch.

        Returns (pctr, occ_grads, grad_dense_or_None); occ_grads are
        already residual-scaled and divided by the FULL batch's real
        example count, so accumulating them across microbatch slices
        reproduces the whole-batch mean-gradient semantics exactly
        (lr_worker.cc:116-118)."""
        rows = self._gather_model_rows(tables, batch)
        return self._grads_from_rows(rows, dense, batch, num_real)

    def _grads_from_rows(
        self,
        rows: dict,
        dense: dict,
        batch: BatchArrays,
        num_real: jax.Array,
    ):
        """_forward_grads with the row gather already done — the hot
        sequential inner supplies rows from the carried hot head plus
        a window-start cold pre-gather instead of a live table
        gather.  Delegates to the module-level ``grads_from_rows`` (the
        one forward/backward, shared with store/hot.py)."""
        return grads_from_rows(
            self.model, rows, dense, self._model_view(batch), num_real
        )

    def _hot_keys_eff_dma(self, batch: BatchArrays) -> jax.Array:
        """Hot-plane keys sentinel-coded for a DROP-mode scatter into
        the FULL [T, D] table (opted-out tables, TableSpec.hot=False):
        masked slots → T, out of range.  _hot_keys_eff's sentinel H is
        a real table row and only works for [H, D] buffers."""
        return jnp.where(
            batch["hot_mask"] > 0,
            batch["hot_keys"],
            jnp.int32(self.cfg.table_size),
        ).reshape(-1)

    def _cold_keys_eff(self, batch: BatchArrays) -> jax.Array:
        """Sentinel-coded flat cold keys: masked slots → T, which the
        drop-mode scatters and consolidate_plan treat as out-of-range.
        The ONE definition of the cold sentinel convention (counterpart
        of _hot_keys_eff), shared by _scatter_grads, _sparse_update and
        the hot inner's window-end pass."""
        return jnp.where(
            batch["mask"] > 0, batch["keys"], jnp.int32(self.cfg.table_size)
        ).reshape(-1)

    @jax.named_scope("xf.scatter")
    def _cold_accumulate(
        self, gbuf: jax.Array, keys_eff: jax.Array, occ: jax.Array
    ) -> jax.Array:
        """Accumulate per-occurrence cold grads [M, D] into a [T, D]
        buffer under the ONE sentinel/drop convention (pad keys carry
        index T, dropped by mode='drop').  Shared by _scatter_grads
        and the hot inner's window-end pass so the two cannot drift."""
        return gbuf.at[keys_eff].add(occ, mode="drop")

    def _scatter_grads(
        self,
        tables: dict,
        batch: BatchArrays,
        occ_grads: dict,
        gbufs: dict,
    ) -> dict:
        """Per-occurrence grads summed into the dense [T, D] buffers
        (one per table): a local scatter-add on one device, the
        exchange's push on a mesh."""
        if self._sharded:
            return self._push_grads(batch, occ_grads, gbufs)
        return self._scatter_local_grads(tables, batch, occ_grads, gbufs)

    def _push_grads(
        self, batch: BatchArrays, occ_grads: dict, gbufs: dict
    ) -> dict:
        """_scatter_local_grads across a mesh (parallel/exchange.py):
        ONE all-gather of the batch's cold keys (shared by the tables)
        and, per table, ONE of its gradient rows; every chip
        scatter-adds the whole batch's slots into its own block of the
        buffer, out-of-block keys dropped.  The head's gradient is
        summed over each chip's batch shard by hot_scatter (no
        collective inside its scan), then over the chips in ONE
        all-reduce of [H, D], and joins rows [0, H) of the block(s)
        that hold them."""
        from xflow_tpu.ops.hot import hot_scatter

        cfg = self.cfg
        kh = batch["hot_keys"].shape[1] if "hot_keys" in batch else 0
        with jax.named_scope("xf.scatter"):
            planes = {"cold": self._cold_keys_eff(batch)}
            if kh:
                planes["hot"] = self._hot_keys_eff(batch)
                if not all(self._mxu_hot.values()):
                    planes["hot_dma"] = self._hot_keys_eff_dma(batch)

        def body(gbufs: dict, planes: dict, occ_grads: dict) -> dict:
            block_rows = next(iter(gbufs.values())).shape[0]
            idx = exchange.block_index(
                exchange.all_batch(planes["cold"]), block_rows
            )
            if "hot_dma" in planes:
                hot_idx = exchange.block_index(
                    exchange.all_batch(planes["hot_dma"]), block_rows
                )
            out = {}
            for name, gbuf in gbufs.items():
                d = gbuf.shape[-1]
                occ = occ_grads[name]
                with jax.named_scope("xf.scatter"):
                    if kh:
                        hot_g = occ[:, :kh].reshape(-1, d)
                        occ = occ[:, kh:]
                    occ = occ.reshape(-1, d)
                gbuf = self._cold_accumulate(
                    gbuf, idx, exchange.all_batch(occ)
                )
                if kh and self._mxu_hot[name]:
                    ghot = exchange.sum_head(hot_scatter(
                        planes["hot"], hot_g, cfg.hot_size,
                        impl=self._hot_impl,
                    ))
                    part = exchange.head_part(ghot, block_rows)
                    with jax.named_scope("xf.scatter"):
                        gbuf = gbuf.at[: part.shape[0]].add(part)
                elif kh:
                    hot_all = exchange.all_batch(hot_g)
                    with jax.named_scope("xf.scatter"):
                        gbuf = gbuf.at[hot_idx].add(hot_all, mode="drop")
                out[name] = gbuf
            return out

        return self._per_chip(body)(gbufs, planes, occ_grads)

    @jax.named_scope("xf.scatter")
    def _scatter_local_grads(
        self,
        tables: dict,
        batch: BatchArrays,
        occ_grads: dict,
        gbufs: dict,
    ) -> dict:
        """Accumulate per-occurrence grads into dense [T, D] buffers
        (one per table): scatter-add for the cold section, two-level
        one-hot MXU matmuls for the hot section (ops/hot.py).  A table
        that ``gbufs`` holds no buffer for (_touched_rows_tables) gets
        what _touched_rows_pass takes instead: the dictionary's rows,
        their summed gradients and the head's [H, D] sum (None without
        a head), none of them written to a table-sized array."""
        cfg = self.cfg
        kh = batch["hot_keys"].shape[1] if "hot_keys" in batch else 0
        keys_eff = self._cold_keys_eff(batch)
        # a whole dictionary-wire batch hands a table of 2 to 64 columns
        # an index per dictionary and tail entry (dict_cold_grads)
        through_dict = {
            name for name, t in tables.items()
            if "cold_plan" in batch
            and t["param"].shape[-1] in DICT_SCATTER_COLUMNS
        }
        if through_dict:
            splan = dict_scatter_plan(
                batch["cold_plan"], cfg.table_size, self._lane_select
            )
        if kh:
            from xflow_tpu.ops.hot import hot_scatter

            hot_keys_eff = self._hot_keys_eff(batch)
        out = {}
        for name, table in tables.items():
            d = table["param"].shape[-1]
            occ = occ_grads[name]
            if kh:
                # hot section grads ride the MXU into a dense [H, D]
                # buffer; cold grads keep the DMA scatter path.
                hot_g = occ[:, :kh].reshape(-1, d)
                occ = occ[:, kh:]
            occ = occ.reshape(-1, d)
            if name not in gbufs:
                ghot = hot_scatter(
                    hot_keys_eff, hot_g, cfg.hot_size, impl=self._hot_impl
                ) if kh else None
                out[name] = (splan["rows"], dict_cold_grads(splan, occ), ghot)
                continue
            if name in through_dict:
                gbuf = self._cold_accumulate(
                    gbufs[name], splan["rows"], dict_cold_grads(splan, occ)
                )
            else:
                gbuf = self._cold_accumulate(gbufs[name], keys_eff, occ)
            if kh:
                if self._mxu_hot[name]:
                    ghot = hot_scatter(
                        hot_keys_eff, hot_g, cfg.hot_size,
                        impl=self._hot_impl,
                    )
                    gbuf = gbuf.at[: cfg.hot_size].add(ghot)
                else:
                    gbuf = gbuf.at[self._hot_keys_eff_dma(batch)].add(
                        hot_g, mode="drop"
                    )
            out[name] = gbuf
        return out

    def _train_impl(
        self, state: State, batch: BatchArrays
    ) -> tuple[State, dict[str, jax.Array]]:
        cfg = self.cfg
        batch = self._expand_wire(batch)
        if cfg.update_mode == "sequential" and cfg.microbatch > 1:
            return self._train_sequential(state, batch)

        tables = state["tables"]
        dense = state["dense"]
        num_real = jnp.maximum(jnp.sum(batch["weights"]), 1.0)

        # sequential with one slice degenerates to a single whole-batch
        # update; honor the configured inner so a sparse-inner run at
        # microbatch=1 doesn't silently pay a full-table dense pass.
        # The 'hot' inner deliberately does NOT route here: with one
        # slice its dispatch window IS the whole batch (per-slice head
        # update + window-end tail collapse into one whole-batch
        # update), so it falls through to the dense accumulate path
        # below — the explicit degenerate form, equivalence pinned by
        # tests/test_sequential.py::test_sequential_microbatch_one_is_dense.
        if cfg.update_mode == "sparse" or (
            cfg.update_mode == "sequential"
            and cfg.sequential_inner == "sparse"
        ):
            pctr, occ_grads, grad_dense = self._forward_grads(
                tables, dense, batch, num_real
            )
            # hot planes, when present, take _sparse_update's hybrid
            # path (dense [H, D] head update, overflow fold)
            new_tables = self._sparse_update(tables, batch, occ_grads)
            ll, cnt = self._batch_logloss(batch, pctr)
            return self._finish_step(
                state, new_tables, dense, grad_dense, ll, cnt
            )

        # -- dense mode: accumulate grads into per-table buffers, then
        # ONE optimizer pass.  Scatter-add consolidates duplicate keys;
        # the recurrence runs elementwise over the full table — no sort,
        # no row gather/scatter.  Untouched rows see g=0, for which
        # FTRL/SGD are idempotent (optim docstrings).
        # A table whose whole cold section is a dictionary of distinct
        # rows takes the recurrence on those rows and gets no buffer
        # (_touched_rows_tables: from shapes).
        touched = self._touched_rows_tables(batch)
        gbufs = self._zero_gbufs(
            {n: t for n, t in tables.items() if n not in touched}
        )
        s = cfg.microbatch
        if s == 1:
            pctr, occ_grads, grad_dense = self._forward_grads(
                tables, dense, batch, num_real
            )
            gbufs = self._scatter_grads(tables, batch, occ_grads, gbufs)
            ll, cnt = self._batch_logloss(batch, pctr)
        else:
            # Gradient accumulation (Config.microbatch): scan over batch
            # slices so every [B-slice, nnz, D] intermediate is 1/s the
            # size.  Grads are pre-divided by the FULL batch num_real, so
            # the accumulated buffers equal the single-pass ones.
            xs = _interleaved_slices(batch, s)
            gdense0 = jax.tree.map(jnp.zeros_like, dense)

            def body(carry, bslice):
                gbufs_c, gdense_c, nll_c, cnt_c = carry
                pctr_s, occ_s, gd = self._forward_grads(
                    tables, dense, bslice, num_real
                )
                gbufs_c = self._scatter_grads(
                    tables, bslice, occ_s, gbufs_c
                )
                if gd is not None:
                    gdense_c = jax.tree.map(
                        lambda a, b: a + b, gdense_c, gd
                    )
                w = bslice["weights"]
                nll_c = nll_c + logloss_sum(bslice["labels"], pctr_s, w)
                return (gbufs_c, gdense_c, nll_c, cnt_c + jnp.sum(w)), None

            zero = jnp.zeros((), jnp.float32)
            (gbufs, grad_dense, nll_sum, cnt), _ = jax.lax.scan(
                body, (gbufs, gdense0, zero, zero), xs
            )
            if not dense:
                grad_dense = None
            ll = nll_sum / jnp.maximum(cnt, 1.0)

        new_tables = {
            name: (
                self._touched_rows_pass(table, *gbufs[name])
                if name in touched
                else self._optimizer_pass(
                    table, gbufs[name], resident=not self._sharded
                )
            )
            for name, table in tables.items()
        }
        return self._finish_step(
            state, new_tables, dense, grad_dense, ll, cnt
        )

    def _touched_rows_tables(self, batch: BatchArrays) -> frozenset[str]:
        """The tables whose dense update runs on the rows this batch's
        dictionary names and on nothing else (_touched_rows_pass), a
        Python-level set from shapes the trace holds; empty for every
        batch without a ``cold_plan`` (the plain wires, a mesh,
        microbatch slices), where the traced program is the one it was.
        touched_rows_selects has the rule and
        TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX the measurements; beside
        them a table must ride the MXU head, or there is no head: an
        opted-out table's hot occurrences are table rows that repeat."""
        if "cold_plan" not in batch or self.cfg.microbatch != 1:
            return frozenset()
        plan = batch["cold_plan"]
        return self._touched_rows_names(
            plan["cu"].shape[0], plan["ct"].shape[0], "hot_keys" in batch
        )

    def _touched_rows_names(
        self, cap_u: int, cap_t: int, head: bool
    ) -> frozenset[str]:
        """_touched_rows_tables from the plane capacities themselves:
        what _book_wire counts a shipped batch by."""
        return frozenset(
            spec.name for spec in self.model.tables()
            if (self._mxu_hot[spec.name] or not head)
            and touched_rows_selects(
                self.cfg.table_size, spec.dim, cap_u, cap_t
            )
        )

    @jax.named_scope("xf.scatter")
    def _zero_gbufs(self, tables: dict) -> dict:
        """The zeroed [T, D] gradient buffers of the dense update, booked
        with the scatter that fills them: one for every table handed in,
        which is every table but those of _touched_rows_tables."""
        return {
            # the [T, D] buffer IS dense mode's design for a table of one
            # column, for a batch with a tail or without a dictionary,
            # and for a table too small for its index count
            # (touched_rows_selects; 'sparse' is the 2^28 form) — budgeted
            # in memory-budget.json, justified here (xf: ignore[XF010])
            name: jnp.zeros_like(t["param"]) for name, t in tables.items()
        }

    @jax.named_scope("xf.optimizer")
    def _optimizer_pass(
        self, table: dict, g: jax.Array, resident: bool = False
    ) -> dict:
        """The optimizer recurrence over whole arrays: the dense [T, D]
        pass, and the [H, D] head of the hot sequential inner.  Three
        arms, chosen from the arrays' shape; in each the same operations
        per element in the same order: the new state is bit for bit
        update_rows(table, g).

        A table of ONE column runs it on the flat [T] view of the same
        bytes.  The TPU lays an f32[T, 1] out in tiles of one sublane
        by 128 lanes (``{0,1:T(1,128)}``) and the flat view in whole
        8 x 128 tiles (``{0:T(1024)}``), which the scatter beside the
        pass reads already: a reshape between the two is a bitcast, and
        the pass over one-sublane tiles ran at 348 GB/s where the same
        fusion over whole tiles reads 670 (PERF.md section 6, PR 37).
        The recurrence is elementwise, so XLA would cancel the reshapes
        through it and put the fusion back on [T, 1]: the barriers on
        both sides hold the view.

        A table that the chip keeps rows-minor while the step gathers
        and scatters its rows columns-minor (resident_pass_selects:
        FFM's v [2^21, 160]) runs it with ``param``, ``n``, ``z``, the
        gradient buffer and the three results held to the resident
        layout (with_layout_constraint inside the program, as
        ops/hot.py holds its pieces; never a format on the jit's
        boundary, which an executable loaded from the persistent
        compile cache does not honour: PERF.md section 6, PR 34), so
        that XLA leaves the fusion on the bytes the state lies in:
        ``n`` and ``z`` are never relaid and the new ``param`` is
        written where it lives.  ``resident`` is the caller's word that
        the arrays ARE the program's state on one device, arguments in
        and results out: the dense update's one pass a step.  Compiled
        for a described v5e (PR 59;
        tests/test_tpu_compile.py::test_ffm_pass_runs_on_the_resident_layout_on_v5e):
        six table-sized copies -> two, the pass's results
        ``{1,0:T(8,128)}`` (2 GiB each) -> ``{0,1:T(8,128)}`` (1.25),
        the program 13.78 -> 7.65 GiB.  PR 37's view between barriers
        does not carry to 160 columns (``a.T`` pins a shape, not a
        layout: XLA gives the VIEW the padded layout, three copies stay
        and the program is refused at 17.78 GiB).  A carry of the
        sequential inners, a block under a mesh and an [H, D] head
        slice keep the arm below: no cell puts a wide table there and
        no compile has been read.

        Every other table keeps its shape, and must: a narrow table's
        rows are padded in memory (10 -> 16 columns), so its flat view
        is a copy of the state, and [T / 1024, 1024] is a trap for
        T x 1 too (reduces over the unit axis and three table-sized
        copies: CHANGES.md, PR 37)."""
        if resident and resident_pass_selects(g.shape[-1]):
            rows_minor = Layout(major_to_minor=(1, 0))

            def held(a):
                return with_layout_constraint(a, rows_minor)

            new = self.optimizer.update_rows(
                {k: held(a) for k, a in table.items()}, held(g)
            )
            return {k: held(a) for k, a in new.items()}
        if g.ndim != 2 or g.shape[-1] != 1:
            return self.optimizer.update_rows(table, g)
        rows, flat_g = jax.lax.optimization_barrier(
            ({k: a.reshape(-1) for k, a in table.items()}, g.reshape(-1))
        )
        new = jax.lax.optimization_barrier(
            self.optimizer.update_rows(rows, flat_g)
        )
        return {k: a.reshape(g.shape) for k, a in new.items()}

    @jax.named_scope("xf.metrics")
    def _batch_logloss(
        self, batch: BatchArrays, pctr: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """(mean logloss, real count) of one whole batch."""
        return (
            logloss(batch["labels"], pctr, batch["weights"]),
            jnp.sum(batch["weights"]),
        )

    def _hot_keys_eff(self, batch: BatchArrays) -> jax.Array:
        """Sentinel-coded flat hot keys: masked slots → H, which both
        the dense path's hot_scatter and the hybrid's [H, D] fold drop
        as out-of-range.  The ONE definition of the hot sentinel
        convention, shared by _scatter_grads and _sparse_update so the
        dense and hybrid update paths cannot drift."""
        return jnp.where(
            batch["hot_mask"] > 0,
            batch["hot_keys"],
            jnp.int32(self.cfg.hot_size),
        ).reshape(-1)

    @jax.named_scope("xf.optimizer")
    def _apply_touched_rows(
        self, table: dict, ukeys: jax.Array, gsum: jax.Array
    ) -> dict:
        """Gather state rows at the consolidated unique keys, run the
        optimizer recurrence, scatter the new rows back (sentinel keys
        clamp on gather and drop on scatter — ops/sparse.py).  The ONE
        touched-rows application, shared by _touched_rows_pass (the
        hybrid of _sparse_update and of the dense update of a
        dictionary-only batch), _sparse_update's opted-out variant and
        the hot inner's sparse window-end so they cannot drift.  A set
        wants DISTINCT keys: consolidated ones, or a dictionary's."""
        state_rows = {k: gather_rows(arr, ukeys) for k, arr in table.items()}
        new_rows = self.optimizer.update_rows(state_rows, gsum)
        return {
            k: scatter_rows(table[k], ukeys, new_rows[k]) for k in table
        }

    @jax.named_scope("xf.optimizer")
    def _touched_rows_pass(
        self, table: dict, rows: jax.Array, gsum: jax.Array, ghot
    ) -> dict:
        """The optimizer recurrence on the DISTINCT table rows ``rows``
        [n] with their summed gradients ``gsum`` [n, D] and, where the
        table rides the MXU head, on the head's H rows with ``ghot``
        [H, D] (None without a head); every other row is left alone.
        Shared by _sparse_update's hybrid and by the dense update of a
        dictionary-only batch (_touched_rows_tables), so the two cannot
        drift.

        Exactly once: a row below H among ``rows`` (the head's overflow
        into the cold section, io/batch.py::split_hot) has its sum
        folded into ``ghot`` (index H, out of range for the [H, D]
        buffer, drops the rest) and is coded as the sentinel T in the
        list that _apply_touched_rows takes, which clamps it on the
        gathers and drops it on the sets, as it does the capacity
        padding that arrives coded so.  Then the head's rows take ONE
        whole-array update on the [:H] slices and go back by a dynamic
        update slice (H rows, 115 KB of traffic beside a table pass).

        Against the dense pass over a zeroed [T, D] buffer this is the
        same state bit for bit wherever ``param`` is what ``(z, n)``
        give, which is every state this program produced: a cold row
        meets ONE summed gradient either way, a head row the two-term
        sum ghot + cold, which commutes, and FTRL at g = 0 keeps ``n``
        and ``z``, keeps ``param`` where ``n == 0`` and recomputes it
        from ``(z, n)`` elsewhere, the number the same formula stored at
        the row's last update (optim/ftrl.py;
        tests/test_ftrl.py::test_ftrl_zero_grad_is_idempotent).  A
        RESTORED state whose ``param`` is not what ``(z, n)`` give is
        repaired by the dense pass and left alone here, as by
        update_mode='sparse' and by the reference's server, which
        updates pushed keys only (ftrl.h:54-79).

        What it costs beside the pass (scripts/probe_touched_rows.py on
        a v5e, PR 56; the table at TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX):
        at [2^25, 10] and 55 296 rows three sets of 5.5 ms, three
        gathers of 1.25 and 0.013 of FTRL, 20.45 ms for the 31.42 of
        zeroing the buffer (3.27), writing the rows into it (5.63) and
        the pass over param, n, z and the buffer (22.40).  unique_indices
        on the sets (20.41), rows in rising order with
        indices_are_sorted (19.95) and the forward's param rows in place
        of the third gather (19.17) were not worth their code."""
        if ghot is None:
            return self._apply_touched_rows(table, rows, gsum)
        hsize = self.cfg.hot_size
        in_hot = rows < hsize
        new = self._apply_touched_rows(
            table, jnp.where(in_hot, jnp.int32(self.cfg.table_size), rows), gsum
        )
        ghot = ghot.at[jnp.where(in_hot, rows, jnp.int32(hsize))].add(
            gsum, mode="drop"
        )
        new_hot = self.optimizer.update_rows(
            {k: arr[:hsize] for k, arr in new.items()}, ghot
        )
        return {
            k: jax.lax.dynamic_update_slice_in_dim(
                new[k], new_hot[k], 0, axis=0
            )
            for k in new
        }

    @jax.named_scope("xf.optimizer")
    def _sparse_update(
        self, tables: dict, batch: BatchArrays, occ_grads: dict
    ) -> dict:
        """Touched-rows-only optimizer application (the reference's
        Push path, ftrl.h:54-79): consolidate per unique key, gather
        state rows, run the recurrence, scatter back.  Shared by the
        sparse update mode (whole batch) and sequential mode's sparse
        inner (per slice — the only viable per-slice form at
        north-star table sizes).

        With the hot table on, this becomes a HYBRID: cold keys keep
        the touched-rows path while the hot section's gradients ride
        the MXU into a dense [H, D] buffer whose rows get one dense
        optimizer pass (H rows ≈ 115 KB of traffic — negligible next
        to a [T, D] full-table pass).  Exactly-once semantics: hot
        rows can ALSO appear among the cold keys (split_hot overflow
        spill, io/batch.py:89-93), so cold contributions to rows
        < H are folded into the hot gradient buffer and masked out of
        the sparse scatter — every row sees ONE summed-gradient
        update, matching the dense path's gbuf semantics bit-for-bit
        in structure (_touched_rows_pass: the one piece of code).

        Tables opted OUT of the MXU path (TableSpec.hot=False, e.g.
        FFM's wide v) instead fold their hot-plane occurrences into a
        SECOND consolidate over cold+hot keys and take the plain
        touched-rows update for everything — same exactly-once
        guarantee, no [H, D] buffer."""
        cfg = self.cfg
        kh = batch["hot_keys"].shape[1] if "hot_keys" in batch else 0
        keys_eff = self._cold_keys_eff(batch)
        # one shared argsort; every table's gradients ride the same
        # permutation/segments (same sharing as _scatter_grads)
        order, seg, ukeys = consolidate_plan(keys_eff, cfg.table_size)
        plan_all = None
        if kh:
            from xflow_tpu.ops.hot import hot_scatter

            hot_keys_eff = self._hot_keys_eff(batch)
            if not all(self._mxu_hot.values()):
                # opted-out tables: one combined plan over cold+hot
                # occurrence keys (shared by every such table)
                keys_all = jnp.concatenate(
                    [keys_eff, self._hot_keys_eff_dma(batch)]
                )
                plan_all = consolidate_plan(keys_all, cfg.table_size)
        new_tables = {}
        for name, table in tables.items():
            d = table["param"].shape[-1]
            occ = occ_grads[name]
            if kh:
                hot_g = occ[:, :kh].reshape(-1, d)
                occ = occ[:, kh:]
            if kh and not self._mxu_hot[name]:
                order_a, seg_a, ukeys_a = plan_all
                gsum_a = consolidate_apply(
                    jnp.concatenate([occ.reshape(-1, d), hot_g]),
                    order_a,
                    seg_a,
                )
                new_tables[name] = self._apply_touched_rows(
                    table, ukeys_a, gsum_a
                )
                continue
            gsum = consolidate_apply(occ.reshape(-1, d), order, seg)
            ghot = hot_scatter(
                hot_keys_eff, hot_g, cfg.hot_size, impl=self._hot_impl
            ) if kh else None
            new_tables[name] = self._touched_rows_pass(
                table, ukeys, gsum, ghot
            )
        return new_tables

    def _train_sequential(
        self, state: State, batch: BatchArrays
    ) -> tuple[State, dict[str, jax.Array]]:
        """update_mode='sequential': scan over microbatch slices with
        the TABLES in the scan carry — the optimizer recurrence runs
        once per slice, with gradients divided by the SLICE's real
        count, and slice k reads the tables as slice k-1 left them.
        One dispatch of batch_size examples is therefore step-for-step
        the same training as `microbatch` successive dense steps of
        batch_size/microbatch examples (tests/test_sequential.py
        asserts bitwise-close equality).  This is what composes the
        proven small-batch FTRL convergence (docs/CONVERGENCE.md,
        B=512) with device-rate dispatch: the reference's effective
        optimizer batch is a per-thread text-block slice of a few
        hundred rows (lr_worker.cc:116-118,190-196), which a
        throughput-sized B would otherwise dilute ~256×.

        Cost model (dense inner, the default): each slice pays one
        full-table elementwise optimizer pass (streaming ~7 arrays of
        [T, D] HBM traffic), so wall-clock per example grows with
        microbatch × table bytes / batch.  With
        config.sequential_inner='sparse' the slice instead pays an
        O(slice nnz) consolidate + gather/update/scatter of touched
        rows only — table-size-independent, the form 2^28-scale tables
        require.  See docs/PERF.md 'Sequential mode'."""
        cfg = self.cfg
        if cfg.sequential_inner == "hot":
            return self._train_sequential_hot(state, batch)
        tables = state["tables"]
        dense = state["dense"]
        s = cfg.microbatch
        xs = _interleaved_slices(batch, s)

        def body(carry, bslice):
            tables_c, dense_c, nll_c, cnt_c = carry
            w_sum = jnp.sum(bslice["weights"])
            num_real = jnp.maximum(w_sum, 1.0)
            pctr_s, occ_s, gd = self._forward_grads(
                tables_c, dense_c, bslice, num_real
            )
            if cfg.sequential_inner == "sparse":
                # touched-rows-only per slice: O(slice nnz), the only
                # viable inner at T=2^28 (config.sequential_inner)
                new_tables = self._sparse_update(tables_c, bslice, occ_s)
            else:
                # dense inner: full-table pass per slice BY CHOICE
                # (config.sequential_inner documents the cost; the
                # sparse/hot inners are the 2^28 forms)
                gbufs = self._scatter_grads(
                    tables_c, bslice, occ_s, self._zero_gbufs(tables_c)
                )
                new_tables = {
                    name: self._optimizer_pass(table, gbufs[name])
                    for name, table in tables_c.items()
                }
            new_dense = self._apply_dense_sgd(dense_c, gd)
            nll_c = nll_c + logloss_sum(
                bslice["labels"], pctr_s, bslice["weights"]
            )
            return (new_tables, new_dense, nll_c, cnt_c + w_sum), None

        zero = jnp.zeros((), jnp.float32)
        (new_tables, new_dense, nll_sum, cnt), _ = jax.lax.scan(
            body, (tables, dense, zero, zero), xs
        )
        ll = nll_sum / jnp.maximum(cnt, 1.0)
        return {
            "tables": new_tables,
            "dense": new_dense,
            "step": state["step"] + 1,
        }, {"logloss": ll, "count": cnt}

    def _train_sequential_hot(
        self, state: State, batch: BatchArrays
    ) -> tuple[State, dict[str, jax.Array]]:
        """sequential_inner='hot': hot-FINE / cold-COARSE.

        The dense and sparse inners both pay per-slice work that fights
        the hardware — a full [T, D] HBM stream (dense) or a
        latency-bound consolidate+gather+scatter of ~85-107 ns/slice
        DMA descriptors (sparse; docs/PERF.md "Multi-lane
        scatter-add").  Measured on v5e they cost 36.8 s and ~50 s per
        10 M-example epoch respectively at the flagship geometry.  This
        inner removes BOTH costs from the scan body:

        * the frequency-hot head (table rows [0, H), ~71% of occurrence
          mass at the lr flagship remap — docs/PERF.md) rides the scan
          carry and takes a FULL-granularity optimizer step per
          B_eff-slice, all in MXU one-hot matmuls + [H, D] elementwise
          work — no DMA;
        * cold (tail) rows are pre-gathered ONCE per dispatch window in
          a single batched DMA gather (the 3 M ex/s throughput path's
          access pattern), their per-occurrence gradients are stacked
          as scan outputs, and the window closes with ONE batched
          scatter-add + ONE full-table optimizer pass — exactly the
          dense-mode tail, amortized over `microbatch` slices.

        Semantics vs true sequential: cold values are stale by at most
        one dispatch window, and a cold key occurring k>1 times in the
        window sees one summed-gradient update instead of k — the
        async-parameter-server behavior of the reference itself, whose
        workers compute on weights pulled a minibatch ago and push
        asynchronously (lr_worker.cc:95-143), here confined to the
        zipf TAIL.  Hot rows — where intra-window repetition actually
        concentrates — get bit-exact B_eff-granular treatment.
        Overflow spill (hot-eligible keys in the cold plane,
        io/batch.py split_hot) is handled exactly once: its grads ride
        the window-end pass, which runs AFTER the evolved head is
        written back, so no update is lost or doubled.  Quality:
        docs/CONVERGENCE.md overlay; wall-clock: docs/PERF.md."""
        cfg = self.cfg
        if "hot_keys" not in batch:
            raise ValueError(
                "sequential_inner='hot' needs hot batch planes — was "
                "the loader built with the hot table geometry?"
            )
        from xflow_tpu.ops.hot import hot_gather, hot_scatter

        tables = state["tables"]
        dense = state["dense"]
        s = cfg.microbatch
        h = cfg.hot_size
        # Window-start cold values: ONE batched gather per table,
        # hoisted out of the scan (through the dictionary where the
        # batch carries one, like _gather_local_rows).
        cold_rows = self._cold_rows(tables, batch)
        heads0 = {
            name: {k: arr[:h] for k, arr in t.items()}
            for name, t in tables.items()
        }
        xs = (
            _interleaved_slices(batch, s),
            _interleaved_slices(cold_rows, s),
        )

        def body(carry, slice_in):
            heads, dense_c, nll_c, cnt_c = carry
            bslice, cold_slice = slice_in
            w_sum = jnp.sum(bslice["weights"])
            num_real = jnp.maximum(w_sum, 1.0)
            b, kh = bslice["hot_keys"].shape
            rows = {}
            for name, head in heads.items():
                d = head["param"].shape[-1]
                hot = hot_gather(
                    head["param"],
                    bslice["hot_keys"].reshape(-1),
                    impl=self._hot_impl,
                ).reshape(b, kh, d)
                rows[name] = jnp.concatenate(
                    [hot, cold_slice[name]], axis=1
                )
            pctr_s, occ_s, gd = self._grads_from_rows(
                rows, dense_c, bslice, num_real
            )
            hot_keys_eff = self._hot_keys_eff(bslice)
            new_heads = {}
            cold_occ = {}
            for name, head in heads.items():
                d = head["param"].shape[-1]
                g = occ_s[name]
                hot_g = g[:, :kh].reshape(-1, d)
                cold_occ[name] = g[:, kh:]
                ghot = hot_scatter(
                    hot_keys_eff, hot_g, h,
                    impl=self._hot_impl,
                )
                new_heads[name] = self._optimizer_pass(head, ghot)
            new_dense = self._apply_dense_sgd(dense_c, gd)
            nll_c = nll_c + logloss_sum(
                bslice["labels"], pctr_s, bslice["weights"]
            )
            return (
                (new_heads, new_dense, nll_c, cnt_c + w_sum),
                cold_occ,
            )

        zero = jnp.zeros((), jnp.float32)
        (new_heads, new_dense, nll_sum, cnt), cold_occ = jax.lax.scan(
            body, (heads0, dense, zero, zero), xs
        )
        # Close the window: write the evolved head back, then apply the
        # accumulated cold-tail grads — as ONE dense full-table pass
        # (g=0 rows are idempotent under FTRL/SGD — optim docstrings),
        # or, with Config.hot_windowend='sparse' (auto at
        # table_size_log2 >= 24), through the consolidated touched-rows
        # update: O(window nnz) transients instead of a [T, D] buffer +
        # full-table pass per table — the only viable form at T=2^28
        # (ADVICE step.py:945; analysis rules XF010/XF014).  Either
        # way, spill grads (cold-plane keys < H) land on the
        # written-back head rows here, exactly once.
        keys_eff = self._cold_keys_eff(batch)
        if self._windowend == "sparse":
            order, seg, ukeys = consolidate_plan(keys_eff, cfg.table_size)
        new_tables = {}
        for name, table in tables.items():
            d = table["param"].shape[-1]
            merged = {
                k: jax.lax.dynamic_update_slice_in_dim(
                    table[k], new_heads[name][k], 0, axis=0
                )
                for k in table
            }
            # un-interleave the stacked [s, B/s, Kc, D] slice outputs
            # back to batch order (example i lives at slice i%s,
            # position i//s — _interleaved_slices)
            occ = cold_occ[name].swapaxes(0, 1).reshape(-1, d)
            if self._windowend == "sparse":
                # routed window-end: every table's gradients ride the
                # one shared plan; touched rows see the same summed
                # window gradient the dense pass would apply, pad/
                # sentinel slots gather-clip and scatter-drop
                # (ops/sparse.py module docstring;
                # tests/test_sequential.py equivalence)
                gsum = consolidate_apply(occ, order, seg)
                new_tables[name] = self._apply_touched_rows(
                    merged, ukeys, gsum
                )
                continue
            gbuf = self._cold_accumulate(
                # dense window-end (the small-table form; see the
                # routed branch above for 2^28) — budgeted in
                # memory-budget.json (xf: ignore[XF010])
                jnp.zeros_like(table["param"]),
                keys_eff,
                occ,
            )
            new_tables[name] = self._optimizer_pass(merged, gbuf)
        ll = nll_sum / jnp.maximum(cnt, 1.0)
        return {
            "tables": new_tables,
            "dense": new_dense,
            "step": state["step"] + 1,
        }, {"logloss": ll, "count": cnt}

    def _apply_dense_sgd(self, dense: dict, grad_dense) -> dict:
        """Module-level ``apply_dense_sgd`` bound to this config —
        shared by _finish_step (per-dispatch application) and
        _train_sequential (per-slice application), so the update modes
        cannot drift apart."""
        return apply_dense_sgd(dense, grad_dense, self.cfg.sgd_lr)

    def _finish_step(self, state, new_tables, dense, grad_dense, ll, cnt):
        """Shared step tail for the non-sequential update modes."""
        new_dense = self._apply_dense_sgd(dense, grad_dense)
        with jax.named_scope("xf.metrics"):
            metrics = {"logloss": ll, "count": cnt}
            return {
                "tables": new_tables,
                "dense": new_dense,
                "step": state["step"] + 1,
            }, metrics

    def _predict_impl(self, state: State, batch: BatchArrays) -> jax.Array:
        """pctr per example (reference calculate_pctr, lr_worker.cc:46-61)."""
        batch = self._expand_wire(batch)
        rows = self._gather_model_rows(state["tables"], batch)
        return sigmoid_ref(
            self._logit(rows, self._model_view(batch), state["dense"])
        )

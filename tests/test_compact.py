"""Host-side batch compaction (io/compact.py) and the dictionary wire
(Config.wire_dedup): compaction must round-trip loader batches
byte-exact, the native and numpy dedup kernels must agree, plane
capacities must bucket deterministically (compile_count stays flat),
and training/prediction over the dict wire must match the plain wire —
compression changes what crosses the link, never the math."""

import subprocess
import sys
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.io.compact import (
    DICT_CAP,
    CompactBatch,
    compact_batch,
    dedup_select,
    plane_cap,
)
from xflow_tpu.parallel.step import ROW_LAYOUT_MIN_COLUMNS

from tests.test_binary import batches_equal, make_loader

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _numpy_dedup(keys, cap):
    """Force the numpy fallback path regardless of the native build."""
    import unittest.mock as mock

    from xflow_tpu import native

    with mock.patch.object(native, "has_dict_encode", lambda: False):
        return dedup_select(keys, cap)


def _decode(keys, uniq, codes):
    """Per-element keys implied by a (uniq, codes) encoding."""
    m = codes != 0xFFFFFFFF
    got = keys.copy()
    if m.any():
        got[m] = uniq[codes[m].astype(np.int64)]
    return got, m


# -- kernel ----------------------------------------------------------------


@pytest.mark.parametrize("dist", ["random", "zipf"])
def test_dedup_select_native_numpy_parity(dist):
    """Same dictionary SET and same per-element tier on both kernel
    implementations (within-dictionary order is free), and both
    encodings decode back to the input keys."""
    from xflow_tpu import native

    rng = np.random.default_rng(3)
    if dist == "random":
        keys = rng.integers(0, 1 << 22, 40000).astype(np.int64)
    else:
        keys = (rng.zipf(1.3, 40000) - 1).astype(np.int64)
    for cap in (64, 1024, DICT_CAP):
        u_np, c_np = _numpy_dedup(keys, cap)
        assert len(u_np) <= cap
        d_np, m_np = _decode(keys, u_np, c_np)
        np.testing.assert_array_equal(d_np, keys)
        if not (native.available() and native.has_dict_encode()):
            continue
        u_nat, c_nat = native.native_dict_encode(keys, cap)
        assert set(u_nat.tolist()) == set(u_np.tolist())
        d_nat, m_nat = _decode(keys, u_nat, c_nat)
        np.testing.assert_array_equal(d_nat, keys)
        np.testing.assert_array_equal(m_nat, m_np)


def test_dedup_select_small_fits_whole_dictionary():
    keys = np.asarray([5, 5, 9, 5, 9, 7], np.int64)
    uniq, codes = _numpy_dedup(keys, DICT_CAP)
    assert sorted(uniq.tolist()) == [5, 7, 9]
    assert (codes != 0xFFFFFFFF).all()
    got, _ = _decode(keys, uniq, codes)
    np.testing.assert_array_equal(got, keys)


def test_dedup_select_threshold_caps_dictionary():
    """With more unique keys than cap, the dictionary keeps the
    most-duplicated ones (count >= threshold) and the tail codes as
    0xFFFFFFFF."""
    rng = np.random.default_rng(0)
    hot = np.repeat(np.arange(10, dtype=np.int64), 50)
    tail = rng.integers(1000, 1 << 30, 500).astype(np.int64)
    keys = np.concatenate([hot, tail])
    rng.shuffle(keys)
    uniq, codes = _numpy_dedup(keys, 16)
    assert set(np.arange(10).tolist()) <= set(uniq.tolist())
    assert len(uniq) <= 16
    got, covered = _decode(keys, uniq, codes)
    np.testing.assert_array_equal(got, keys)
    assert covered.sum() >= 500  # the hot head is covered


# -- capacities ------------------------------------------------------------


def test_plane_cap_bucketing():
    slots = 131072 * 16
    g = max(256, slots // 32)
    assert plane_cap(0, slots) == 0
    assert plane_cap(1, slots) == g
    assert plane_cap(g, slots) == g
    assert plane_cap(g + 1, slots) == 2 * g
    assert plane_cap(slots, slots) == slots
    assert plane_cap(slots - 1, slots) == slots  # never exceeds slots
    # nearby batch sizes share one bucket -> one compiled program
    assert plane_cap(g + 5, slots) == plane_cap(g + g // 2, slots)


# -- round trip ------------------------------------------------------------


@pytest.mark.parametrize("hot", [False, True])
def test_compact_roundtrip_loader_batches(toy_dataset, hot):
    """compact -> expand is byte-exact for every loader-produced batch,
    including the zero-padded partial tail batch."""
    src = toy_dataset.train_prefix + "-00000"
    kw = dict(hot_size=256, hot_nnz=6) if hot else {}
    if hot:
        rng = np.random.default_rng(3)
        kw["remap"] = rng.permutation(1 << 14).astype(np.int32)
    loader = make_loader(src, **kw)
    n = 0
    for batch, _ in loader.iter_batches():
        cb = compact_batch(batch, 1 << 14, 256 if hot else 0)
        batches_equal(batch, cb.expand())
        assert cb.num_real() == batch.num_real()
        np.testing.assert_array_equal(cb.labels, batch.labels)
        np.testing.assert_array_equal(cb.weights, batch.weights)
        n += 1
    assert n > 2


def test_compact_roundtrip_all_padding():
    """An all-padding batch (every key sentinel/masked) compacts to
    empty planes and expands back to zeros."""
    b, k = 8, 6
    z_i = np.zeros((b, k), np.int32)
    z_f = np.zeros((b, k), np.float32)
    batch = make_batch(
        z_i, z_i, z_f, z_f,
        np.zeros(b, np.float32), np.zeros(b, np.float32),
    )
    cb = compact_batch(batch, 1 << 14, 0)
    assert cb.n_cold == 0 and cb.n_dict == 0 and cb.num_real() == 0
    batches_equal(batch, cb.expand())


def test_compact_wire_is_smaller_and_fixed_point(toy_dataset):
    """The wire is smaller than the plain compact wire's planes, and
    compact(expand(cb)) reproduces cb's planes exactly (the packed-v2
    fixed point)."""
    from xflow_tpu.parallel.step import compact_wire_np

    src = toy_dataset.train_prefix + "-00000"
    loader = make_loader(src)
    batch, _ = next(iter(loader.iter_batches()))
    cb = compact_batch(batch, 1 << 14, 0)
    old = sum(
        v.nbytes for v in compact_wire_np(batch, ship_slots=True).values()
    )
    assert cb.wire_nbytes(ship_slots=True) < old
    cb2 = compact_batch(cb.expand(), 1 << 14, 0)
    for f in (
        "cu", "ci", "ct", "cf", "cc", "h8", "hx", "hxh", "hf", "hc",
        "lb", "wb", "cs", "hs",
    ):
        np.testing.assert_array_equal(
            getattr(cb, f), getattr(cb2, f), err_msg=f
        )


def test_packed_v2_mmap_vs_buffered_byte_equality(toy_dataset, tmp_path):
    """The packed-v2 reader's two paths — zero-copy mmap views of the
    shard file (the fan-out steady state) and the buffered fallback
    (unmmapable streams: no fileno) — must produce byte-identical
    planes, counts and record offsets.  The mmap path really is
    zero-copy: each plane's memory is backed by the mapping, not a
    per-record allocation."""
    import io as _io
    import mmap as _mmap

    from xflow_tpu.io import packed

    src = toy_dataset.train_prefix + "-00000"
    dst = str(tmp_path / "shard.pk2")
    packed.convert_shard(
        src, dst, fmt="v2", batch_size=32, max_nnz=24,
        table_size=1 << 14,
    )
    with open(dst, "rb") as f:
        via_mmap = list(packed.iter_compact_batches(f))
    with open(dst, "rb") as f:
        blob = f.read()
    # BytesIO has no usable fileno -> the reader falls back to read()
    via_buffer = list(packed.iter_compact_batches(_io.BytesIO(blob)))
    assert len(via_mmap) == len(via_buffer) > 1
    planes = (
        "cu", "ci", "ct", "cf", "cc", "h8", "hx", "hxh", "hf", "hc",
        "lb", "wb", "cs", "hs",
    )
    for (ma, oa, na), (mb, ob, nb) in zip(via_mmap, via_buffer):
        assert (oa, na) == (ob, nb)
        assert ma.n_real == mb.n_real and ma.n_cold == mb.n_cold
        for pl in planes:
            a, b = getattr(ma, pl), getattr(mb, pl)
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b, err_msg=pl)
    # zero-copy witness: an mmap-path plane's base buffer is the map
    def root_buffer(arr):
        while isinstance(getattr(arr, "base", None), np.ndarray):
            arr = arr.base
        return getattr(arr, "base", None)

    first = via_mmap[0][0]
    # hot-off shards synthesize default hot planes (from_planes) — the
    # zero-copy witness only applies to planes present in the record
    record_planes = ("cu", "ci", "ct", "cf", "cc", "lb", "wb", "cs")
    sized = [
        getattr(first, pl) for pl in record_planes
        if getattr(first, pl).size
    ]
    def is_map_backed(buf):
        return isinstance(buf, _mmap.mmap) or (
            isinstance(buf, memoryview)
            and isinstance(buf.obj, _mmap.mmap)
        )

    assert sized and all(
        is_map_backed(root_buffer(arr)) for arr in sized
    ), "mmap-path planes are not views of the mapping"
    # padded expansion equality too (the v1-contract surface)
    with open(dst, "rb") as f:
        exp_mmap = [b for b, _, _ in packed.iter_batches(f)]
    exp_buf = [b for b, _, _ in packed.iter_batches(_io.BytesIO(blob))]
    for a, b in zip(exp_mmap, exp_buf):
        batches_equal(a, b)


# -- validation ------------------------------------------------------------


def test_compact_rejects_value_batches():
    b = make_batch(
        np.zeros((2, 3), np.int32), np.zeros((2, 3), np.int32),
        np.asarray([[0.5, 1, 1], [1, 1, 1]], np.float32),
        np.ones((2, 3), np.float32),
        np.zeros(2, np.float32), np.ones(2, np.float32),
    )
    with pytest.raises(ValueError, match="binary features"):
        compact_batch(b, 1 << 14, 0)


def test_holey_rows_compact_semantically_but_not_strictly():
    """Rows with interior padding (mask holes) still ride the dict
    wire — entries re-compact leftward with their triplets intact
    (models are permutation-invariant over the feature axis) — but the
    packed-v2 writer's strict_layout contract refuses them, because
    byte-exact round-trip is impossible."""
    mask = np.asarray([[1, 0, 1]], np.float32)
    b = make_batch(
        np.asarray([[3, 0, 5]], np.int32),
        np.asarray([[1, 0, 2]], np.int32),
        mask.copy(), mask,
        np.zeros(1, np.float32), np.ones(1, np.float32),
    )
    eb = compact_batch(b, 1 << 14, 0).expand()
    np.testing.assert_array_equal(eb.keys, [[3, 5, 0]])
    np.testing.assert_array_equal(eb.slots, [[1, 2, 0]])
    np.testing.assert_array_equal(eb.mask, [[1, 1, 0]])
    with pytest.raises(ValueError, match="left-compacted"):
        compact_batch(b, 1 << 14, 0, strict_layout=True)


def test_compact_rejects_out_of_range_keys():
    mask = np.ones((1, 2), np.float32)
    b = make_batch(
        np.asarray([[3, 40000]], np.int32), np.zeros((1, 2), np.int32),
        mask.copy(), mask,
        np.zeros(1, np.float32), np.ones(1, np.float32),
    )
    with pytest.raises(ValueError, match="table_size"):
        compact_batch(b, 1 << 14, 0)


# -- wire parity on device -------------------------------------------------


def _train_once(cfg, batch):
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    mesh = make_mesh(1)
    model, opt = make_model(cfg), make_optimizer(cfg)
    step = TrainStep(model, opt, cfg, mesh)
    state = init_state(model, opt, cfg, mesh)
    state, m = step.train(state, step.put_batch(batch))
    pctr = step.predict(state, step.put_batch(batch))
    return step, jax.device_get(state["tables"]), np.asarray(pctr)


@pytest.mark.parametrize("model", ["lr", "mvm"])
def test_dict_wire_matches_plain_wire(model):
    """One train step + predict over the dict wire equals the plain
    compact wire to float tolerance."""
    rng = np.random.default_rng(11)
    b, k = 64, 24
    nnz = rng.integers(1, k, b)
    mask = (np.arange(k)[None, :] < nnz[:, None]).astype(np.float32)
    keys = np.where(
        mask > 0, rng.integers(0, 1 << 14, (b, k)), 0
    ).astype(np.int32)
    head = rng.integers(0, 64, (b, k)).astype(np.int32)
    keys = np.where((rng.random((b, k)) < 0.5) & (mask > 0), head, keys)
    slots = np.where(mask > 0, rng.integers(0, 8, (b, k)), 0).astype(
        np.int32
    )
    labels = (rng.random(b) < 0.4).astype(np.float32)
    weights = (np.arange(b) < 60).astype(np.float32)
    batch = make_batch(
        keys, slots, mask.copy(), mask, labels * weights, weights,
        1 << 8, 8,
    )
    kw = dict(
        model=model, batch_size=b, table_size_log2=14, max_nnz=16,
        max_fields=8, num_devices=1, hot_size_log2=8, hot_nnz=8,
    )
    step_off, tables_off, pctr_off = _train_once(
        Config(wire_dedup="off", **kw), batch
    )
    step_on, tables_on, pctr_on = _train_once(
        Config(wire_dedup="on", **kw), batch
    )
    assert not step_off.dict_wire and step_on.dict_wire
    assert step_on.wire_format == "dict"
    jax.tree.map(
        lambda a, c: np.testing.assert_allclose(
            a, c, rtol=1e-5, atol=1e-6
        ),
        tables_off,
        tables_on,
    )
    np.testing.assert_allclose(pctr_off, pctr_on, rtol=1e-5, atol=1e-6)


# -- the device decode against the host's inverse -----------------------------

_DECODE_DATA = ("all_padding", "full_rows", "odd_caps", "zipf", "tail_only")


def _decode_case(data, hot, key_bytes, table_log2=None):
    """(Batch, table_size, hot_size, dict_cap) for one decode case.  The
    batch is in wire order already: left-compacted rows, hot ids
    < hot_size in the hot section, everything else cold.  The table has
    2^20 rows (u24 keys) or 2^26 (u32) unless ``table_log2`` says."""
    rng = np.random.default_rng(
        _DECODE_DATA.index(data) * 7 + key_bytes + len(hot)
    )
    table = 1 << (table_log2 or (20 if key_bytes == 3 else 26))
    hot_size = {"none": 0, "u12": 1 << 10, "u16": 1 << 14}[hot]
    # odd_caps: B*K is no multiple of 128 and nearly every slot is real,
    # so the occurrence planes are capped at B*K (259 cold, 185 hot)
    b, kc, kh = (37, 7, 5) if data == "odd_caps" else (48, 9, 11)
    if not hot_size:
        kh = 0
    if data == "all_padding":
        cc, hc = np.zeros(b, np.int64), np.zeros(b, np.int64)
    elif data == "odd_caps":
        cc, hc = np.full(b, kc), np.full(b, kh)
        cc[:2] -= 1
        hc[:2] = np.maximum(hc[:2] - 1, 0)
    else:
        cc = rng.integers(0, kc + 1, b)
        hc = rng.integers(0, kh + 1, b)
        cc[3], hc[3] = kc, kh  # a row at full K
        cc[5], hc[5] = 0, 0    # and an empty one
    cm = np.arange(kc)[None, :] < cc[:, None]
    hm = np.arange(kh)[None, :] < hc[:, None]
    if data == "zipf":  # duplicates: a dictionary tier AND a tail
        cold = hot_size + np.minimum(
            rng.zipf(1.3, (b, kc)), table - hot_size - 1
        )
    elif data == "tail_only":  # no key repeats, more keys than dict_cap
        cold = hot_size + 1 + 3 * rng.permutation(b * kc).reshape(b, kc)
    else:
        cold = rng.integers(hot_size, table, (b, kc))
    # both hot tiers: ids < 256 and above
    hot_ids = np.where(
        rng.random((b, kh)) < 0.5,
        rng.integers(0, 256, (b, kh)),
        rng.integers(0, max(hot_size, 1), (b, kh)),
    )
    f32 = np.float32
    batch = make_batch(
        np.where(cm, cold, 0).astype(np.int32),
        np.where(cm, rng.integers(0, 200, (b, kc)), 0).astype(np.int32),
        cm.astype(f32), cm.astype(f32),
        (rng.random(b) < 0.4).astype(f32),
        (rng.random(b) < 0.9).astype(f32),
    )
    batch.hot_keys = np.where(hm, hot_ids, 0).astype(np.int32)
    batch.hot_slots = np.where(
        hm, rng.integers(0, 200, (b, kh)), 0
    ).astype(np.int32)
    batch.hot_vals, batch.hot_mask = hm.astype(f32), hm.astype(f32)
    dict_cap = {"zipf": 24, "tail_only": 4}.get(data, DICT_CAP)
    return batch, table, hot_size, dict_cap


def _decode_step(model, table, hot_size, b, kc, kh):
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep

    cfg = Config(
        model=model, batch_size=b, max_nnz=kc,
        table_size_log2=table.bit_length() - 1, num_devices=1,
        hot_size_log2=hot_size.bit_length() - 1 if hot_size else 0,
        hot_nnz=kh, wire_dedup="on",
    )
    return TrainStep(make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1))


@pytest.mark.parametrize("data", _DECODE_DATA)
@pytest.mark.parametrize("model", ["lr", "mvm"])  # slots not shipped / shipped
@pytest.mark.parametrize("key_bytes", [3, 4])
@pytest.mark.parametrize("hot", ["none", "u12", "u16"])
def test_device_decode_equals_host_expand(hot, key_bytes, model, data):
    """The jitted decode of the cw_* planes equals CompactBatch.expand()
    plane for plane, bit for bit: every wire variant one device can
    meet."""
    batch, table, hot_size, dict_cap = _decode_case(data, hot, key_bytes)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    assert (cb.key_bytes, cb.hx16) == (key_bytes, hot == "u16")
    if data == "zipf":
        assert 0 < cb.n_dict_occ < cb.n_cold  # both cold tiers in play
    if data == "tail_only":
        assert cb.n_dict == 0 == len(cb.cu) and cb.n_cold  # the tail alone
    if data == "odd_caps":
        assert len(cb.cs) % 128 and (not hot_size or len(cb.hs) % 128)
    b, kc, kh = batch.batch_size, batch.max_nnz, batch.hot_nnz
    step = _decode_step(model, table, hot_size, b, kc, kh)
    assert step.dict_wire
    ship = model == "mvm"
    wire = cb.wire(step._ship_slots)
    assert ("cw_cs" in wire) == ship
    got = jax.device_get(jax.jit(step._expand_wire)(wire))
    plan = got.pop("cold_plan")  # its reader: the cold-row tests below
    assert {k: len(plan[k]) for k in ("cu", "ci", "ct")} == {
        "cu": len(cb.cu), "ci": len(cb.ci), "ct": len(cb.ct)
    }
    want = cb.expand()
    batches_equal(want, batch)  # the host's inverse is exact
    cold = {
        "keys": want.keys, "vals": want.vals, "mask": want.mask,
        "slots": want.slots if ship else np.zeros_like(want.slots),
        "labels": want.labels, "weights": want.weights,
    }
    hot_planes = {
        "hot_keys": want.hot_keys, "hot_vals": want.hot_vals,
        "hot_mask": want.hot_mask,
        "hot_slots": (
            want.hot_slots if ship else np.zeros_like(want.hot_slots)
        ),
    } if hot_size else {}
    assert set(got) == set(cold) | set(hot_planes)
    for name, plane in {**cold, **hot_planes}.items():
        assert got[name].dtype == plane.dtype, name
        np.testing.assert_array_equal(got[name], plane, err_msg=name)


@pytest.mark.parametrize("data", _DECODE_DATA)
@pytest.mark.parametrize("hot", ["none", "u12", "u16"])
def test_device_decode_tpu_form_interpreted(hot, data):
    """The decode as a TPU traces it (the Mosaic lane shuffle of
    ops/window.py, run here by the Pallas TPU interpreter) equals the
    form this backend runs, plane for plane."""
    from jax.experimental.pallas import tpu as pltpu

    from xflow_tpu.ops import window
    from xflow_tpu.parallel.step import expand_dict_wire

    batch, table, hot_size, dict_cap = _decode_case(data, hot, 4)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    step = _decode_step(
        "mvm", table, hot_size, batch.batch_size, batch.max_nnz,
        batch.hot_nnz,
    )
    wire = cb.wire(True)
    want = jax.device_get(jax.jit(step._expand_wire)(wire))
    with pltpu.force_tpu_interpret_mode():
        got = jax.device_get(jax.jit(
            lambda w: expand_dict_wire(step.cfg, window.lane_select_tpu, w)
        )(wire))
    got, want = (
        {**{k: v for k, v in planes.items() if k != "cold_plan"},
         **{f"plan.{k}": v for k, v in planes["cold_plan"].items()}}
        for planes in (got, want)
    )
    assert set(got) == set(want)
    for name, plane in want.items():
        assert got[name].dtype == plane.dtype, name
        np.testing.assert_array_equal(got[name], plane, err_msg=name)


@pytest.mark.parametrize("model", ["lr", "mvm"])
def test_a_shorter_plane_takes_the_longest_length_the_step_has_shipped(model):
    """Two batches of one geometry whose flat planes fall into different
    plane_cap granules: once the step has shipped the longer form it
    ships the shorter batch at that length, zero-padded
    (TrainStep._settle_planes), the decode reads the same batch out of
    it, and the train program is compiled for ONE set of shapes.  A step
    that meets the shorter one first ships it as it is."""
    from xflow_tpu.parallel.step import init_state

    full, table, hot_size, _ = _decode_case("full_rows", "u16", 4)
    sparse, *_ = _decode_case("all_padding", "u16", 4)  # no hot entry at all
    sparse.keys[:4, :2], sparse.mask[:4, :2] = full.keys[:4, :2], 1.0
    sparse.vals[:4, :2] = 1.0
    b, kc, kh = full.batch_size, full.max_nnz, full.hot_nnz
    step, fresh = (_decode_step(model, table, hot_size, b, kc, kh) for _ in range(2))
    plain, _ = fresh.host_wire_np(sparse)
    long, _ = step.host_wire_np(full)
    settled, _ = step.host_wire_np(sparse)
    assert {k: v.shape for k, v in settled.items()} == {
        k: v.shape for k, v in long.items()
    }
    grown = [k for k in plain if len(plain[k]) < len(settled[k])]
    assert {"cw_h8", "cw_hx", "cw_hf"} <= set(grown)
    assert ("cw_hs" in grown) == (model == "mvm")
    for k in grown:
        assert not settled[k][len(plain[k]):].any()
        np.testing.assert_array_equal(settled[k][: len(plain[k])], plain[k])
    decode = jax.jit(step._expand_wire)
    got, want = jax.device_get((decode(settled), jax.jit(fresh._expand_wire)(plain)))
    got.pop("cold_plan"), want.pop("cold_plan")
    for name, plane in want.items():
        np.testing.assert_array_equal(got[name], plane, err_msg=name)
    state = init_state(step.model, step.optimizer, step.cfg, step.mesh)
    for batch in (full, sparse, full):
        state, _ = step.train(state, step.put_batch(batch))
    assert step.train._cache_size() == 1


def test_a_train_program_reads_the_same_first_or_later():
    """On one device the step hands the tables back replicated, and
    init_state places them so: the program lowered for a batch's shapes
    is the same text, so the same compile-cache key, whether those shapes
    are the process's first or come after a step of other shapes."""
    from jax.sharding import PartitionSpec
    from xflow_tpu.parallel.step import abstract_like, init_state

    full, table, hot_size, _ = _decode_case("full_rows", "u16", 4)
    sparse, *_ = _decode_case("all_padding", "u16", 4)
    b, kc, kh = full.batch_size, full.max_nnz, full.hot_nnz

    def lowered(first):
        step = _decode_step("mvm", table, hot_size, b, kc, kh)
        state = init_state(step.model, step.optimizer, step.cfg, step.mesh)
        assert state["tables"]["v"]["param"].sharding.spec == PartitionSpec()
        if first is not None:
            state, _ = step.train(state, step.put_batch(first))
        arrays = {  # sparse's own shapes, whatever the step shipped before
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=step._bsharding)
            for k, v in _decode_step(
                "mvm", table, hot_size, b, kc, kh
            ).host_wire_np(sparse)[0].items()
        }
        return step.train.lower(abstract_like(state), arrays).as_text()

    assert lowered(None) == lowered(full)


# -- the cold rows through the batch's dictionary ------------------------------


def _cold_rows_case(data, hot, key_bytes, d, lane_select):
    """(rows the dictionary route returns [B, K, D], param[keys], mask)
    for one decode case and a random [T, D] table."""
    from xflow_tpu.parallel.step import dict_cold_rows, expand_dict_wire

    # a wide row over 2^16 rows: 2^20 of 160 columns are 640 MiB a case
    batch, table, hot_size, dict_cap = _decode_case(
        data, hot, key_bytes, table_log2=16 if d > 16 else None
    )
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    step = _decode_step(
        "lr", table, hot_size, batch.batch_size, batch.max_nnz,
        batch.hot_nnz,
    )
    rng = np.random.default_rng(d)
    param = rng.standard_normal((table, d), dtype=np.float32)
    param[0] = 7.0  # what a padding slot of param[keys] reads

    def route(w, p):
        plan = expand_dict_wire(step.cfg, lane_select, w)["cold_plan"]
        return dict_cold_rows(plan, {"t": p}, lane_select)["t"]

    got = np.asarray(jax.jit(route)(cb.wire(False), param))
    return (
        got.reshape(batch.keys.shape + (d,)), param[batch.keys],
        batch.mask > 0,
    )


# Widths on both sides of the route's layouts (dict_cold_rows: column by
# column below ROW_LAYOUT_MIN_COLUMNS, row by row from it on): LR's one
# column, FM's and MVM's ten, the widest row laid out by columns, the
# narrowest laid out by rows, and FFM's 160 (benchmarks/configs/).
_ROUTE_WIDTHS = sorted(
    {1, 10, ROW_LAYOUT_MIN_COLUMNS - 1, ROW_LAYOUT_MIN_COLUMNS, 160}
)


# u32 keys need a table above 2^24 rows: at D = 1 only (256 MiB a table)
@pytest.mark.parametrize(
    "key_bytes,d", [(4, 1)] + [(3, d) for d in _ROUTE_WIDTHS]
)
@pytest.mark.parametrize("data", _DECODE_DATA)
@pytest.mark.parametrize("hot", ["none", "u12"])
def test_dict_cold_rows_equal_param_at_keys(hot, data, key_bytes, d):
    """The cold rows fetched through the dictionary (the table read per
    dictionary and tail entry, the occurrences resolved out of those,
    the padded layout by column takes or by row gathers as the width
    says) equal ``param[keys]`` bit for bit on every unmasked slot, and
    are 0 on padding: no dictionary beside a tail, no tail, nothing at
    all, rows at max_nnz, capacities that are no multiple of 128."""
    from xflow_tpu.ops import window

    got, want, real = _cold_rows_case(
        data, hot, key_bytes, d, window.lane_select_xla
    )
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        got[real].view(np.uint32), want[real].view(np.uint32)
    )
    assert not got[~real].view(np.uint32).any()


@pytest.mark.parametrize("d", _ROUTE_WIDTHS)
@pytest.mark.parametrize("data", _DECODE_DATA)
def test_dict_cold_rows_tpu_form_interpreted(data, d):
    """The same as a TPU traces the route: the decode's running counts
    and a narrow row's columns through the Mosaic lane shuffle (run here
    by the Pallas TPU interpreter), a wide row by row gathers."""
    from jax.experimental.pallas import tpu as pltpu

    from xflow_tpu.ops import window

    with pltpu.force_tpu_interpret_mode():
        got, want, real = _cold_rows_case(
            data, "u12", 3, d, window.lane_select_tpu
        )
    np.testing.assert_array_equal(
        got[real].view(np.uint32), want[real].view(np.uint32)
    )
    assert not got[~real].view(np.uint32).any()


def _dict_and_expanded_states(model, steps, hot, **mode):
    """Train state after ``steps`` train steps on one zipf batch that
    rides the dictionary wire (cold rows through the dictionary), and
    after the same steps on its ``CompactBatch.expand()`` over the plain
    compact wire (a row per padded slot: the parent's form)."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    batch, table, hot_size, dict_cap = _decode_case("zipf", hot, 3)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    out = []
    for dedup, fed in (("on", cb), ("off", cb.expand())):
        cfg = Config(
            model=model, batch_size=batch.batch_size, max_nnz=batch.max_nnz,
            table_size_log2=table.bit_length() - 1, num_devices=1,
            hot_size_log2=hot_size.bit_length() - 1 if hot_size else 0,
            hot_nnz=batch.hot_nnz, wire_dedup=dedup, **mode,
        )
        mesh = make_mesh(1)
        m, opt = make_model(cfg), make_optimizer(cfg)
        step = TrainStep(m, opt, cfg, mesh)
        assert step.dict_wire == (dedup == "on")
        state = init_state(m, opt, cfg, mesh)
        for _ in range(steps):
            state, metrics = step.train(state, step.put_batch(fed))
        pctr = step.predict(state, step.put_batch(fed))
        out.append(jax.device_get((state, metrics, pctr)))
    return out


# D = 1; w beside v at D = 8; w on the column takes beside a v of 32
# fields x 4 = 128 columns laid out by row gathers, off the MXU head
@pytest.mark.parametrize("mode,model", [
    (mode, model)
    for model in ("lr", "fm", "ffm")
    for mode in ("dense", "sparse", "sequential_hot")
    # the hot sequential inner refuses a table that opted out of the head
    if (mode, model) != ("sequential_hot", "ffm")
])
def test_dict_route_leaves_the_state_of_the_expanded_batch(mode, model):
    """Three dense steps, one update_mode='sparse' step and one window
    of the hot sequential inner (its window-start gather) on a
    dictionary-wire batch leave every table, the metrics and the
    trainer's eval bit-equal to the same steps on the expanded batch:
    the rows are the same float32 values by another route."""
    steps, hot, kw = {
        "dense": (3, "u12", {}),
        # the touched-rows update runs without a hot table
        "sparse": (1, "none", {"update_mode": "sparse"}),
        "sequential_hot": (1, "u12", {
            "update_mode": "sequential", "sequential_inner": "hot",
            "microbatch": 4,
        }),
    }[mode]
    got, want = _dict_and_expanded_states(model, steps, hot, **kw)
    jax.tree.map(
        lambda a, c: np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(c).view(np.uint32)
        ),
        got, want,
    )


def _table_gathers(step, state, fed):
    """Index count of every gather out of a [T, D] table in the traced
    train step."""
    shapes = {t["param"].shape for t in state["tables"].values()}
    return sorted(
        int(np.prod(e.invars[1].aval.shape[:-1]))
        for e in _eqns(jax.make_jaxpr(step._train_impl)(
            state, step.put_batch(fed)
        ).jaxpr)
        if e.primitive.name == "gather" and e.invars[0].aval.shape in shapes
    )


@pytest.mark.parametrize("model", ["lr", "fm"])
def test_dict_wire_step_reads_the_table_per_dictionary_and_tail_entry(model):
    """The mechanism of PR 30, pinned on the traced step: on a
    dictionary-wire batch every gather out of a [T, D] table has the
    dictionary's or the tail's index count (cap(cu) + cap(ct) a table,
    under B * max_nnz by the wire's construction), never a padded
    plane's; the same rows over the plain compact wire still gather a
    row per padded slot (the parent's form, and every other batch's)."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    batch, table, hot_size, dict_cap = _decode_case("zipf", "u12", 3)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    b, kc = batch.batch_size, batch.max_nnz
    assert 0 < len(cb.cu) + len(cb.ct) < b * kc
    counts = {}
    for dedup, fed in (("on", cb), ("off", cb.expand())):
        cfg = Config(
            model=model, batch_size=b, max_nnz=kc,
            table_size_log2=table.bit_length() - 1, num_devices=1,
            hot_size_log2=hot_size.bit_length() - 1, hot_nnz=batch.hot_nnz,
            wire_dedup=dedup, hot_impl="mxu",
        )
        mesh = make_mesh(1)
        m, opt = make_model(cfg), make_optimizer(cfg)
        step = TrainStep(m, opt, cfg, mesh)
        counts[dedup] = _table_gathers(
            step, init_state(m, opt, cfg, mesh), fed
        )
    tables = 2 if model == "fm" else 1
    assert counts["on"] == sorted([len(cb.cu), len(cb.ct)] * tables)
    assert counts["off"] == [b * kc] * tables


def _narrow_gathers(jaxpr, found):
    """Every XLA ``gather`` whose slices are single elements, or rows
    of a source two columns wide (ops/window.py::wide_take), through
    all nested jaxprs but a Mosaic kernel's (there the gather is a lane
    shuffle inside one vreg, not a DMA): (index count, operand shape,
    whether only the minor axis is indexed)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "gather" and (
            int(np.prod(eqn.params["slice_sizes"])) <= 2
        ):
            operand, indices = (v.aval for v in eqn.invars[:2])
            dn = eqn.params["dimension_numbers"]
            found.append((
                int(np.prod(indices.shape[:-1])), operand.shape,
                tuple(dn.start_index_map) == (operand.ndim - 1,),
            ))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _narrow_gathers(sub, found)
    return found


@pytest.mark.parametrize("data", ["zipf", "odd_caps"])
def test_device_decode_has_no_padded_size_element_gather(data):
    """The mechanism of PR 25, pinned: no padded [B, K] plane is rebuilt
    by a gather of one-element slices (a gather costs the TPU one DMA
    descriptor per slice: 335 ms of a 407 ms step when the decode
    gathered elements).  ONE gather with a free index is allowed, by
    name: the dictionary resolve ``cu[ci]``, one index per entry of the
    ``cw_ci`` occurrence plane, and since PR 30 it reads two-word rows
    (operand: the dictionary plane beside a column of zeros;
    ops/window.py::wide_take), which cost the chip 2.5 ns an index
    where single elements cost 8.6.  That plane is smaller than B*K
    only by the data (plane_cap caps it AT B*K, which the odd_caps case
    reaches: every slot a dictionary hit), so the resolve is pinned as
    the exception and not by a size.  Beside it the TPU form has no
    element gather at all; the form other backends run gathers elements
    only inside a 256-wide window row
    (ops/window.py::lane_select_xla), never from a stream."""
    from xflow_tpu.ops import window
    from xflow_tpu.parallel.step import expand_dict_wire

    batch, table, hot_size, dict_cap = _decode_case(data, "u12", 4)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    b, kc, kh = batch.batch_size, batch.max_nnz, batch.hot_nnz
    step = _decode_step("mvm", table, hot_size, b, kc, kh)
    wire = cb.wire(True)
    if data == "odd_caps":
        assert len(cb.ci) == b * kc  # plane_cap's ceiling, reached
    else:
        assert len(cb.ci) < b * kc
    cap_d = len(cb.cu)
    assert cap_d not in (0, 2 * window.LANES)

    def others(lane_select):
        """The decode's element gathers but the resolve, which must be
        there exactly once."""
        found = _narrow_gathers(
            jax.make_jaxpr(
                lambda w: expand_dict_wire(step.cfg, lane_select, w)
            )(wire).jaxpr, []
        )
        assert not [g for g in found if g[1] == (cap_d,)], found
        resolve = [g for g in found if g[1] == (cap_d, 2)]
        assert resolve == [(len(cb.ci), (cap_d, 2), False)], found
        return [g for g in found if g[1] != (cap_d, 2)]

    assert step._lane_select is window.lane_select_xla  # this backend's
    lane_selects = others(window.lane_select_xla)
    assert lane_selects and all(
        shape[-1] == 2 * window.LANES and minor_only
        for _, shape, minor_only in lane_selects
    ), lane_selects
    assert others(window.lane_select_tpu) == []  # what a TPU traces


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("wire,microbatch", [
    ("dict", 1), ("dict", 4), ("compact", 1),
])
def test_dict_wire_train_step_scatters_each_table_once(wire, microbatch):
    """The dense step's cold scatter, per table ONE scatter-add into its
    [T, D] buffer, and how many indices it hands it (PR 48).  A whole
    dictionary-wire batch: a table wider than one column takes
    cap(cu) + cap(ct) indices, one per dictionary and tail entry, after
    ONE scatter-add of the B * max_nnz slots into the [cap(cu), D]
    dictionary buffer (step.py::dict_cold_grads); the one-column table
    keeps an index per padded slot.  No KEY is sorted for it: the one
    sort in the step orders the B * max_nnz POSITIONS under ``is_tail``
    (a single operand; merging duplicate keys by a sort lost on the chip:
    docs/PERF.md "Cold consolidation").  The same rows over the plain
    compact wire, and the dictionary wire cut into microbatch slices
    (which go without the plan), keep the parent's form: every table an
    index per padded slot of the batch or slice, no sort at all.  Traced
    with the MXU head, whose gradient is matmuls plus one add of rows
    [0, H); the "seg" head other backends run is a segment-sum."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    batch, table, hot_size, dict_cap = _decode_case("zipf", "u12", 4)
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    b, kc = batch.batch_size, batch.max_nnz
    caps = len(cb.cu) + len(cb.ct)
    assert len(cb.cu) and len(cb.ct) and caps < b * kc
    cfg = Config(
        model="fm", batch_size=b, max_nnz=kc,
        table_size_log2=table.bit_length() - 1, num_devices=1,
        hot_size_log2=hot_size.bit_length() - 1, hot_nnz=batch.hot_nnz,
        wire_dedup="on" if wire == "dict" else "off", hot_impl="mxu",
        microbatch=microbatch,
    )
    mesh = make_mesh(1)
    model, opt = make_model(cfg), make_optimizer(cfg)
    step = TrainStep(model, opt, cfg, mesh)
    assert step.wire_format == wire
    state = init_state(model, opt, cfg, mesh)
    eqns = list(_eqns(jax.make_jaxpr(step._train_impl)(
        state, step.put_batch(cb if wire == "dict" else batch)
    ).jaxpr))
    through_dict = wire == "dict" and microbatch == 1
    sorts = [
        [v.aval.shape for v in e.invars]
        for e in eqns if e.primitive.name == "sort"
    ]
    assert sorts == ([[(b * kc,)]] if through_dict else []), sorts
    shapes = {
        name: t["param"].shape for name, t in state["tables"].items()
    }
    assert len(shapes) == 2  # w [T, 1] and v [T, D]
    d = shapes["v"][1]
    adds = [
        tuple(v.aval.shape for v in e.invars[:2])
        for e in eqns if e.primitive.name == "scatter-add"
    ]
    # nothing accumulates anywhere but in a table's gradient buffer and,
    # on the route, in the dictionary's
    buffers = set(shapes.values())
    if through_dict:
        buffers.add((len(cb.cu), d))
        assert adds.count(((len(cb.cu), d), (b * kc, 1))) == 1, adds
    assert {operand for operand, _ in adds} == buffers, adds
    slots = b * kc // microbatch
    for name, shape in shapes.items():
        # (the head's rows [0, H) join the buffer as one add at index 0)
        cold = [i[0] for o, i in adds if o == shape and i != (1,)]
        want = caps if through_dict and name == "v" else slots
        assert cold == [want], (name, adds)


def _scatter_case(data, hot, d, integers, lane_select):
    """(the [T, D] buffer the dictionary route leaves, the per-slot
    scatter-add's, the float64 sums) for one decode case and made-up
    occurrence gradients, a masked slot's left non-zero.  The planes of
    the dictionary and of the tail are three and five entries LONGER than
    the batch needs (zeros, as TrainStep._settle_planes pads them), and
    without a head one live cold key is 0, the key every masked slot
    decodes to."""
    from xflow_tpu.parallel.step import (
        dict_cold_grads, dict_scatter_plan, expand_dict_wire,
    )

    batch, table, hot_size, dict_cap = _decode_case(data, hot, 3, table_log2=16)
    if not hot_size and data != "all_padding":
        batch.keys[3, 0] = 0  # row 3 is at full K
    cb = CompactBatch.from_batch(batch, table, hot_size, dict_cap=dict_cap)
    step = _decode_step(
        "lr", table, hot_size, batch.batch_size, batch.max_nnz,
        batch.hot_nnz,
    )
    wire = cb.wire(False)
    for name, extra in (("cw_cu", 3), ("cw_ct", 5)):
        plane = wire[name]
        wire[name] = np.pad(
            plane, [(0, extra)] + [(0, 0)] * (plane.ndim - 1)
        )
    m = batch.keys.size
    rng = np.random.default_rng(d + len(data))
    occ = (
        rng.integers(-8, 9, (m, d)) if integers
        else rng.standard_normal((m, d))
    ).astype(np.float32)

    def both(w, o):
        planes = expand_dict_wire(step.cfg, lane_select, w)
        keys_eff = jnp.where(
            planes["mask"] > 0, planes["keys"], table
        ).reshape(-1)
        zeros = jnp.zeros((table, d), jnp.float32)
        splan = dict_scatter_plan(planes["cold_plan"], table, lane_select)
        assert splan["rows"].shape == (len(cb.cu) + 3 + len(cb.ct) + 5,)
        return (
            zeros.at[splan["rows"]].add(
                dict_cold_grads(splan, o), mode="drop"
            ),
            zeros.at[keys_eff].add(o, mode="drop"),
        )

    got, want = jax.device_get(jax.jit(both)(wire, occ))
    live = batch.mask.reshape(-1) > 0
    exact = np.zeros((table, d))
    np.add.at(exact, batch.keys.reshape(-1)[live], occ[live].astype(np.float64))
    return got, want, exact


@pytest.mark.parametrize("d", [1, 10, 26])
@pytest.mark.parametrize("data", _DECODE_DATA)
@pytest.mark.parametrize("hot", ["none", "u12"])
def test_dict_cold_scatter_equals_per_slot_sums(hot, data, d):
    """The gradient buffer by the dictionary route (the occurrences of a
    dictionary key summed first, the tail's rows picked in stream order,
    cap(cu) + cap(ct) indices handed to the table: step.py::
    dict_scatter_plan, dict_cold_grads) equals the scatter-add per
    padded slot: exactly on integer-valued gradients, whose sums no
    order of adds can change, and within 1e-6 of the largest float64
    sum on random ones.  No dictionary beside a tail, no tail, nothing
    at all, rows at max_nnz, capacities that are no multiple of 128,
    planes longer than their content (the padding scatters nowhere: a
    clipped row would land on row 0, which the no-head cases make a live
    key), masked slots whose gradient is not zero."""
    from xflow_tpu.ops import window

    got, want, exact = _scatter_case(
        data, hot, d, True, window.lane_select_xla
    )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, exact)
    if data != "all_padding":
        assert np.abs(got).sum() > 0
    got, want, exact = _scatter_case(
        data, hot, d, False, window.lane_select_xla
    )
    scale = max(np.abs(exact).max(), 1.0)
    assert np.abs(got - exact).max() <= 1e-6 * scale
    assert np.abs(want - exact).max() <= 1e-6 * scale


@pytest.mark.parametrize("data", ["zipf", "tail_only", "odd_caps"])
def test_dict_cold_scatter_tpu_form_interpreted(data):
    """The same as a TPU traces the route: the dictionary index of each
    slot through the Mosaic lane shuffle (run here by the Pallas TPU
    interpreter)."""
    from jax.experimental.pallas import tpu as pltpu

    from xflow_tpu.ops import window

    with pltpu.force_tpu_interpret_mode():
        got, want, exact = _scatter_case(
            data, "u12", 10, True, window.lane_select_tpu
        )
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, exact)


@pytest.mark.parametrize("model", ["fm", "mvm"])
def test_dict_scatter_route_leaves_the_state_of_the_expanded_batch(model):
    """Three dense steps on a dictionary-wire batch, whose D > 1 table
    takes its cold gradients through the dictionary (PR 48), leave every
    table, the metrics and the trainer's eval where the same steps on
    the expanded batch over the plain compact wire (a scatter-add per
    padded slot) leave them: the same float32 adds of the same numbers
    in another order, so to 1e-6 and not by bits (on this backend, which
    adds in index order, they are the same bits: a dictionary key's
    occurrences meet an empty buffer either way)."""
    got, want = _dict_and_expanded_states(model, 3, "u12")
    jax.tree.map(
        lambda a, c: np.testing.assert_allclose(a, c, rtol=1e-6, atol=1e-7),
        got, want,
    )
    tables = got[0]["tables"]
    assert any(t["param"].shape[1] > 1 for t in tables.values())
    assert all(np.abs(t["z"]).sum() > 0 for t in tables.values())


def _touched_rows_batches(hot, table_log2=18):
    """Two dictionary-only batches of one decode case (random cold keys,
    every one in the dictionary: an EMPTY tail plane) over the same keys,
    so over the same dictionary and one train program: with a head, some
    cold keys lie below ``hot_size`` (the head's overflow into the cold
    section); the second batch gives a fifth of the first's real examples
    the weight 0, so rows that took a gradient in a step take an exactly
    zero one in the next."""
    batch, table, hot_size, _ = _decode_case("full_rows", hot, 3, table_log2)
    rng = np.random.default_rng(5)
    if hot_size:
        spill = (rng.random(batch.keys.shape) < 0.1) & (batch.mask > 0)
        batch.keys = np.where(
            spill, rng.integers(0, hot_size, batch.keys.shape), batch.keys
        ).astype(np.int32)
    cbs = []
    for weights in (
        batch.weights,
        batch.weights * (rng.random(batch.batch_size) < 0.8),
    ):
        batch.weights = weights.astype(np.float32)
        batch.labels = batch.labels * batch.weights
        cbs.append(CompactBatch.from_batch(batch, table, hot_size))
    a, b = cbs
    assert len(a.ct) == 0 and 0 < a.n_dict < len(a.cu)  # sentinel padding
    np.testing.assert_array_equal(a.cu, b.cu)
    keys = batch.keys[batch.mask > 0]
    assert not hot_size or (keys < hot_size).any()
    return a, b, table, hot_size


# D = 10 and 16; with a head (whose overflow reaches the cold section)
# and without; one table (mvm's v), and two of which w's one column is
# not selected (fm)
@pytest.mark.parametrize("model,d,hot", [
    ("mvm", 10, "u12"), ("mvm", 16, "none"),
    ("fm", 10, "none"), ("fm", 16, "u12"),
])
def test_touched_rows_pass_leaves_the_dense_pass_state_bit_for_bit(
    monkeypatch, model, d, hot
):
    """Three chained dense steps on dictionary-only batches: a table
    that the rule selects (step.py::touched_rows_selects: 2 to 64
    columns, an empty tail plane, large enough for its index count) gets
    no [T, D] gradient buffer and takes FTRL on the dictionary's rows
    and on the head alone (TrainStep._touched_rows_pass), and leaves
    every array of the state, the metrics and the eval BIT FOR BIT where
    the dense pass over a zeroed buffer leaves them (the same step with
    the size constant out of reach).  Among the rows: head rows named by
    the dictionary, capacity padding beyond ``n_dict``, rows whose
    summed gradient is exactly 0 before any gradient reached them (their
    ``param`` keeps its initial draw) and after one did (``param``
    recomputed from ``(z, n)``); fm's ``w`` of one column keeps its
    buffer in both.  Traced with the MXU head, the form a TPU runs at
    D = 10.  The "seg" head of other backends is one scatter-add into
    zeros, and there the two arms stand an ulp apart on a head row with
    two hot occurrences and a cold one (rows 3 and 105 of this batch, at
    D = 16): the CPU's compiler writes the dense arm's ``buffer[:H] +
    scatter(zeros, keys, g)`` as a scatter into the buffer, (cold + h1)
    + h2 for cold + (h1 + h2)."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel import step as step_mod
    from xflow_tpu.parallel.mesh import make_mesh

    first, second, table, hot_size = _touched_rows_batches(hot)
    cfg = Config(
        model=model, v_dim=d, batch_size=first.batch_size,
        max_nnz=first.max_nnz, table_size_log2=table.bit_length() - 1,
        num_devices=1, wire_dedup="on", hot_nnz=first.hot_nnz,
        hot_size_log2=hot_size.bit_length() - 1 if hot_size else 0,
        hot_impl="mxu",
    )
    mesh = make_mesh(1)
    m, opt = make_model(cfg), make_optimizer(cfg)
    out = {}
    for arm in ("touched", "dense"):
        if arm == "dense":
            monkeypatch.setattr(
                step_mod, "TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX", 1 << 40
            )
        step = step_mod.TrainStep(m, opt, cfg, mesh)
        state = step_mod.init_state(m, opt, cfg, mesh)
        fed = step.put_batch(first)
        eqns = list(_eqns(jax.make_jaxpr(step._train_impl)(state, fed).jaxpr))
        into_v = {
            name: sum(
                e.primitive.name == name
                and e.invars[0].aval.shape == (table, d)
                for e in eqns
            )
            for name in ("scatter", "scatter-add")
        }
        # param, n, z set at the dictionary's rows and no buffer to add
        # into; or one add of those rows (and, with a head, one of its H)
        assert into_v == (
            {"scatter": 3, "scatter-add": 0} if arm == "touched"
            else {"scatter": 0, "scatter-add": 1 + bool(hot_size)}
        ), (arm, into_v)
        seen = [jax.device_get(state)]
        for cb in (first, second, first):
            state, metrics = step.train(state, step.put_batch(cb))
            seen.append(jax.device_get(state))
        pctr = step.predict(state, step.put_batch(first))
        out[arm] = jax.device_get((seen, metrics, pctr))
    jax.tree.map(
        lambda a, c: np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(c).view(np.uint32)
        ),
        out["touched"], out["dense"],
    )
    (start, one, two, _), _, _ = out["touched"]
    expanded = first.expand()
    rows = np.unique(expanded.keys[expanded.mask > 0])
    v = {k: [s["tables"]["v"][k][rows] for s in (start, one, two)]
         for k in ("param", "n", "z")}
    assert (v["n"][1] > 0).any() and (np.abs(v["z"][2]).sum() > 0)
    never = (v["n"][1] == 0).all(axis=1)  # live, and no gradient yet
    assert never.any()
    np.testing.assert_array_equal(v["param"][1][never], v["param"][0][never])
    still = ((v["n"][2] == v["n"][1]) & (v["n"][1] > 0)).all(axis=1)
    assert still.any()  # a zero gradient after a real one
    np.testing.assert_array_equal(v["z"][2][still], v["z"][1][still])
    if hot_size:
        assert (rows < hot_size).any() and (v["n"][1][rows < hot_size] > 0).any()


def test_touched_rows_rule_selects_emb_of_the_three_b16384_cells_alone():
    """step.py::touched_rows_selects over the geometries of the eight
    benchmark configurations (benchmarks/configs/*.json; the dictionary's
    and the tail's plane capacities of one real batch of each cell, PERF.md
    section 5): exactly ``emb`` of xDeepFM, AutoInt and FiBiNET, 55 296
    indices into [2^25, 10 or 16] and an empty tail.  LR's and every
    ``w`` has one column, MVM's, DCN's and LR's batches have tails, FFM's
    ``v`` has 160 columns and is off the head, the FM mesh ships no plan;
    and a [2^16, 10] table under a dictionary-only batch is too small for
    its index count: the dense pass is cheaper there."""
    from benchmarks.harness import manifest
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import (
        TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX, TrainStep, padded_columns,
        touched_rows_selects,
    )

    caps = {
        "lr_ftrl_criteo_tb": (53248, 294912),
        "fm_ftrl_criteo_tb": None,  # four chips: the compact wire, no plan
        "mvm_ftrl_criteo_tb": (43008, 294912),
        "ffm_ftrl_criteo_tb": (53248, 0),
        "dcn_ftrl_criteo_tb": (40960, 131072),
        "xdeepfm_ftrl_criteo_tb": (55296, 0),
        "autoint_ftrl_criteo_tb": (55296, 0),
        "fibinet_ftrl_criteo_tb": (55296, 0),
    }
    selected = {}
    for name, planes in caps.items():
        doc = manifest.config_file(f"benchmarks/configs/{name}.json")
        fields = {
            k: v for k, v in manifest.apply_rehearsal(doc, False).items()
            if k not in manifest.CONFIG_META
        }
        if planes is None:
            assert fields["num_devices"] == 4
            continue
        cfg = Config(**fields)
        step = TrainStep(make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1))
        assert step.dict_wire and cfg.hot_size
        selected[name] = sorted(step._touched_rows_names(*planes, True))
    assert selected == {
        name: ["emb"] if name.split("_")[0] in ("xdeepfm", "autoint", "fibinet")
        else []
        for name in selected
    }
    assert [padded_columns(d) for d in (1, 8, 10, 16, 26, 160)] == [
        8, 8, 16, 16, 32, 160
    ]
    # the three cells stand at 9 709 padded elements an index
    assert (1 << 25) * 16 // 55296 == 9709 > TOUCHED_ROWS_MIN_ELEMENTS_PER_INDEX
    assert touched_rows_selects(1 << 25, 10, 55296, 0)
    assert not touched_rows_selects(1 << 16, 10, 55296, 0)   # a small table
    assert not touched_rows_selects(1 << 16, 10, 256, 0)
    assert touched_rows_selects(1 << 17, 10, 256, 0)
    assert not touched_rows_selects(1 << 25, 10, 55296, 256)  # a tail
    assert not touched_rows_selects(1 << 28, 1, 53248, 0)     # one column
    assert not touched_rows_selects(1 << 25, 160, 53248, 0)   # too wide


def test_the_benchmark_reads_the_touched_rows_counter_or_nothing():
    """benchmarks/layer_metrics/touched_rows_indices_per_step.py: the mean
    of the epochs' ``_wire`` rows that carry the counter, and NOTHING (no
    raise) on a run of a program older than the counter, which is what the
    driver's traced run of the parent hands it; its BENCHMARK.json entry
    lists the three cells whose ``emb`` the rule selects and says what the
    module says."""
    import json

    from benchmarks.layer_metrics import touched_rows_indices_per_step as reader

    assert reader.read({}) is None
    assert reader.read({"epochs": [{"_wire": {"format": "dict"}}, {}]}) is None
    assert reader.read({"epochs": [
        {"_wire": {"touched_rows_indices_per_step": 55296}},
        {"_wire": {"touched_rows_indices_per_step": 55296}},
        {"seconds": 1.0},
    ]}) == 55296
    assert reader.read(
        {"epochs": [{"_wire": {"touched_rows_indices_per_step": 0}}]}
    ) == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == reader.__name__.rsplit(".", 1)[-1])
    assert entry == {
        "name": "touched_rows_indices_per_step", "unit": reader.UNIT,
        "better": "higher", "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": [
            "xdeepfm_tb.train_packed", "autoint_tb.train_packed",
            "fibinet_tb.train_packed",
        ],
    }


@pytest.mark.parametrize("wire,microbatch,model,table_log2", [
    ("dict", 1, "lr", 14), ("dict", 4, "lr", 14), ("compact", 1, "lr", 14),
    ("dict", 1, "fm", 14), ("dict", 4, "fm", 14),
    ("dict", 1, "fm", 20), ("dict", 4, "fm", 20), ("dict", 1, "lr", 20),
])
def test_the_wire_row_counts_what_the_cold_gather_asks_of_the_table(
    toy_dataset, tmp_path, wire, microbatch, model, table_log2
):
    """The epoch's ``wire`` row says how far the dictionary route
    engages, from shapes: ``table_gather_indices_per_step`` beside
    ``padded_cold_slots_per_step`` (B * max_nnz).  A step that reads
    the dictionary wire's plan hands the table the dictionary's and the
    tail's capacities; a plain-compact batch, and a dictionary-wire
    batch cut into microbatch slices (which go without the plan), a row
    per padded slot: ratio 1.0.  ``table_scatter_indices_per_step`` is
    the way back, summed over the tables (PR 48): the same capacities
    for a table wider than one column where the step read the plan (FM's
    ``v``), the padded slots for a one-column table (LR's and FM's
    ``w``) and for every table of every other batch.  At 2^20 rows FM's
    ``v`` is large enough for a dictionary of at most 1 536 entries (these
    batches have no tail) to take the optimizer on the dictionary's rows
    alone (step.py::touched_rows_selects): it gets no gradient buffer,
    leaves ``table_scatter_indices_per_step`` and is counted by
    ``touched_rows_indices_per_step``, the dictionary's capacity as
    shipped; 0 at 2^14 rows, for LR's one column and under microbatch
    slices."""
    from xflow_tpu.obs import schema
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        model=model, train_path=toy_dataset.train_prefix, epochs=1,
        batch_size=64, table_size_log2=table_log2, max_nnz=24, num_devices=1,
        wire_dedup="on" if wire == "dict" else "off",
        microbatch=microbatch, metrics_out=str(tmp_path / "m.jsonl"),
    )
    trainer = Trainer(cfg)
    try:
        assert trainer.step.wire_format == wire
        caps, shipped = [], []
        book = trainer.step._book_wire

        def spy(nbytes, examples, cb=None, **shapes):
            if cb is not None:
                caps.append(len(cb.cu) + len(cb.ct))
                assert len(cb.ct) == 0
                shipped.append(sum(shapes["plane_caps"]))
            book(nbytes, examples, cb=cb, **shapes)

        trainer.step._book_wire = spy
        stats = trainer.train_epoch()
    finally:
        trainer.close()
    row = stats["_wire"]
    slots = row["padded_cold_slots_per_step"]
    tables = {"lr": 1, "fm": 2}[model]
    assert slots == 64 * 24
    if wire == "dict" and microbatch == 1:
        assert len(caps) == stats["steps"]
        assert row["table_gather_indices_per_step"] == round(
            sum(caps) / len(caps)
        ) < slots
        touched = model == "fm" and table_log2 == 20
        # w per padded slot; v, where there is one, per entry, to its
        # buffer or, where it has none, to the touched-rows application
        assert row["table_scatter_indices_per_step"] == round(
            slots + (tables - 1 - touched) * sum(caps) / len(caps)
        ) <= tables * slots
        assert row["touched_rows_indices_per_step"] == round(
            touched * sum(shipped) / len(shipped)
        )
        assert all(a >= b for a, b in zip(shipped, caps))
        assert touched == bool(row["touched_rows_indices_per_step"])
    else:
        assert row["table_gather_indices_per_step"] / slots == 1.0
        assert row["table_scatter_indices_per_step"] == tables * slots
        assert row["touched_rows_indices_per_step"] == 0
    assert not schema.validate_row({"t": 0.0, "kind": "wire", **row})


def test_dict_wire_eligibility_gates():
    common = dict(batch_size=64, table_size_log2=14, num_devices=1)
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep

    def mk(**kw):
        cfg = Config(**common, **kw)
        return TrainStep(
            make_model(cfg), make_optimizer(cfg), cfg, make_mesh(1)
        )

    assert mk(model="lr").dict_wire
    assert mk(model="mvm").dict_wire
    # numeric mode carries values -> no compaction
    assert not mk(model="lr", hash_mode=False).dict_wire
    # u8 count planes bound the row widths
    assert not mk(model="lr", max_nnz=300).dict_wire
    # multi-device mesh: stream planes have no batch-axis sharding
    cfg = Config(
        model="lr", batch_size=64, table_size_log2=14, num_devices=2
    )
    step = TrainStep(
        make_model(cfg), make_optimizer(cfg), cfg, make_mesh(2)
    )
    assert not step.dict_wire
    with pytest.raises(ValueError, match="wire_dedup"):
        mk(model="lr", hash_mode=False, wire_dedup="on")


def test_serve_engine_pins_dict_wire_off(toy_dataset):
    """Serving must keep content-independent wire shapes (the
    one-compile-per-bucket guarantee), so the engine disables the
    dict wire regardless of eligibility."""
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.trainer import Trainer

    cfg = Config(
        model="lr", train_path=toy_dataset.train_prefix,
        batch_size=64, table_size_log2=14, max_nnz=24, num_devices=1,
        epochs=1,
    )
    t = Trainer(cfg)
    assert t.step.dict_wire  # the training feed does compact
    eng = PredictEngine(cfg, t.state, buckets=(1, 8))
    assert not eng.step.dict_wire
    eng.warm()
    n = eng.compile_count
    eng.predict(eng.featurize_raw([np.asarray([1, 2, 3])]))
    assert eng.compile_count == n
    t.close()


# -- tier-1 wiring ---------------------------------------------------------


def test_dedup_select_pathological_cap_truncates():
    """More than dict_cap keys EACH repeating > dict_cap times (so the
    count histogram can't separate them): selection truncates to
    dict_cap instead of overflowing the capped planes."""
    keys = np.repeat(np.arange(9, dtype=np.int64), 6)  # 9 keys x 6 > cap 4
    uniq, codes = _numpy_dedup(keys, 4)
    assert len(uniq) <= 4
    got, _ = _decode(keys, uniq, codes)
    np.testing.assert_array_equal(got, keys)


def test_engine_serves_wire_dedup_on_config_on_multi_device_mesh():
    """A wire_dedup='on' training config must still serve on a
    multi-device mesh: the engine overrides the step's wire, and the
    digest-locked artifact config keeps its identity."""
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state
    from xflow_tpu.serve.engine import PredictEngine

    cfg = Config(
        model="lr", batch_size=64, table_size_log2=14, max_nnz=16,
        num_devices=1, wire_dedup="on",
    )
    mesh2 = make_mesh(2)
    state = init_state(
        make_model(cfg), make_optimizer(cfg), cfg, mesh2
    )
    eng = PredictEngine(
        cfg, state, mesh=mesh2, buckets=(2,), warm=False
    )
    assert not eng.step.dict_wire
    assert eng.cfg.wire_dedup == "on"  # artifact identity untouched
    assert eng.digest == cfg.digest()
    out = eng.predict(eng.featurize_raw([np.asarray([1, 2, 3])]))
    assert out.shape == (1,)


def test_check_wire_roundtrip_script():
    """Tier-1 wiring for scripts/check_wire_roundtrip.py (same pattern
    as check_metrics_schema/check_serve_smoke)."""
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "check_wire_roundtrip.py"),
        ],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr

"""ISSUE 40: a request batch crosses to the device as ONE packed buffer.
``step.pack_wire_np`` / ``unpack_wire`` round-trip every plane bit for bit;
the engine's packed put answers exactly what the trainer's plane-by-plane
``put_batch`` + ``predict`` answers, on one device and on two; the h2d leg
makes one transfer a batch and says so in the ``serve_stats`` row."""

import numpy as np
import pytest

from xflow_tpu.config import Config
from xflow_tpu.io.batch import Batch

BUCKETS = (1, 8, 64, 512)
H2D_FIELDS = ["h2d_transfers_mean", "h2d_bytes_mean"]


def _batch(rows, cold=12, hot=28, seed=0, binary=True):
    """A batch of the benchmark cell's widths, with padding entries and
    garbage under the mask."""
    rng = np.random.default_rng(seed)

    def section(k, top):
        mask = (rng.random((rows, k)) < 0.7).astype(np.float32)
        vals = mask
        if not binary:
            vals = rng.normal(size=(rows, k)).astype(np.float32)
        return (
            rng.integers(0, top, (rows, k)).astype(np.int32),
            rng.integers(0, 300, (rows, k)).astype(np.int32) - 20,
            vals, mask,
        )

    keys, slots, vals, mask = section(cold, 1 << 20)
    hk, hs, hv, hm = section(hot, 1 << 12)
    return Batch(
        keys=keys, slots=slots, vals=vals, mask=mask,
        labels=rng.integers(0, 2, rows).astype(np.float32),
        weights=rng.integers(0, 2, rows).astype(np.float32),
        hot_keys=hk, hot_slots=hs, hot_vals=hv, hot_mask=hm,
    )


def _wire(kind, batch):
    from xflow_tpu.parallel.step import compact_wire_np

    if kind == "full":
        return {
            "keys": batch.keys, "slots": batch.slots, "vals": batch.vals,
            "mask": batch.mask, "labels": batch.labels,
            "weights": batch.weights, "hot_keys": batch.hot_keys,
            "hot_slots": batch.hot_slots, "hot_vals": batch.hot_vals,
            "hot_mask": batch.hot_mask,
        }
    return compact_wire_np(
        batch, ship_slots=kind == "compact_slots",
        hot_u16=kind != "compact_i32",
    )


# -- (a) pack -> unpack, bit for bit ----------------------------------------

# bytes a row: the benchmark cell's compact wire is 48 + 56 + 1 + 1
ROW_BYTES = {
    "compact_u16": 106, "compact_i32": 162, "compact_slots": 146,
    "full": 648,
}


@pytest.mark.parametrize("rows", BUCKETS)
@pytest.mark.parametrize("kind", sorted(ROW_BYTES))
def test_pack_unpack_round_trips_every_plane(kind, rows):
    import jax

    from xflow_tpu.parallel.step import pack_wire_np, unpack_wire

    wire = _wire(kind, _batch(rows, seed=rows, binary=kind != "full"))
    buf, layout = pack_wire_np(wire)
    assert buf.dtype == np.uint8 and buf.shape == (rows, ROW_BYTES[kind])
    assert sorted(name for name, *_ in layout) == sorted(wire)
    # widest element first: every plane starts on its own alignment
    for _, dtype, _, off in layout:
        assert off % np.dtype(dtype).itemsize == 0
    out = jax.jit(unpack_wire, static_argnames="layout")(buf, layout=layout)
    assert sorted(out) == sorted(wire)
    for name, plane in wire.items():
        got = np.asarray(out[name])
        assert got.dtype == plane.dtype and got.shape == plane.shape, name
        assert got.tobytes() == plane.tobytes(), name


def test_layout_follows_from_shapes_alone():
    from xflow_tpu.parallel.step import pack_wire_np

    _, first = pack_wire_np(_wire("compact_u16", _batch(8, seed=1)))
    _, second = pack_wire_np(_wire("compact_u16", _batch(8, seed=2)))
    assert first == second and hash(first) == hash(second)


# -- (b) the packed put answers what the plane route answers ----------------

FAMILIES = {
    "lr": {"hot_size_log2": 6, "hot_nnz": 4},  # hot_ckeys_u16 rides
    "mvm": {},  # the slots plane rides
    "two_tower": {},
}


def _engine(model, devices=1, buckets=(4, 8), **over):
    """An engine over random parameters: no trainer, no data."""
    import jax

    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import init_state
    from xflow_tpu.serve.engine import PredictEngine

    cfg = Config(**{
        "model": model, "table_size_log2": 10, "batch_size": 8, "max_nnz": 8,
        "max_fields": 8, "tower_split_field": 4, "tower_dim": 4,
        "num_devices": devices, **FAMILIES.get(model, {}), **over,
    })
    mesh = make_mesh(devices)
    state = init_state(make_model(cfg), make_optimizer(cfg), cfg, mesh)
    rng = np.random.default_rng(11)
    for table in state["tables"].values():  # LR's start at zero: all 0.5
        param = table["param"]
        table["param"] = jax.device_put(
            rng.normal(size=param.shape).astype(np.float32), param.sharding
        )
    remap = (
        np.random.default_rng(5).permutation(cfg.table_size).astype(np.int32)
        if cfg.hot_size_log2 else None
    )
    return PredictEngine(cfg, state, remap=remap, mesh=mesh, buckets=buckets)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.integers(0, 1024, 6).astype(np.int64),
            rng.integers(0, 8, 6).astype(np.int32),
            None,
        )
        for _ in range(n)
    ]


def _item_index(engine, n=6):
    rng = np.random.default_rng(0)
    dim = engine.model.index_dim
    return {
        "count": n, "dim": dim,
        "item_index": rng.normal(size=(n, dim)).astype(np.float32),
        "item_ids": (10 + np.arange(n)).astype(np.int64),
    }


def _plane_route(engine, impl, batch, *extra):
    """``impl(state, *extra, arrays)`` over the trainer's own put: a
    transfer a plane, no packed buffer."""
    import jax

    arrays = engine.step.put_batch(batch, predict=True)
    return jax.tree.map(
        np.asarray,
        jax.device_get(jax.jit(impl)(engine.state, *extra, arrays)),
    )


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("model", sorted(FAMILIES))
def test_predict_prepared_equals_the_plane_route(model, devices):
    engine = _engine(model, devices)
    for n in (3, 7):  # one batch in each bucket
        batch = engine.featurize(_requests(n, seed=n))
        want = _plane_route(engine, engine.step._predict_impl, batch)
        got = engine.predict_prepared(batch)
        assert got.dtype == want.dtype and np.array_equal(got, want)
        # not all one score: the planes reached the model
        assert len(np.unique(got[:n])) > 1


@pytest.mark.parametrize("devices", [1, 2])
def test_topk_and_item_embeddings_equal_the_plane_route(devices):
    import jax

    engine = _engine("two_tower", devices)
    engine.attach_item_index(_item_index(engine), topk_k=3)
    step, model = engine.step, engine.model

    def tower(embed):
        def impl(state, arrays):
            batch = step._expand_wire(arrays)
            rows = step._gather_model_rows(state["tables"], batch)
            return embed(rows, step._model_view(batch), state["dense"])

        return impl

    def topk(state, index, arrays):
        u = tower(model.user_embed)(state, arrays)
        vals, idx = jax.lax.top_k(u @ index.T, engine.topk_k)
        return vals, idx, u

    rows = _requests(5, seed=3)
    batch = engine.featurize(rows)
    vals, idx, u = _plane_route(engine, topk, batch, engine._index_arr)
    ids, got_vals, got_u = engine.topk_prepared(batch)
    assert np.array_equal(got_vals, vals) and np.array_equal(got_u, u)
    assert np.array_equal(ids, engine.item_index["item_ids"][idx])
    want = _plane_route(engine, tower(model.item_embed), batch)
    assert np.array_equal(engine.item_embeddings(rows), want[: len(rows)])


# -- (c) one transfer a batch, one compile a bucket -------------------------


@pytest.mark.parametrize("model", sorted(FAMILIES))
def test_h2d_leg_makes_one_transfer_a_batch(model, monkeypatch):
    import jax
    import jax.numpy as jnp

    engine = _engine(model)
    engine.warm()
    batch = engine.featurize(_requests(7))
    puts, hops = [], []

    def counting(calls, real):
        return lambda x, *a, **k: calls.append(x) or real(x, *a, **k)

    # the trainer's own put for this batch: a hop and a call a plane
    monkeypatch.setattr(jax, "device_put", counting(puts, jax.device_put))
    monkeypatch.setattr(jnp, "asarray", counting(hops, jnp.asarray))
    planes = engine.step.put_batch(batch, predict=True)
    assert len(puts) == len(hops) == len(planes) >= 3
    # the engine's: one buffer, straight from numpy
    del puts[:], hops[:]
    engine.predict_prepared(batch)
    (buf,) = puts
    assert hops == []
    assert isinstance(buf, np.ndarray) and buf.dtype == np.uint8
    assert buf.shape[0] == batch.batch_size
    assert engine.last_h2d == {"transfers": 1, "bytes": buf.nbytes}


def test_one_compile_a_bucket_replicas_sharing_it():
    engine = _engine("lr")
    engine.warm()
    assert engine.compile_count == len(engine.buckets) == 2
    replica = engine.clone()
    assert replica._compiled is engine._compiled
    for n in (1, 3, 4, 5, 8):
        batch = replica.featurize(_requests(n, seed=n))
        assert np.array_equal(
            replica.predict_prepared(batch), engine.predict_prepared(batch)
        )
    assert engine.compile_count == replica.compile_count == 2
    # more rows than the largest bucket: chunks, no new shape
    engine.predict(engine.featurize_raw(_requests(19)))
    assert engine.compile_count == 2


def test_wire_counters_still_book_with_a_live_obs():
    from xflow_tpu.obs import Obs

    engine = _engine("mvm")
    engine.warm()
    obs = Obs()
    engine.obs = engine.step.obs = obs
    batch = engine.featurize(_requests(5))
    engine.predict_prepared(batch)
    snap = obs.registry.snapshot()
    assert snap.counters["wire.examples"] == 5
    assert snap.counters["wire.batches"] == 1
    # the planes' bytes ARE the buffer's, and the slots plane is counted
    assert snap.counters["wire.bytes"] == engine.last_h2d["bytes"]
    assert snap.counters["wire.slots_bytes"] == batch.keys.size
    assert snap.counters["phase.h2d"] > 0


# -- (d) the compact-wire check stays inside the leg ------------------------


def test_value_carrying_request_is_refused_inside_the_h2d_leg():
    engine = _engine("lr")
    engine.warm()
    keys = np.arange(4, dtype=np.int64)
    batch = engine.featurize([(keys, None, np.full(4, 2.0, np.float32))])
    engine.last_device_phases = engine.last_h2d = None
    with pytest.raises(ValueError, match="binary features"):
        engine.predict_prepared(batch)
    # refused before anything crossed or ran
    assert engine.last_h2d is None and engine.last_device_phases is None
    assert engine.compile_count == 2


# -- (e) the serve_stats row -------------------------------------------------


@pytest.fixture(scope="module")
def stats_row():
    from xflow_tpu.serve.fleet import ReplicaFleet

    fleet = ReplicaFleet(
        _engine("lr", buckets=(8,)), replicas=1, max_wait_ms=2.0
    )
    try:
        for burst in range(4):
            futs = [fleet.submit(*r) for r in _requests(5, burst)]
            for f in futs:
                f.result(timeout=60)
        return fleet.emit_stats()["stats"]
    finally:
        fleet.close()


def test_stats_row_says_one_transfer_a_batch(stats_row):
    assert stats_row["batches"] >= 1
    assert stats_row["h2d_transfers_mean"] == 1.0
    # bucket 8 of (cold 8 x int32) + (hot 4 x uint16) + labels + weights
    assert stats_row["h2d_bytes_mean"] == 8 * (32 + 8 + 1 + 1)


@pytest.mark.parametrize("stream", ["new", "old"])
def test_stats_row_validates_with_and_without_the_fields(stats_row, stream):
    from xflow_tpu.obs.schema import validate_row

    row = stats_row
    assert set(H2D_FIELDS) <= set(row)
    if stream == "old":  # a stream from before ISSUE 40
        row = {k: v for k, v in row.items() if k not in H2D_FIELDS}
    assert validate_row({"t": 0.0, "kind": "serve_stats", **row}) == []


def test_stats_row_of_an_engine_without_the_counter_reads_zero():
    from xflow_tpu.obs.registry import MetricsRegistry
    from xflow_tpu.serve.batcher import stats_row_from_snapshot

    reg = MetricsRegistry()
    reg.counter_add("serve.batches", 3.0)
    row = stats_row_from_snapshot(reg.snapshot())
    assert row["h2d_transfers_mean"] == row["h2d_bytes_mean"] == 0.0

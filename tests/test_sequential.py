"""update_mode='sequential' is step-for-step the same training as a
sequence of dense steps of batch_size/microbatch examples (the scan
carries the tables; gradients divide by the slice's real count) —
the property that lets one device dispatch compose with the proven
small-batch FTRL convergence (config.update_mode docstring)."""

import numpy as np
import jax
import pytest

from xflow_tpu.config import Config
from xflow_tpu.io.batch import make_batch
from xflow_tpu.models import make_model
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, init_state

B, M, K = 64, 4, 12  # superbatch, slice count, padded nnz


def rand_batch(rng, b, hot_size=0, hot_nnz=0, table=1 << 12, fields=8):
    keys = rng.integers(0, table, (b, K)).astype(np.int32)
    slots = rng.integers(0, fields, (b, K)).astype(np.int32)
    vals = rng.uniform(0.5, 1.5, (b, K)).astype(np.float32)
    mask = (rng.uniform(size=(b, K)) < 0.8).astype(np.float32)
    labels = (rng.uniform(size=b) < 0.4).astype(np.float32)
    weights = np.ones(b, np.float32)
    weights[-3:] = 0.0  # pad examples in the last slices
    return keys, slots, vals, mask, labels, weights


def slice_rows(arrs, j, m):
    """Interleaved slice j (example i -> slice i % m), matching
    parallel.step._interleaved_slices."""
    return tuple(a[j::m] for a in arrs)


def build(model, cfg):
    mesh = make_mesh(cfg.num_devices)
    mdl = make_model(cfg)
    opt = make_optimizer(cfg)
    step = TrainStep(mdl, opt, cfg, mesh)
    return step, init_state(mdl, opt, cfg, mesh)


def base_cfg(model, **kw):
    d = dict(
        model=model,
        batch_size=B,
        table_size_log2=12,
        max_nnz=K,
        max_fields=8,
        num_devices=1,
        wire_mode="full",
        emb_dim=4,
        hidden_dim=8,
        ffm_v_dim=2,
    )
    d.update(kw)
    return Config(**d)


@pytest.mark.parametrize(
    "model,kw",
    [
        ("lr", {}),
        ("fm", {}),
        ("mvm", {}),
        ("ffm", {}),
        ("wide_deep", {}),
        ("lr", {"hot_size_log2": 8, "hot_nnz": 6}),
        ("lr", {"optimizer": "sgd"}),
    ],
)
def test_sequential_equals_dense_sequence(model, kw):
    rng = np.random.default_rng(7)
    raw = rand_batch(rng, B)
    hot_size = (1 << kw["hot_size_log2"]) if kw.get("hot_size_log2") else 0
    hot_nnz = kw.get("hot_nnz", 0)

    seq_cfg = base_cfg(
        model, update_mode="sequential", microbatch=M, **kw
    )
    sstep, sstate = build(model, seq_cfg)
    sbatch = make_batch(*raw, hot_size, hot_nnz)
    sstate, smetrics = sstep.train(sstate, sstep.put_batch(sbatch))

    dense_cfg = base_cfg(
        model, update_mode="dense", batch_size=B // M, **kw
    )
    dstep, dstate = build(model, dense_cfg)
    nll, cnt = 0.0, 0.0
    for j in range(M):
        db = make_batch(*slice_rows(raw, j, M), hot_size, hot_nnz)
        dstate, dm = dstep.train(dstate, dstep.put_batch(db))
        c = float(jax.device_get(dm["count"]))
        nll += float(jax.device_get(dm["logloss"])) * c
        cnt += c

    for name in dstate["tables"]:
        for part in dstate["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(jax.device_get(sstate["tables"][name][part])),
                np.asarray(jax.device_get(dstate["tables"][name][part])),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )
    for key in dstate["dense"]:
        np.testing.assert_allclose(
            np.asarray(jax.device_get(sstate["dense"][key])),
            np.asarray(jax.device_get(dstate["dense"][key])),
            rtol=1e-5,
            atol=1e-6,
            err_msg=f"{model}:dense/{key}",
        )
    # dispatch-window metrics == weighted mean over the dense sequence
    assert float(jax.device_get(smetrics["count"])) == cnt
    np.testing.assert_allclose(
        float(jax.device_get(smetrics["logloss"])),
        nll / cnt,
        rtol=1e-5,
    )


def test_sequential_empty_slice_is_noop():
    """A slice of all-padding examples (weights 0 — multi-host step
    alignment feeds these) must leave the carried tables untouched."""
    rng = np.random.default_rng(3)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    weights = weights.copy()
    weights[1::M] = 0.0  # slice 1 entirely padding
    mask[1::M] = 0.0

    cfg = base_cfg("lr", update_mode="sequential", microbatch=M)
    step, state = build("lr", cfg)
    batch = make_batch(keys, slots, vals, mask, labels, weights)
    state, _ = step.train(state, step.put_batch(batch))

    dcfg = base_cfg("lr", update_mode="dense", batch_size=B // M)
    dstep, dstate = build("lr", dcfg)
    for j in [0, 2, 3]:  # skip the empty slice entirely
        db = make_batch(
            *slice_rows((keys, slots, vals, mask, labels, weights), j, M)
        )
        dstate, _ = dstep.train(dstate, dstep.put_batch(db))
    np.testing.assert_allclose(
        np.asarray(jax.device_get(state["tables"]["w"]["param"])),
        np.asarray(jax.device_get(dstate["tables"]["w"]["param"])),
        rtol=1e-5,
        atol=1e-7,
    )


def test_sequential_sharded_matches_single():
    rng = np.random.default_rng(11)
    raw = rand_batch(rng, B)
    out = {}
    for ndev in (1, 8):
        cfg = base_cfg(
            "lr", update_mode="sequential", microbatch=M, num_devices=ndev
        )
        step, state = build("lr", cfg)
        state, _ = step.train(state, step.put_batch(make_batch(*raw)))
        out[ndev] = np.asarray(
            jax.device_get(state["tables"]["w"]["param"])
        )
    np.testing.assert_allclose(out[1], out[8], rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("model", ["lr", "fm", "wide_deep"])
def test_sequential_sparse_inner_equals_dense_inner(model):
    """config.sequential_inner='sparse' (touched-rows-only per slice —
    the north-star-table form) is the same training as the dense
    inner."""
    rng = np.random.default_rng(13)
    raw = rand_batch(rng, B)
    out = {}
    for inner in ("dense", "sparse"):
        cfg = base_cfg(
            model,
            update_mode="sequential",
            microbatch=M,
            sequential_inner=inner,
        )
        step, state = build(model, cfg)
        state, _ = step.train(state, step.put_batch(make_batch(*raw)))
        out[inner] = jax.device_get(state)
    for name in out["dense"]["tables"]:
        for part in out["dense"]["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(out["sparse"]["tables"][name][part]),
                np.asarray(out["dense"]["tables"][name][part]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )
    for key in out["dense"]["dense"]:
        np.testing.assert_allclose(
            np.asarray(out["sparse"]["dense"][key]),
            np.asarray(out["dense"]["dense"][key]),
            rtol=1e-5,
            atol=1e-6,
        )


@pytest.mark.parametrize("model", ["lr", "fm", "ffm"])
def test_sequential_sparse_inner_hybrid_hot(model):
    """sparse inner + hot table (the hybrid, step.py::_sparse_update):
    cold keys keep the touched-rows path, the hot section gets a dense
    [H, D] head update, and hot rows that ALSO arrive through the cold
    planes (split_hot overflow spill) are folded into the hot buffer so
    every row sees exactly one summed-gradient update — the same
    training as the dense inner."""
    rng = np.random.default_rng(17)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    # force heavy hot-head traffic incl. per-row overflow: half the
    # columns draw from hot rows [0, 16), so rows carry more hot keys
    # than hot_nnz=4 and the excess spills into the cold planes with
    # row ids < H — the exactly-once case the hybrid must fold in
    keys[:, ::2] = rng.integers(0, 16, (B, (K + 1) // 2)).astype(np.int32)
    raw = (keys, slots, vals, mask, labels, weights)
    hot_size, hot_nnz = 1 << 8, 4
    out = {}
    for inner in ("dense", "sparse"):
        cfg = base_cfg(
            model,
            update_mode="sequential",
            microbatch=M,
            sequential_inner=inner,
            hot_size_log2=8,
            hot_nnz=hot_nnz,
        )
        step, state = build(model, cfg)
        state, _ = step.train(
            state, step.put_batch(make_batch(*raw, hot_size, hot_nnz))
        )
        out[inner] = jax.device_get(state)
    for name in out["dense"]["tables"]:
        for part in out["dense"]["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(out["sparse"]["tables"][name][part]),
                np.asarray(out["dense"]["tables"][name][part]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )


@pytest.mark.parametrize("model", ["lr", "fm", "wide_deep"])
def test_sequential_hot_inner_all_hot_equals_dense_inner(model):
    """sequential_inner='hot' with NO cold traffic (every key < H,
    hot_nnz >= per-row key count, so split_hot sends everything to the
    hot planes) is bit-for-bit true sequential training: the per-slice
    hot-head update IS the whole update, and the window-end cold pass
    runs on an all-zero gradient buffer (idempotent)."""
    rng = np.random.default_rng(19)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    keys = rng.integers(0, 1 << 8, (B, K)).astype(np.int32)
    raw = (keys, slots, vals, mask, labels, weights)
    hot_size, hot_nnz = 1 << 8, K
    out = {}
    for inner in ("dense", "hot"):
        cfg = base_cfg(
            model,
            update_mode="sequential",
            microbatch=M,
            sequential_inner=inner,
            hot_size_log2=8,
            hot_nnz=hot_nnz,
        )
        step, state = build(model, cfg)
        state, metrics = step.train(
            state, step.put_batch(make_batch(*raw, hot_size, hot_nnz))
        )
        out[inner] = (jax.device_get(state), jax.device_get(metrics))
    for name in out["dense"][0]["tables"]:
        for part in out["dense"][0]["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(out["hot"][0]["tables"][name][part]),
                np.asarray(out["dense"][0]["tables"][name][part]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )
    for key in out["dense"][0]["dense"]:
        np.testing.assert_allclose(
            np.asarray(out["hot"][0]["dense"][key]),
            np.asarray(out["dense"][0]["dense"][key]),
            rtol=1e-5,
            atol=1e-6,
        )
    np.testing.assert_allclose(
        float(out["hot"][1]["logloss"]),
        float(out["dense"][1]["logloss"]),
        rtol=1e-5,
    )


@pytest.mark.parametrize("model", ["lr", "fm"])
def test_sequential_hot_inner_singleton_cold_equals_dense_inner(model):
    """Hot-fine/cold-coarse's two divergences from true sequential —
    window-stale cold forward values and summed-gradient cold updates —
    both vanish when every cold key occurs exactly ONCE in the dispatch
    window (its pre-gathered value equals the live value at its slice,
    and a one-occurrence sum is the one gradient).  With unique cold
    keys and spill-free hot traffic, the hot inner must reproduce the
    dense inner exactly.  This pins the window-end pass: grads
    un-interleave to batch order, land post-writeback, exactly once."""
    rng = np.random.default_rng(23)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    nhot = (K + 1) // 2
    # even columns: hot rows [0, 256) with capacity hot_nnz = nhot (no
    # spill); odd columns: globally unique cold keys >= H
    keys[:, ::2] = rng.integers(0, 1 << 8, (B, nhot)).astype(np.int32)
    ncold = K - nhot
    uniq = (1 << 8) + np.arange(B * ncold, dtype=np.int32)
    keys[:, 1::2] = rng.permutation(uniq).reshape(B, ncold)
    raw = (keys, slots, vals, mask, labels, weights)
    hot_size, hot_nnz = 1 << 8, nhot
    out = {}
    for inner in ("dense", "hot"):
        cfg = base_cfg(
            model,
            update_mode="sequential",
            microbatch=M,
            sequential_inner=inner,
            hot_size_log2=8,
            hot_nnz=hot_nnz,
        )
        step, state = build(model, cfg)
        state, _ = step.train(
            state, step.put_batch(make_batch(*raw, hot_size, hot_nnz))
        )
        out[inner] = jax.device_get(state)
    for name in out["dense"]["tables"]:
        for part in out["dense"]["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(out["hot"]["tables"][name][part]),
                np.asarray(out["dense"]["tables"][name][part]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )


def test_sequential_hot_inner_sharded_matches_single():
    rng = np.random.default_rng(29)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    keys[:, ::2] = rng.integers(0, 1 << 8, (B, (K + 1) // 2)).astype(
        np.int32
    )
    raw = (keys, slots, vals, mask, labels, weights)
    out = {}
    for ndev in (1, 8):
        cfg = base_cfg(
            "lr",
            update_mode="sequential",
            microbatch=M,
            sequential_inner="hot",
            hot_size_log2=8,
            hot_nnz=4,
            num_devices=ndev,
        )
        step, state = build("lr", cfg)
        state, _ = step.train(
            state, step.put_batch(make_batch(*raw, 1 << 8, 4))
        )
        out[ndev] = np.asarray(
            jax.device_get(state["tables"]["w"]["param"])
        )
    np.testing.assert_allclose(out[1], out[8], rtol=1e-5, atol=1e-7)


def test_sequential_hot_inner_spill_trains():
    """With per-row hot overflow spilling into the cold planes (keys
    < H arriving cold), the hot inner defers those grads to the
    window-end pass — approximate vs true sequential by design
    (docstring), but every update must land exactly once and training
    must make progress.  Train a few windows on a learnable batch and
    check the loss moves down and all state stays finite."""
    rng = np.random.default_rng(31)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    # heavy hot traffic (8 of 12 columns) against hot_nnz=4 capacity —
    # guaranteed spill — and labels correlated with one hot key so
    # there is signal to learn
    keys[:, :8] = rng.integers(0, 16, (B, 8)).astype(np.int32)
    labels = (keys[:, 0] < 8).astype(np.float32)
    raw = (keys, slots, vals, mask, labels, weights)
    cfg = base_cfg(
        "lr",
        update_mode="sequential",
        microbatch=M,
        sequential_inner="hot",
        hot_size_log2=8,
        hot_nnz=4,
    )
    step, state = build("lr", cfg)
    batch = step.put_batch(make_batch(*raw, 1 << 8, 4))
    losses = []
    for _ in range(15):
        state, metrics = step.train(state, batch)
        losses.append(float(jax.device_get(metrics["logloss"])))
    assert losses[-1] < losses[0] - 0.03, losses
    for name, table in state["tables"].items():
        for part, arr in table.items():
            assert np.isfinite(np.asarray(jax.device_get(arr))).all(), (
                name,
                part,
            )


@pytest.mark.parametrize("model", ["lr", "fm"])
def test_hot_windowend_sparse_matches_dense(model):
    """Config.hot_windowend='sparse' routes the window-end cold-tail
    pass through the consolidated touched-rows update (ops/sparse.py)
    instead of a [T, D] buffer + full-table optimizer pass — the
    T=2^28 form (analysis rules XF010/XF014).  Same training on
    duplicate-heavy cold traffic WITH hot-overflow spill (cold-plane
    keys < H landing on the written-back head, exactly once)."""
    rng = np.random.default_rng(41)
    keys, slots, vals, mask, labels, weights = rand_batch(rng, B)
    # heavy hot traffic with spill (8 of 12 columns vs hot_nnz=4) AND
    # duplicate-heavy cold keys >= H
    keys[:, :8] = rng.integers(0, 16, (B, 8)).astype(np.int32)
    keys[:, 8:] = (
        (1 << 8) + rng.integers(0, 32, (B, K - 8))
    ).astype(np.int32)
    raw = (keys, slots, vals, mask, labels, weights)
    out = {}
    for windowend in ("dense", "sparse"):
        cfg = base_cfg(
            model,
            update_mode="sequential",
            microbatch=M,
            sequential_inner="hot",
            hot_size_log2=8,
            hot_nnz=4,
            hot_windowend=windowend,
        )
        step, state = build(model, cfg)
        assert step._windowend == windowend
        state, _ = step.train(
            state, step.put_batch(make_batch(*raw, 1 << 8, 4))
        )
        out[windowend] = jax.device_get(state)
    for name in out["dense"]["tables"]:
        for part in out["dense"]["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(out["sparse"]["tables"][name][part]),
                np.asarray(out["dense"]["tables"][name][part]),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )


def test_hot_windowend_auto_routes_by_table_size():
    """auto = dense below 2^24 (full-table pass is noise there),
    sparse from 2^24 up (the [T, D] transient is the hazard)."""
    small = base_cfg(
        "lr", update_mode="sequential", microbatch=M,
        sequential_inner="hot", hot_size_log2=8, hot_nnz=4,
    )
    step, _ = build("lr", small)
    assert step._windowend == "dense"
    big = small.replace(table_size_log2=24)
    mesh = make_mesh(big.num_devices)
    big_step = TrainStep(
        make_model(big), make_optimizer(big), big, mesh
    )
    assert big_step._windowend == "sparse"


def test_hot_inner_requires_hot_table():
    with pytest.raises(ValueError, match="hot"):
        base_cfg("lr", update_mode="sequential", sequential_inner="hot")


def test_hot_inner_rejects_mxu_opted_out_tables():
    """ffm opts its wide v table out of the MXU hot path
    (TableSpec.hot=False) — the hot inner carries every table's head
    in the scan, so TrainStep must refuse the combination up front."""
    cfg = base_cfg(
        "ffm",
        update_mode="sequential",
        microbatch=M,
        sequential_inner="hot",
        hot_size_log2=8,
        hot_nnz=4,
    )
    with pytest.raises(ValueError, match="opts table"):
        build("ffm", cfg)


def test_mxu_opted_out_inner_hot_legal_outside_sequential():
    """ADVICE round-5 low #2 regression: the hot-inner/opt-out check
    only applies when the hot inner RUNS (update_mode='sequential').
    ffm + dense mode + sequential_inner='hot' is a legal Config (the
    inner is an unused knob there) and must build and train."""
    rng = np.random.default_rng(43)
    raw = rand_batch(rng, B)
    cfg = base_cfg(
        "ffm",
        update_mode="dense",
        sequential_inner="hot",
        hot_size_log2=8,
        hot_nnz=4,
    )
    step, state = build("ffm", cfg)  # used to raise at build
    state, metrics = step.train(
        state, step.put_batch(make_batch(*raw, 1 << 8, 4))
    )
    assert np.isfinite(float(jax.device_get(metrics["logloss"])))


@pytest.mark.parametrize(
    "inner,hot",
    [("dense", False), ("sparse", False), ("sparse", True), ("hot", True)],
)
def test_sequential_microbatch_one_is_dense(inner, hot):
    """microbatch=1 degenerates to a single whole-batch update — via
    the dense pass or, with sequential_inner='sparse', the
    touched-rows-only path (which must not silently fall through to a
    full-table pass at north-star table sizes).  The hot-on case pins
    the degenerate path of the hybrid inner."""
    rng = np.random.default_rng(5)
    raw = rand_batch(rng, B)
    hot_kw = {"hot_size_log2": 8, "hot_nnz": 4} if hot else {}
    hot_args = (1 << 8, 4) if hot else ()
    states = {}
    for mode in ("sequential", "dense"):
        cfg = base_cfg(
            "lr", update_mode=mode, sequential_inner=inner, **hot_kw
        )
        step, state = build("lr", cfg)
        state, _ = step.train(
            state, step.put_batch(make_batch(*raw, *hot_args))
        )
        states[mode] = np.asarray(
            jax.device_get(state["tables"]["w"]["param"])
        )
    np.testing.assert_allclose(
        states["sequential"], states["dense"], rtol=1e-5, atol=1e-7
    )

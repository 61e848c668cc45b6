"""Retrieval→ranking cascade: registry, top-k engine mode, cascade
engine semantics, doctor diagnoses, and the tier-1 smoke gate
(scripts/check_cascade_smoke.py — trains both stages, serves the
cascade over HTTP, loadgens a zipf mix, checks parity/recompiles/
schema)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from xflow_tpu.config import Config
from xflow_tpu.models import (
    ModelFamily,
    make_model,
    model_family,
    model_names,
    register_model,
)

repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry ----------------------------------------------------------------


def test_registry_names_cover_all_families():
    assert set(model_names()) == {
        "lr", "fm", "mvm", "ffm", "wide_deep", "two_tower", "dcn", "xdeepfm",
        "autoint", "fibinet", "dlrm",
    }


def test_registry_unknown_model_actionable():
    with pytest.raises(ValueError, match="registered families"):
        Config(model="gbdt")
    with pytest.raises(ValueError, match="registered families"):
        model_family("gbdt")


def test_registry_refuses_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        register_model(ModelFamily("lr", lambda cfg: None, "dup"))


def test_registry_retrieval_flag():
    assert model_family("two_tower").retrieval
    assert not model_family("dcn").retrieval
    assert not model_family("lr").retrieval


def test_two_tower_split_validation():
    with pytest.raises(ValueError, match="tower_split_field"):
        Config(model="two_tower", tower_split_field=0)
    with pytest.raises(ValueError, match="tower_split_field"):
        Config(model="two_tower", max_fields=8, tower_split_field=8)
    with pytest.raises(ValueError, match="cross_layers"):
        Config(model="dcn", cross_layers=0)


# -- engine top-k mode -------------------------------------------------------


def _live_engine(model_name, **over):
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import init_state
    from xflow_tpu.serve.engine import PredictEngine

    base = dict(
        model=model_name,
        table_size_log2=10,
        batch_size=8,
        max_nnz=8,
        max_fields=8,
        tower_split_field=4,
        tower_dim=4,
        num_devices=1,
    )
    base.update(over)
    cfg = Config(**base)
    mesh = make_mesh(1)
    model = make_model(cfg)
    state = init_state(model, make_optimizer(cfg), cfg, mesh)
    return PredictEngine(cfg, state, mesh=mesh, buckets=(4, 8))


def _toy_index(n=6, dim=6, nnz=3, table_size=1024, seed=0):
    # dim = tower_dim + 2: the bias-lane augmentation widens tower
    # outputs by [bias, 1] (models/two_tower.py docstring)
    rng = np.random.default_rng(seed)
    return {
        "count": n,
        "dim": dim,
        "item_index": rng.normal(size=(n, dim)).astype(np.float32),
        "item_ids": (10 + np.arange(n)).astype(np.int64),
        "item_keys": rng.integers(0, table_size, (n, nnz)).astype(np.int64),
        "item_slots": np.full((n, nnz), 5, np.int32),
        "item_vals": np.ones((n, nnz), np.float32),
        "item_nnz": np.full(n, nnz, np.int32),
    }


def test_topk_refused_without_index():
    eng = _live_engine("two_tower")
    with pytest.raises(ValueError, match="no item index"):
        eng.topk_prepared(eng._empty_batch(4))


def test_attach_index_refused_for_non_retrieval_model():
    eng = _live_engine("dcn")
    with pytest.raises(ValueError, match="retrieval=False"):
        eng.attach_item_index(_toy_index())


def test_topk_matches_full_scan_and_never_recompiles():
    eng = _live_engine("two_tower")
    eng.attach_item_index(_toy_index(), topk_k=4)
    eng.warm()
    warm = eng.compile_count
    rng = np.random.default_rng(1)
    rows = [
        (rng.integers(0, 1024, 5).astype(np.int64),
         np.arange(5, dtype=np.int32) % 4, None)
        for _ in range(3)
    ]
    from xflow_tpu.io.batch import pad_batch_rows

    prepared = pad_batch_rows(
        eng._prepare(eng.featurize_raw(rows)), eng.bucket_for(3)
    )
    ids, scores, u = eng.topk_prepared(prepared)
    ids, scores, u = ids[:3], scores[:3], u[:3]
    full = u @ eng.item_index["item_index"].T
    order = np.argsort(-full, axis=1, kind="stable")[:, :4]
    np.testing.assert_allclose(
        scores, np.take_along_axis(full, order, axis=1), atol=1e-6
    )
    np.testing.assert_array_equal(ids, eng.item_index["item_ids"][order])
    # mixed k and mixed sizes slice the ONE compiled width — the
    # no-recompile guarantee covers top-k traffic too
    for k in (1, 2, 4):
        eng.topk(eng.featurize_raw(rows[:2]), k=k)
    assert eng.compile_count == warm
    with pytest.raises(ValueError, match="topk_k"):
        eng.topk(eng.featurize_raw(rows[:1]), k=5)


def test_clone_shares_index_and_compiles():
    eng = _live_engine("two_tower")
    eng.attach_item_index(_toy_index(), topk_k=2)
    eng.warm()
    rep = eng.clone()
    assert rep.item_index is eng.item_index
    assert rep.topk_k == eng.topk_k
    assert rep._compiled is eng._compiled


def test_item_embeddings_requires_item_tower():
    eng = _live_engine("lr")
    with pytest.raises(ValueError, match="item tower"):
        eng.item_embeddings([(np.asarray([1, 2]), None, None)])


# -- cascade engine ----------------------------------------------------------


def _toy_cascade(k=2, topk_k=4, index=None):
    from xflow_tpu.serve.cascade import CascadeEngine
    from xflow_tpu.serve.fleet import ReplicaFleet

    reng = _live_engine("two_tower")
    reng.attach_item_index(
        _toy_index() if index is None else index, topk_k=topk_k
    )
    reng.warm()
    keng = _live_engine("dcn")
    keng.warm()
    retrieval = ReplicaFleet(reng, 2, topk=True, revive=False)
    ranking = ReplicaFleet(keng, 2, revive=False)
    return CascadeEngine(retrieval, ranking, k=k)


def test_cascade_requires_topk_retrieval_stage():
    from xflow_tpu.serve.cascade import CascadeEngine
    from xflow_tpu.serve.fleet import ReplicaFleet

    keng = _live_engine("dcn")
    plain = ReplicaFleet(keng, 1, revive=False)
    with pytest.raises(ValueError, match="top-k fleet"):
        CascadeEngine(plain, plain, k=1)
    plain.close()


def test_cascade_ranks_candidates_and_books_stats():
    casc = _toy_cascade(k=3)
    try:
        res = casc.recommend(
            np.asarray([3, 7, 11], np.int64),
            np.asarray([0, 1, 2], np.int32),
        )
        assert len(res["items"]) == 3
        assert res["pctr"] == sorted(res["pctr"], reverse=True)
        assert set(res["items"]) <= set(
            int(i) for i in casc.retrieval.engines[0].item_index["item_ids"]
        )
        row = casc.emit_stats()
        assert row["requests"] == 1 and row["errors"] == 0
        assert row["starved"] == 0 and row["k_returned_mean"] == 3.0
        assert row["e2e_p99"] >= row["rank_p50"] >= 0
        from xflow_tpu.obs.schema import validate_row

        assert validate_row(dict(row, t=0.0, kind="cascade")) == []
    finally:
        casc.close()


def test_cascade_starvation_counted_not_failed():
    """k beyond the compiled top-k width (a rollout can shrink the
    index under live traffic): served best-effort with fewer
    candidates, counted as starvation — never a failed request."""
    casc = _toy_cascade(k=2, topk_k=3)
    try:
        res = casc.recommend(
            np.asarray([5, 9], np.int64), np.asarray([0, 1], np.int32),
            k=5,
        )
        assert len(res["items"]) == 3  # index width, not the asked 5
        row = casc.emit_stats()
        assert row["starved"] == 1 and row["errors"] == 0
    finally:
        casc.close()


# -- doctor ------------------------------------------------------------------


def _cascade_row(**over):
    row = {
        "t": 1.0, "kind": "cascade", "requests": 10, "errors": 0,
        "shed_total": 0, "starved": 0, "k": 5, "k_returned_mean": 5.0,
        "retrieval_p50": 0.002, "retrieval_p99": 0.004,
        "rank_p50": 0.008, "rank_p99": 0.020,
        "e2e_p50": 0.011, "e2e_p99": 0.024,
    }
    row.update(over)
    return row


def test_doctor_cascade_starvation_and_attribution():
    from xflow_tpu.obs.doctor import diagnose

    finds = diagnose([_cascade_row(starved=3, k_returned_mean=3.2)])
    codes = {d.code: d.severity for d in finds}
    assert codes.get("candidate_starvation") == "warn"
    # per-stage p99 attribution blames the dominant stage by name
    attach = [d for d in finds if d.code == "cascade_stage_p99"]
    assert attach and "ranking" in attach[0].message


def test_doctor_cascade_clean_run_is_clean():
    from xflow_tpu.obs.doctor import diagnose

    finds = diagnose([_cascade_row()])
    assert all(
        d.severity not in ("crit", "warn") for d in finds
    ), [f"{d.code}: {d.message}" for d in finds]


def test_doctor_cascade_errors_warn():
    from xflow_tpu.obs.doctor import diagnose

    finds = diagnose([_cascade_row(errors=2)])
    assert any(
        d.code == "cascade_errors" and d.severity == "warn" for d in finds
    )


# -- tier-1 gate -------------------------------------------------------------


def test_check_cascade_smoke_script():
    """The CI lint (scripts/check_cascade_smoke.py) passes — run as a
    subprocess exactly as CI would (tier-1 wiring, like
    check_serve_smoke.py)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo, "scripts", "check_cascade_smoke.py")],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        timeout=600,
        cwd=repo,
    )
    assert proc.returncode == 0, (
        f"check_cascade_smoke failed:\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}"
    )


def test_topk_fleet_rollout_refuses_indexless_candidate(tmp_path):
    """A top-k fleet must refuse a candidate artifact with no item
    index at the rollout gate — per-request failures after the swap
    would evict every replica."""
    from xflow_tpu.serve.fleet import ReplicaFleet

    reng = _live_engine("two_tower")
    reng.attach_item_index(_toy_index(), topk_k=2)
    reng.warm()
    fleet = ReplicaFleet(reng, 1, topk=True, revive=False)
    try:
        bare = _live_engine("two_tower")  # same cfg digest, no index
        with pytest.raises(ValueError, match="no item index"):
            fleet.begin_rollout(bare)
    finally:
        fleet.close()

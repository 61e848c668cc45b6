"""Dense and sparse update paths must produce identical training states
— same consolidation semantics, different execution strategies
(config.update_mode docstring)."""

import numpy as np
import jax
import pytest

from xflow_tpu.config import Config
from xflow_tpu.trainer import Trainer


def cfg_for(ds, mode, model="lr", **kw):
    base = dict(
        train_path=ds.train_prefix,
        test_path=ds.test_prefix,
        epochs=2,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        max_fields=12,
        num_devices=1,
        update_mode=mode,
    )
    base.update(kw)
    return Config(model=model, **base)


@pytest.mark.parametrize(
    "model,table",
    [("lr", "w"), ("fm", "v"), ("mvm", "v"), ("wide_deep", "emb")],
)
def test_dense_equals_sparse(toy_dataset, model, table):
    kw = {"emb_dim": 4, "hidden_dim": 8} if model == "wide_deep" else {}
    td = Trainer(cfg_for(toy_dataset, "dense", model, **kw))
    td.train()
    ts = Trainer(cfg_for(toy_dataset, "sparse", model, **kw))
    ts.train()
    for name in td.state["tables"]:
        for part in td.state["tables"][name]:
            a = np.asarray(jax.device_get(td.state["tables"][name][part]))
            b = np.asarray(jax.device_get(ts.state["tables"][name][part]))
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-7, err_msg=f"{name}/{part}"
            )
    # dense (MLP) params must train in BOTH modes — a refactor once
    # dropped grad_dense on the sparse path and only the tables moved
    if td.state["dense"]:
        init_dense = Trainer(
            cfg_for(toy_dataset, "dense", model, **kw)
        ).state["dense"]
        for key in td.state["dense"]:
            a = np.asarray(jax.device_get(td.state["dense"][key]))
            b = np.asarray(jax.device_get(ts.state["dense"][key]))
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-6, err_msg=f"dense/{key}"
            )
            assert not np.allclose(
                a, np.asarray(jax.device_get(init_dense[key]))
            ) or a.size <= 1, f"dense/{key} never updated"


def test_dense_equals_sparse_sgd(toy_dataset):
    td = Trainer(cfg_for(toy_dataset, "dense", optimizer="sgd"))
    td.train()
    ts = Trainer(cfg_for(toy_dataset, "sparse", optimizer="sgd"))
    ts.train()
    a = np.asarray(jax.device_get(td.state["tables"]["w"]["param"]))
    b = np.asarray(jax.device_get(ts.state["tables"]["w"]["param"]))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize(
    "model,kw",
    [
        ("lr", {}),
        ("fm", {}),
        ("ffm", {"ffm_v_dim": 2}),
        ("wide_deep", {"emb_dim": 4, "hidden_dim": 8}),
        # hot table + microbatch compose: hot sections split per slice
        ("lr", {"hot_size_log2": 8, "hot_nnz": 8}),
        # mixed per-table hot (TableSpec.hot): ffm's w rides the MXU,
        # v keeps plain DMA for its hot-plane occurrences
        ("ffm", {"ffm_v_dim": 2, "hot_size_log2": 8, "hot_nnz": 8}),
    ],
)
def test_microbatch_equals_full_batch(toy_dataset, model, kw):
    """Gradient accumulation (Config.microbatch) is the same optimizer
    step as the single-pass dense path — grads are pre-divided by the
    full batch's real count, accumulated, then applied once."""
    t1 = Trainer(cfg_for(toy_dataset, "dense", model, **kw))
    t1.train()
    t4 = Trainer(cfg_for(toy_dataset, "dense", model, microbatch=4, **kw))
    t4.train()
    for name in t1.state["tables"]:
        for part in t1.state["tables"][name]:
            np.testing.assert_allclose(
                np.asarray(jax.device_get(t1.state["tables"][name][part])),
                np.asarray(jax.device_get(t4.state["tables"][name][part])),
                rtol=1e-5,
                atol=1e-7,
                err_msg=f"{model}:{name}/{part}",
            )
    for key in t1.state["dense"]:
        np.testing.assert_allclose(
            np.asarray(jax.device_get(t1.state["dense"][key])),
            np.asarray(jax.device_get(t4.state["dense"][key])),
            rtol=1e-5,
            atol=1e-6,
            err_msg=f"{model}:dense/{key}",
        )


@pytest.mark.parametrize("mb", [1, 4])
def test_dense_sharded_matches_single(toy_dataset, mb):
    t1 = Trainer(cfg_for(toy_dataset, "dense", num_devices=1))
    t1.train()
    t8 = Trainer(cfg_for(toy_dataset, "dense", num_devices=8, microbatch=mb))
    t8.train()
    np.testing.assert_allclose(
        np.asarray(jax.device_get(t1.state["tables"]["w"]["param"])),
        np.asarray(jax.device_get(t8.state["tables"]["w"]["param"])),
        rtol=1e-5,
        atol=1e-7,
    )

"""Parallel sharded input fan-out (io/fanout.py; ISSUE 14 tentpole).

The pool's whole contract is "faster, otherwise invisible": N
concurrent shard streams must merge back into the serial reader's
exact batch sequence (bitwise — training is order-dependent), resume
cursors must keep working, failures must propagate, and close() must
reap every producer thread.  The tier-1 gate
(scripts/check_input_fanout.py) runs the packed-v2 corpus + sanitizer
acceptance; these tests cover the unit surface.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from xflow_tpu.config import Config
from xflow_tpu.io.fanout import ShardStreamPool
from xflow_tpu.io.loader import ShardLoader
from xflow_tpu.trainer import Trainer, find_shards

BATCH_FIELDS = (
    "keys", "slots", "vals", "mask", "labels", "weights",
    "hot_keys", "hot_slots", "hot_vals", "hot_mask",
)


def _loader_factory(batch_size=32, max_nnz=24, table_log2=14):
    def make(path):
        return ShardLoader(
            path, batch_size=batch_size, max_nnz=max_nnz,
            table_size=1 << table_log2,
        )
    return make


def _batches_equal(a, b) -> bool:
    return all(
        np.array_equal(getattr(a, f), getattr(b, f)) for f in BATCH_FIELDS
    )


def _collect(shards, n, **kw):
    pool = ShardStreamPool(
        shards, _loader_factory(), num_streams=n, depth=2, **kw
    )
    try:
        return [(si, resume, b) for b, si, resume in pool]
    finally:
        pool.close()


@pytest.fixture(scope="module")
def shards(toy_dataset):
    return find_shards(toy_dataset.train_prefix)


def test_pool_matches_serial_bitwise(shards):
    """N=1, N=2 and N=4 pools all yield the serial loaders' exact
    (batch, shard, resume) sequence."""
    serial = []
    make = _loader_factory()
    for si, path in enumerate(shards):
        for batch, resume in make(path).iter_batches():
            serial.append((si, resume, batch))
    for n in (1, 2, 4):
        got = _collect(shards, n)
        assert len(got) == len(serial)
        for (sa, ra, ba), (sb, rb, bb) in zip(serial, got):
            assert (sa, ra) == (sb, rb)
            assert _batches_equal(ba, bb)


def test_pool_resume_cursor(shards):
    """A pool resumed at (start_shard, start_offset) yields exactly
    what the serial readers yield from the same cursor (resume
    granularity — bounded block replay — included)."""
    full = _collect(shards, 3)
    # resume from the second shard at the offset its second batch
    # reported (the trainer's checkpoint cursor shape)
    anchor = [i for i, (si, _, _) in enumerate(full) if si == 1][1]
    start_offset = full[anchor][1]
    make = _loader_factory()
    serial = []
    for si in range(1, len(shards)):
        offset = start_offset if si == 1 else 0
        for batch, resume in make(shards[si]).iter_batches(offset):
            serial.append((si, resume, batch))
    got = _collect(shards, 3, start_shard=1, start_offset=start_offset)
    assert len(got) == len(serial)
    for (sa, ra, ba), (sb, rb, bb) in zip(serial, got):
        assert (sa, ra) == (sb, rb)
        assert _batches_equal(ba, bb)


def test_pool_clamps_streams_and_validates(shards):
    pool = ShardStreamPool(
        shards[:2], _loader_factory(), num_streams=8, depth=2
    )
    try:
        assert pool.num_streams == 2  # never more streams than shards
    finally:
        pool.close()
    with pytest.raises(ValueError, match="num_streams"):
        ShardStreamPool(shards, _loader_factory(), num_streams=0)
    with pytest.raises(ValueError, match="depth"):
        ShardStreamPool(shards, _loader_factory(), num_streams=1, depth=0)


def test_pool_close_mid_iteration_reaps_threads(shards):
    before = {t.ident for t in threading.enumerate()}
    pool = ShardStreamPool(shards, _loader_factory(), num_streams=3, depth=2)
    it = iter(pool)
    next(it)  # streams are live
    assert pool.alive
    pool.close()
    deadline = time.time() + 10
    while time.time() < deadline and pool.alive:
        time.sleep(0.02)
    assert not pool.alive
    leaked = {
        t.ident for t in threading.enumerate() if t.is_alive()
    } - before
    assert not leaked, f"leaked stream threads: {leaked}"
    pool.close()  # idempotent


def test_pool_propagates_stream_exception(shards):
    """A loader failure inside one stream surfaces to the merging
    consumer (the quarantine-budget / I/O failure path)."""

    class Boom(RuntimeError):
        pass

    make = _loader_factory()

    def factory(path):
        loader = make(path)
        if path.endswith("-00001"):
            def bad_iter(*a, **k):
                raise Boom("stream reader died")
                yield  # pragma: no cover
            loader.iter_batches = bad_iter
        return loader

    pool = ShardStreamPool(shards, factory, num_streams=3, depth=2)
    try:
        with pytest.raises(Boom, match="stream reader died"):
            for _ in pool:
                pass
    finally:
        pool.close()


def test_pool_transform_runs_on_stream(shards):
    """The per-batch transform (TrainStep.precompact's seat) runs on
    the producer threads, not the consumer."""
    consumer = threading.get_ident()
    seen = []

    def tag(batch):
        seen.append(threading.get_ident())
        return batch

    out = _collect(shards, 2, transform=tag)
    assert out and seen
    assert consumer not in set(seen)


def test_pool_stream_stats_accounting(shards):
    pool = ShardStreamPool(shards, _loader_factory(), num_streams=2, depth=1)
    try:
        n = sum(b.num_real() for b, _, _ in pool)
    finally:
        pool.close()
    stats = pool.stream_stats()
    assert [s["stream"] for s in stats] == [0, 1]
    assert sum(s["shards"] for s in stats) == len(shards)
    assert sum(s["examples"] for s in stats) == n
    for s in stats:
        assert s["batches"] > 0
        assert s["seconds"] > 0
        assert s["examples_per_sec"] > 0
        assert s["stall_seconds"] >= 0


def test_pool_stall_seconds_under_slow_consumer(shards):
    """A consumer slower than the readers books backpressure stall on
    the streams — the signal that separates 'slow reader' from
    'saturated device' in the stream rows."""
    pool = ShardStreamPool(shards, _loader_factory(), num_streams=2, depth=1)
    try:
        for i, _ in enumerate(pool):
            if i < 4:
                time.sleep(0.12)
    finally:
        pool.close()
    assert sum(s["stall_seconds"] for s in pool.stream_stats()) > 0.1


# -- trainer integration ----------------------------------------------------


def _train_state(toy_dataset, tmp_path, streams, metrics=""):
    import jax

    cfg = Config(
        model="lr", train_path=toy_dataset.train_prefix, epochs=1,
        batch_size=32, table_size_log2=14, max_nnz=24, num_devices=1,
        input_streams=streams, metrics_out=metrics,
    )
    with Trainer(cfg) as t:
        t.train_epoch()
        return jax.device_get(t.state)


def test_trainer_fanout_bitwise_parity(toy_dataset, tmp_path):
    """input_streams=4 trains to the exact serial state and emits
    schema-valid per-stream rows plus the serial path's shard rows."""
    import jax.tree_util as tu

    from xflow_tpu.obs.schema import load_jsonl, validate_rows

    metrics = str(tmp_path / "fan.jsonl")
    s1 = _train_state(toy_dataset, tmp_path, streams=1)
    s4 = _train_state(toy_dataset, tmp_path, streams=4, metrics=metrics)
    for a, b in zip(tu.tree_leaves(s1), tu.tree_leaves(s4)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    rows = load_jsonl(metrics)
    assert validate_rows(rows) == []
    stream_rows = [r for r in rows if r.get("kind") == "stream"]
    shard_rows = [r for r in rows if r.get("kind") == "shard"]
    assert len(stream_rows) >= 2
    assert len(shard_rows) == 3  # toy corpus: one row per shard
    assert sum(r["shards"] for r in stream_rows) == 3
    assert all(r["examples_per_sec"] > 0 for r in stream_rows)


def test_trainer_fanout_preemption_reaps(toy_dataset, tmp_path):
    """Abandoning a fan-out epoch mid-stream (the preemption/crash
    shape) leaves no stream threads behind Trainer.close()."""
    before = {t.ident for t in threading.enumerate()}
    cfg = Config(
        model="lr", train_path=toy_dataset.train_prefix, epochs=1,
        batch_size=32, table_size_log2=14, max_nnz=24, num_devices=1,
        input_streams=3,
    )
    t = Trainer(cfg)
    it = t.iter_train_batches()
    next(it)
    t.close()
    deadline = time.time() + 10
    while time.time() < deadline:
        leaked = {
            th.ident for th in threading.enumerate() if th.is_alive()
        } - before
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"fan-out streams leaked: {leaked}"


# -- config surface ---------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="input_streams must be >= 1"):
        Config(input_streams=0)
    with pytest.raises(ValueError, match="transfer_ahead_depth"):
        Config(transfer_ahead_depth=0)
    with pytest.raises(ValueError, match="ROADMAP item 2"):
        Config(
            input_streams=2, store_mode="tiered",
            table_size_log2=20, hot_capacity_log2=10,
        )
    # legacy manifests (pre-rename) keep loading
    cfg = Config.from_json(json.dumps({"transfer_ahead": 5}))
    assert cfg.transfer_ahead_depth == 5


@pytest.mark.parametrize("key,default,other", [
    ("cold_consolidate", False, True),
    ("hot_dtype", "float32", "bfloat16"),
    ("param_dtype", "float32", "bfloat16"),
])
@pytest.mark.parametrize("at_default", [True, False])
def test_retired_config_keys_in_old_manifests(key, default, other, at_default):
    """Manifests written before PR 29 embed three fields that are gone
    (docs/MIGRATION.md): at its old default a key asked for what every
    run does now and is dropped; any other value asked for a path that
    no longer exists, and is refused by name."""
    old = json.loads(Config(table_size_log2=20).to_json())
    old[key] = default if at_default else other
    if at_default:
        assert Config.from_json(json.dumps(old)) == Config(table_size_log2=20)
    else:
        with pytest.raises(ValueError, match=f"{key}.*MIGRATION.md"):
            Config.from_json(json.dumps(old))


# -- packed-v2 shard splitting ----------------------------------------------


def test_split_shard_v2(tmp_path, toy_dataset):
    """split_shard_v2 sub-shards stream the source's records
    byte-identically, in order, with correct per-shard totals."""
    from xflow_tpu.io import packed

    src = str(tmp_path / "whole.pk")
    packed.convert_shard(
        toy_dataset.train_prefix + "-00000", src, fmt="v2",
        batch_size=32, max_nnz=24, table_size=1 << 14,
    )
    parts = packed.split_shard_v2(src, str(tmp_path / "part"), 3)
    assert len(parts) == 3
    with open(src, "rb") as f:
        want = list(packed.iter_compact_batches(f))
    got = []
    total_examples = 0
    for p in parts:
        assert packed.is_packed_shard(p)
        total_examples += packed.shard_example_count(p)
        with open(p, "rb") as f:
            got.extend(cb for cb, _, _ in packed.iter_compact_batches(f))
    assert len(got) == len(want)
    assert total_examples == sum(cb.n_real for cb, _, _ in want)
    for (ca, _, _), cb in zip(want, got):
        for pl in (
            "cu", "ci", "ct", "cf", "cc", "lb", "wb", "cs",
        ):
            assert np.array_equal(getattr(ca, pl), getattr(cb, pl))
    with pytest.raises(ValueError, match="num_shards"):
        packed.split_shard_v2(src, str(tmp_path / "bad"), 0)


# -- obs surface ------------------------------------------------------------


def _stream_row(stream, eps, stall=0.0):
    return {
        "t": 1.0, "kind": "stream", "epoch": 0, "stream": stream,
        "shards": 2, "batches": 10, "examples": 1000,
        "seconds": 1.0, "read_seconds": 1000.0 / eps,
        "stall_seconds": stall, "examples_per_sec": eps,
    }


def test_doctor_stream_straggler(tmp_path, capsys):
    from xflow_tpu.obs.__main__ import main

    path = tmp_path / "streams.jsonl"
    rows = [
        _stream_row(0, 9000.0), _stream_row(1, 9500.0),
        _stream_row(2, 2000.0), _stream_row(3, 8800.0),
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    rc = main(["doctor", str(path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stream_straggler" in out and "stream 2" in out


def test_doctor_balanced_streams_clean(tmp_path, capsys):
    from xflow_tpu.obs.__main__ import main

    path = tmp_path / "streams.jsonl"
    rows = [_stream_row(s, 9000.0 + 100 * s) for s in range(4)]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    rc = main(["doctor", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "stream_skew" in out and "stream_straggler" not in out


def test_summarize_stream_spread_line(tmp_path, capsys):
    from xflow_tpu.obs.__main__ import main

    path = tmp_path / "streams.jsonl"
    rows = [_stream_row(0, 8000.0), _stream_row(1, 4000.0, stall=0.5)]
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert main(["summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "input streams: 2" in out
    assert "spread max/min = 2.00x" in out
    assert "backpressure stall 0.5s" in out


# -- tier-1 gate wiring -----------------------------------------------------


def test_check_input_fanout_script():
    """scripts/check_input_fanout.py: the packed-v2 corpus acceptance
    (bitwise N=4 vs serial, schema-valid stream rows, zero thread
    leaks, sanitizer-clean lock orders) exits 0 on the shipped tree."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "scripts", "check_input_fanout.py"),
        ],
        capture_output=True,
        text=True,
        timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK:" in proc.stdout

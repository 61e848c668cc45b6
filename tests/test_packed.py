"""Packed-batch cache (io/packed.py): stored batches must be
bit-identical to what the text loader assembles at the same config, the
geometry validation must refuse mismatched caches, and training from a
packed prefix must reproduce training from text exactly."""

import os

import numpy as np
import pytest

from xflow_tpu.io import packed
from xflow_tpu.io.loader import ShardLoader

from tests.test_binary import batches_equal, make_loader

T = 1 << 14


@pytest.fixture(scope="module")
def packed_shard(toy_dataset, tmp_path_factory):
    src = toy_dataset.train_prefix + "-00000"
    dst = str(tmp_path_factory.mktemp("pk") / "shard-00000")
    meta = packed.convert_shard(
        src, dst, batch_size=64, max_nnz=24, table_size=T, block_mib=0.002
    )
    return src, dst, meta


def test_packed_matches_text(packed_shard):
    src, dst, meta = packed_shard
    assert packed.is_packed_shard(dst)
    assert meta["examples"] == 200
    assert packed.shard_example_count(dst) == 200
    text = list(make_loader(src).iter_batches())
    pk = list(make_loader(dst).iter_batches())
    assert len(text) == len(pk) == meta["batches"]
    for (tb, _), (pb, _) in zip(text, pk):
        batches_equal(tb, pb)


def test_packed_hot_remap(toy_dataset, tmp_path):
    """Hot geometry + remap bake into the cache; loading with the same
    remap matches text, with a different remap refuses."""
    src = toy_dataset.train_prefix + "-00000"
    dst = str(tmp_path / "hot-00000")
    rng = np.random.default_rng(3)
    remap = rng.permutation(T).astype(np.int32)
    packed.convert_shard(
        src, dst, batch_size=64, max_nnz=24, table_size=T,
        hot_size=256, hot_nnz=6, remap=remap, block_mib=0.002,
    )
    kw = dict(remap=remap, hot_size=256, hot_nnz=6)
    text = list(make_loader(src, **kw).iter_batches())
    pk = list(make_loader(dst, **kw).iter_batches())
    for (tb, _), (pb, _) in zip(text, pk):
        batches_equal(tb, pb)
    other = rng.permutation(T).astype(np.int32)
    with pytest.raises(ValueError, match="remap_sha256"):
        list(make_loader(dst, remap=other, hot_size=256, hot_nnz=6).iter_batches())


def test_packed_geometry_mismatch_rejected(packed_shard):
    _, dst, _ = packed_shard
    with pytest.raises(ValueError, match="batch_size"):
        list(make_loader(dst, batch_size=32).iter_batches())
    with pytest.raises(ValueError, match="cold_nnz"):
        list(make_loader(dst, max_nnz=16).iter_batches())
    with pytest.raises(ValueError, match="table_size"):
        list(make_loader(dst, table_size=1 << 12).iter_batches())
    with pytest.raises(ValueError, match="seed"):
        list(make_loader(dst, hash_seed=9).iter_batches())


def test_packed_resume_exact(packed_shard):
    """Packed resume offsets are exact (record-aligned): no replay at
    all, unlike the block-granularity text/CSR caches."""
    _, dst, _ = packed_shard
    loader = make_loader(dst)
    full = list(loader.iter_batches())
    assert len(full) > 2
    _, resume = full[0]
    tail = list(loader.iter_batches(start_offset=resume))
    assert len(tail) == len(full) - 1
    for (fb, fo), (tb, to) in zip(full[1:], tail):
        batches_equal(fb, tb)
        assert fo == to


def test_packed_stale_resume_cursor_rejected(packed_shard):
    """A resume offset past EOF (checkpoint cursor against a cache
    rebuilt shorter) fails with a clear message — like the CSR cache's
    'past the shard end' — instead of silently dropping the shard
    remainder or claiming a truncated record."""
    _, dst, _ = packed_shard
    loader = make_loader(dst)
    full = list(loader.iter_batches())
    rec_size = full[1][1] - full[0][1]  # record-aligned stride
    with pytest.raises(ValueError, match="past the packed shard end"):
        list(loader.iter_batches(start_offset=full[-1][1] + rec_size))


def test_packed_cli_and_training_parity(toy_dataset, tmp_path):
    out = str(tmp_path / "pk")
    rc = packed.main([
        "--train", toy_dataset.train_prefix, "--out", out,
        "--batch-size", "64", "--max-nnz", "24",
        "--table-size-log2", "14", "--block-mib", "0.01",
    ])
    assert rc == 0
    assert sorted(os.listdir(tmp_path)) == ["pk-00000", "pk-00001", "pk-00002"]

    from xflow_tpu.config import Config
    from xflow_tpu.trainer import Trainer
    import jax

    base = dict(
        model="lr", epochs=2, batch_size=64, table_size_log2=14,
        max_nnz=24, num_devices=1, test_path=toy_dataset.test_prefix,
    )
    t_text = Trainer(Config(train_path=toy_dataset.train_prefix, **base))
    t_text.train()
    t_pk = Trainer(Config(train_path=out, **base))
    t_pk.train()
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t_text.state["tables"]["w"]["param"])),
        np.asarray(jax.device_get(t_pk.state["tables"]["w"]["param"])),
    )


# -- the remap's digest: one hash per holder of a remap ---------------------


def _tobytes_digest(remap):
    """``remap_digest`` as it was before it hashed the array's buffer."""
    import hashlib

    return hashlib.sha256(
        np.ascontiguousarray(remap, np.int32).tobytes()
    ).hexdigest()


def _read_only(a):
    a = a.copy()
    a.flags.writeable = False
    return a


@pytest.mark.parametrize("variant", [
    lambda r: r,
    lambda r: r[::3],  # non-contiguous
    lambda r: r.reshape(128, 128).T,  # non-contiguous, two axes
    lambda r: r.astype(np.int64),
    _read_only,
    lambda r: r[:0],  # no rows
], ids=["int32", "strided", "transposed", "int64", "read_only", "empty"])
def test_remap_digest_is_the_tobytes_digest(variant):
    remap = variant(np.random.default_rng(5).permutation(T).astype(np.int32))
    assert packed.remap_digest(remap) == _tobytes_digest(remap)


def test_remap_digest_of_no_remap():
    assert packed.remap_digest(None) is None
    assert packed.RemapDigest(None).get() is None


@pytest.fixture
def hashes(monkeypatch):
    """Every remap ``packed.remap_digest`` is asked to hash, in order."""
    seen = []
    real = packed.remap_digest

    def counting(remap):
        seen.append(remap)
        return real(remap)

    monkeypatch.setattr(packed, "remap_digest", counting)
    return seen


def test_remap_digest_holder_hashes_once_under_concurrent_first_calls(
    hashes, monkeypatch
):
    """Many streams open their first shards at once: one sha256, the
    others wait for it and read the same digest."""
    import os
    import sys
    import threading
    import time

    remap = np.arange(T, dtype=np.int32)
    want = _tobytes_digest(remap)
    real = packed.remap_digest  # the counting one

    def slow(r):
        time.sleep(0.05)  # every thread arrives while the first hashes
        return real(r)

    monkeypatch.setattr(packed, "remap_digest", slow)
    holder = packed.RemapDigest(remap)
    n = 2 * (os.cpu_count() or 4)
    gate = threading.Barrier(n)
    got = []

    def first_open():
        gate.wait(timeout=30)
        got.append(holder.get())

    threads = [threading.Thread(target=first_open) for _ in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == [want] * n and len(hashes) == 1
    assert holder.get() == want and len(hashes) == 1


@pytest.fixture(scope="module")
def hot_corpus(toy_dataset, tmp_path_factory):
    """The toy set packed under two different remaps, each with the
    checkpoint dir (remap.npy) a Trainer reads its remap from."""
    from xflow_tpu.io import freq

    root = tmp_path_factory.mktemp("hot_pk")
    rng = np.random.default_rng(13)
    sides = {}
    for name in ("a", "b"):
        ck = root / f"ck_{name}"
        ck.mkdir()
        remap_path = str(ck / "remap.npy")
        freq.save_remap(remap_path, rng.permutation(T).astype(np.int32))
        out = str(root / f"pk_{name}")
        assert packed.main([
            "--train", toy_dataset.train_prefix, "--out", out,
            "--batch-size", "64", "--max-nnz", "24",
            "--table-size-log2", "14", "--hot-size-log2", "8",
            "--hot-nnz", "6", "--remap", remap_path, "--block-mib", "0.01",
        ]) == 0
        sides[name] = (out, str(ck))
    return sides


def _hot_cfg(train_path, checkpoint_dir, **kw):
    from xflow_tpu.config import Config

    base = dict(
        model="lr", epochs=2, batch_size=64, table_size_log2=14,
        max_nnz=24, hot_size_log2=8, hot_nnz=6, num_devices=1,
        train_path=train_path, checkpoint_dir=checkpoint_dir,
    )
    base.update(kw)
    return Config(**base)


def test_packed_cli_hashes_once_for_all_shards(toy_dataset, tmp_path, hashes):
    from xflow_tpu.io import freq

    remap_path = str(tmp_path / "remap.npy")
    remap = np.random.default_rng(17).permutation(T).astype(np.int32)
    freq.save_remap(remap_path, remap)
    out = str(tmp_path / "pk")
    assert packed.main([
        "--train", toy_dataset.train_prefix, "--out", out,
        "--batch-size", "64", "--max-nnz", "24", "--table-size-log2", "14",
        "--hot-size-log2", "8", "--hot-nnz", "6", "--remap", remap_path,
    ]) == 0
    assert len(hashes) == 1
    want = _tobytes_digest(remap)
    for i in range(3):
        with open(f"{out}-{i:05d}", "rb") as f:
            assert packed.read_header(f)[0]["remap_sha256"] == want


@pytest.mark.parametrize("streams", [1, 3])
def test_trainer_hashes_its_remap_once(hot_corpus, tmp_path, hashes, streams):
    """Two epochs over three packed shards: six opens, one sha256 (with
    three streams the first epoch's opens are concurrent), stated in
    the epoch records; the phase stays inside ``shard_open``."""
    import json

    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.trainer import Trainer

    out, ck = hot_corpus["a"]
    metrics = tmp_path / "m.jsonl"
    cfg = _hot_cfg(
        out, ck, input_streams=streams, metrics_out=str(metrics)
    )
    with Trainer(cfg) as t:
        assert not t.remap.flags.writeable
        t.train()
        assert len(hashes) == 1 and hashes[0] is t.remap
    rows = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert validate_rows(rows) == []
    epochs = [r for r in rows if r["kind"] == "train_epoch"]
    assert [e["shard_opens"] for e in epochs] == [3, 3]
    assert [e["remap_hashes"] for e in epochs] == [1, 0]
    for e in epochs:
        assert e["overlapped"]["remap_digest"] <= e["overlapped"]["shard_open"]


def test_epoch_without_a_remap_states_no_hash(toy_dataset, tmp_path):
    """``remap_hashes`` is written whenever ``shard_opens`` is: a packed
    run with no hot table reads 0, not a missing field."""
    import json

    from xflow_tpu.config import Config
    from xflow_tpu.trainer import Trainer

    out = str(tmp_path / "pk")
    assert packed.main([
        "--train", toy_dataset.train_prefix, "--out", out,
        "--batch-size", "64", "--max-nnz", "24", "--table-size-log2", "14",
    ]) == 0
    metrics = tmp_path / "m.jsonl"
    with Trainer(Config(
        model="lr", epochs=1, batch_size=64, table_size_log2=14, max_nnz=24,
        num_devices=1, train_path=out, metrics_out=str(metrics),
    )) as t:
        t.train()
    (epoch,) = [
        r for r in map(json.loads, metrics.read_text().splitlines())
        if r["kind"] == "train_epoch"
    ]
    assert epoch["shard_opens"] == 3 and epoch["remap_hashes"] == 0


@pytest.mark.parametrize("bad", [0, 2], ids=["first_open", "later_open"])
def test_trainer_refuses_a_shard_of_another_remap(hot_corpus, tmp_path, bad):
    """The memo changes who hashes, not what is checked: a shard packed
    under another remap is refused by the same sha256, whether it is the
    trainer's first open (the hash itself) or a later one (the lookup)."""
    import shutil

    from xflow_tpu.trainer import Trainer

    (out_a, ck_a), (out_b, _) = hot_corpus["a"], hot_corpus["b"]
    mixed = str(tmp_path / "mixed")
    for i in range(3):
        src = out_b if i == bad else out_a
        shutil.copy(f"{src}-{i:05d}", f"{mixed}-{i:05d}")
    with Trainer(_hot_cfg(mixed, ck_a, epochs=1)) as t:
        with pytest.raises(ValueError, match="remap_sha256"):
            t.train()


def test_two_trainers_check_against_their_own_remaps(hot_corpus, hashes):
    """No process-wide memo: each trainer hashes the remap it holds and
    refuses the other's shards."""
    from xflow_tpu.trainer import Trainer

    (out_a, ck_a), (out_b, ck_b) = hot_corpus["a"], hot_corpus["b"]
    with Trainer(_hot_cfg(out_a, ck_a)) as ta, \
            Trainer(_hot_cfg(out_b, ck_b)) as tb:
        for t, own, other in ((ta, out_a, out_b), (tb, out_b, out_a)):
            assert len(list(t._loader(own + "-00000").iter_batches())) > 0
            with pytest.raises(ValueError, match="remap_sha256"):
                list(t._loader(other + "-00001").iter_batches())
        assert len(hashes) == 2
        assert hashes[0] is ta.remap and hashes[1] is tb.remap
        assert ta._remap_digest.get() != tb._remap_digest.get()


def test_bare_loader_and_handed_digest_round_trip(toy_dataset, tmp_path, hashes):
    """A ShardLoader built without a digest hashes for itself, once per
    loader; ``convert_shard(remap_sha256=...)`` writes the digest it is
    handed and the shard reads back like one it hashed itself."""
    src = toy_dataset.train_prefix + "-00000"
    remap = np.random.default_rng(3).permutation(T).astype(np.int32)
    kw = dict(remap=remap, hot_size=256, hot_nnz=6)
    conv = dict(
        batch_size=64, max_nnz=24, table_size=T, block_mib=0.002, **kw
    )
    hashed = str(tmp_path / "hashed-00000")
    handed = str(tmp_path / "handed-00000")
    packed.convert_shard(src, hashed, **conv)
    assert len(hashes) == 1
    packed.convert_shard(
        src, handed, remap_sha256=packed.remap_digest(remap), **conv
    )
    assert len(hashes) == 2  # the caller's own, none inside
    with open(hashed, "rb") as f, open(handed, "rb") as g:
        assert f.read() == g.read()
    del hashes[:]
    loader = make_loader(handed, **kw)
    text = list(make_loader(src, **kw).iter_batches())
    for _ in range(2):
        pk = list(loader.iter_batches())
        assert len(pk) == len(text)
        for (tb, _), (pb, _) in zip(text, pk):
            batches_equal(tb, pb)
    assert len(hashes) == 1  # two opens by one loader, one hash
    list(make_loader(handed, **kw).iter_batches())
    assert len(hashes) == 2  # another bare loader hashes its own

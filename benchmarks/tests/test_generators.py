"""The row generator and the arrival timeline."""

from concurrent.futures import Future
import time

import numpy as np
import pytest

from benchmarks.generators import timeline
from benchmarks.generators.rows import RowGenerator, RowSpec, write_text_shards
from benchmarks.harness import corpus
from benchmarks.reference import steering

SPEC = RowSpec()


def test_rows_are_a_function_of_seed_and_stream():
    a = RowGenerator(SPEC, 7).draw(3000, (0, 0))
    b = RowGenerator(SPEC, 7).draw(3000, (0, 0))
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert (RowGenerator(SPEC, 8).draw(3000, (0, 0))[0] != a[0]).any()
    assert (RowGenerator(SPEC, 7).draw(3000, (0, 1))[0] != a[0]).any()


def test_key_space_matches_a_terabyte_scale_table():
    gen = RowGenerator(SPEC, 1)
    assert gen.spec.fields == 39
    assert 0.9e8 < gen.vocab.sum() < 1.1e8
    gid, labels = gen.draw(20000, (0, 0))
    local = gid - gen.offset
    assert (local >= 0).all() and (local < gen.vocab).all()
    assert 0.2 < labels.mean() < 0.45  # bias -1 and a planted signal
    # rank 1 of a field with V >> head carries 1 / (sum of the head's pmf +
    # the tail integral) of its draws
    v, a = float(gen.vocab[-1]), SPEC.zipf_a
    head = (np.arange(1, SPEC.head + 1) ** -a).sum()
    tail = ((SPEC.head + 0.5) ** (1 - a) - (v + 0.5) ** (1 - a)) / (a - 1)
    assert (local[:, -1] == 0).mean() == pytest.approx(1 / (head + tail), rel=0.1)
    assert (local[:, -1] >= SPEC.head).mean() == pytest.approx(
        tail / (head + tail), rel=0.1
    )


def test_planted_weights_need_no_table():
    gen = RowGenerator(SPEC, 3)
    gid = np.arange(200000).reshape(-1, 1)
    w = gen.planted_weights(gid)
    assert w.dtype == np.float32 and (w == gen.planted_weights(gid)).all()
    assert w.std() == pytest.approx(SPEC.w_scale, rel=0.02)
    assert abs(w.mean()) < 0.01
    assert (RowGenerator(SPEC, 4).planted_weights(gid) != w).mean() > 0.99


@pytest.mark.parametrize("table_log2, hash_seed", [(28, 0), (20, 5)])
def test_text_parses_to_the_generators_own_keys(table_log2, hash_seed):
    """``keys`` is the benchmark's own hash; native/parser.cc (and the
    pure-Python parser) must read the same rows out of the text."""
    from xflow_tpu import native
    from xflow_tpu.io.libffm import parse_block
    from xflow_tpu.io.loader import make_parse_fn

    gen = RowGenerator(SPEC, 11)
    gid, labels = gen.draw(500, (2, 0))
    text = gen.text(gid, labels)
    want = gen.keys(gid, 1 << table_log2, hash_seed)
    assert native.available(), "the native parser did not build"
    for parse in (
        make_parse_fn(1 << table_log2, True, hash_seed),
        lambda d: parse_block(d[: 50 * len(d) // 500], 1 << table_log2, True, hash_seed),
    ):
        block = parse(text)
        n = block.num_samples
        assert n in (500, 50)
        assert (block.keys.reshape(n, 39) == want[:n]).all()
        assert (block.labels == labels[:n]).all()
        assert (block.slots.reshape(n, 39) == np.arange(39)).all()


def test_shards_can_be_drawn_again(tmp_path):
    gen = RowGenerator(SPEC, 5)
    paths = write_text_shards(gen, str(tmp_path / "t"), 2, 9000)
    gid, labels = corpus.shard_rows(gen, 1, 9000)
    with open(paths[1], "rb") as f:
        assert f.read() == gen.text(gid, labels)


def test_hot_remap_is_build_remaps_permutation():
    from xflow_tpu.io import freq

    gen = RowGenerator(SPEC, 11)
    t, h = 1 << 16, 256
    remap, mass = corpus.hot_remap(gen, t, h, 11, sample_rows=8192)
    assert sorted(remap.tolist()) == list(range(t))
    keys = gen.keys(corpus.shard_rows(gen, 0, 8192)[0], t, 11).ravel()
    counts = np.bincount(keys, minlength=t).astype(np.float64)
    assert mass == pytest.approx(freq.hot_mass(counts, remap, h))
    # the same head as build_remap's (count ties aside), in descending
    # frequency, and the same order behind it
    head = np.argsort(remap)[:h]
    assert (np.diff(counts[head]) <= 0).all()
    counts[head] += 0.5  # break the ties the way hot_remap did
    theirs = freq.build_remap(counts, h)
    assert ((theirs < h) == (remap < h)).all()
    assert (theirs[theirs >= h] == remap[remap >= h]).all()


@pytest.mark.parametrize(
    "hot_size, hot_nnz, max_nnz", [(64, 4, 8), (64, 12, 4), (64, 8, 12), (0, 0, 8)]
)
def test_steering_is_the_programs(hot_size, hot_nnz, max_nnz):
    """``steering.kept`` keeps what ``io/batch.py::pack_batch`` keeps."""
    from xflow_tpu.io.batch import ParsedBlock, pack_batch

    rng = np.random.default_rng(0)
    n, k = 300, 16
    keys = rng.integers(0, 256, (n, k))
    block = ParsedBlock(
        labels=np.zeros(n, np.float32),
        row_ptr=np.arange(n + 1, dtype=np.int64) * k,
        keys=keys.ravel().astype(np.int64),
        slots=np.zeros(n * k, np.int32),
        vals=np.ones(n * k, np.float32),
    )
    batch = pack_batch(block, 0, n, n, max_nnz, hot_size, hot_nnz)
    kept = steering.kept(keys, hot_size, hot_nnz, max_nnz)
    theirs = np.concatenate([
        np.where(batch.hot_mask > 0, batch.hot_keys, -1),
        np.where(batch.mask > 0, batch.keys, -1),
    ], axis=1)
    mine = np.where(kept, keys, -1)
    width = max(theirs.shape[1], k)
    pad = lambda a: np.pad(a, ((0, 0), (0, width - a.shape[1])), constant_values=-1)
    assert (np.sort(pad(mine), axis=1) == np.sort(pad(theirs), axis=1)).all()
    assert steering.dropped_share(keys, hot_size, hot_nnz, max_nnz) == pytest.approx(
        1.0 - (batch.mask.sum() + batch.hot_mask.sum()) / (n * k)
    )


def test_poisson_due_is_a_rate():
    due = timeline.poisson_due(np.random.default_rng(1), 2000.0, 5.0)
    assert len(due) == pytest.approx(10000, rel=0.05)
    assert (np.diff(due) > 0).all() and due[-1] < 5.0


class _Shed(Exception):
    pass


def test_latency_runs_from_the_due_instant():
    """A generator held up by a slow submit is late for the next rows; their
    latency still counts from when they were due, so the stall shows."""
    def submit(row):
        if row == 0:
            time.sleep(0.05)  # the stall
        fut = Future()
        fut.set_result(0.25)
        return fut

    due = np.asarray([0.0, 0.001, 0.002])
    loop = timeline.OpenLoop(submit, [0, 1, 2], due, _Shed)
    loop.run()
    assert loop.drain(1.0) == 0
    assert (loop.status == timeline.ANSWERED).all() and (loop.answer == 0.25).all()
    assert loop.late[0] < 0.02 and loop.late[1] > 0.04 and loop.late[2] > 0.04
    assert (loop.latency[1:] >= loop.late[1:]).all() and loop.latency[1] > 0.04


def test_shed_errors_and_unanswered_are_told_apart():
    hung = Future()

    def submit(row):
        if row == "shed":
            raise _Shed()
        if row == "boom":
            raise ValueError("bad row")
        if row == "late shed":
            fut = Future()
            fut.set_exception(_Shed())
            return fut
        return hung

    loop = timeline.OpenLoop(
        submit, ["shed", "boom", "late shed", "hang"], np.zeros(4), _Shed
    )
    loop.run()
    assert loop.drain(0.05) == 1
    assert loop.status.tolist() == [
        timeline.SHED, timeline.ERROR, timeline.SHED, timeline.PENDING
    ]

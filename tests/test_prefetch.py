"""Prefetch lifecycle (io/loader.py::_PrefetchIter): the producer
thread must die on explicit close() — including when the consumer
abandons the iterator mid-shard — not whenever the GC notices, and
Trainer.close() must close every prefetch it spawned."""

import threading
import time

import numpy as np
import pytest

from xflow_tpu.config import Config
from xflow_tpu.io.loader import ShardLoader, _PrefetchIter
from xflow_tpu.trainer import Trainer


def _wait_dead(it, timeout=5.0) -> bool:
    t0 = time.time()
    while time.time() - t0 < timeout:
        if not it.alive:
            return True
        time.sleep(0.01)
    return False


def test_prefetch_close_stops_abandoned_producer(toy_dataset):
    """Consumer takes ONE item and walks away; close() must stop the
    producer even while it is blocked on a full queue."""
    loader = ShardLoader(
        toy_dataset.train_prefix + "-00000",
        batch_size=16, max_nnz=24, table_size=1 << 14, block_mib=1,
    )
    it = loader.prefetch(depth=1)
    batch, _ = next(it)
    assert batch.num_real() == 16
    assert it.alive  # producer blocked on the depth-1 queue
    it.close()
    assert _wait_dead(it)
    # closed iterator is exhausted, not wedged
    assert list(it) == []


def test_prefetch_close_idempotent_and_context_manager(toy_dataset):
    loader = ShardLoader(
        toy_dataset.train_prefix + "-00000",
        batch_size=16, max_nnz=24, table_size=1 << 14, block_mib=1,
    )
    with loader.prefetch(depth=2) as it:
        next(it)
    assert _wait_dead(it)
    it.close()  # second close is a no-op

    # depth 0 degrades to a synchronous passthrough with the same
    # close() surface
    it0 = loader.prefetch(depth=0)
    next(it0)
    it0.close()
    assert list(it0) == []


def test_prefetch_exception_propagates(tmp_path):
    def boom():
        yield 1
        raise RuntimeError("producer exploded")

    it = _PrefetchIter(boom(), depth=2)
    assert next(it) == 1
    try:
        next(it)
        raise AssertionError("expected RuntimeError")
    except RuntimeError:
        pass
    assert _wait_dead(it)


class _Abandon(Exception):
    pass


@pytest.mark.parametrize("how", ["queue_full", "source_raised", "exhausted"])
def test_prefetch_close_unwinds_the_source(how):
    """However the iteration ends, close() leaves the source generator
    unwound (its ``finally`` ran, its ``with`` blocks released what
    they held: in iter_batches the shard file and the parse pool) even
    while a traceback still references the iterator.  Before the
    producer closed its source, an abort on a full queue left the
    generator suspended inside its pool until the garbage collector
    found it, and the pool's thread outlived Trainer.close()."""
    from concurrent.futures import ThreadPoolExecutor

    unwound = []

    def source():
        with ThreadPoolExecutor(max_workers=1) as pool:
            try:
                for i in range(8):
                    if how == "source_raised" and i == 1:
                        raise RuntimeError("source exploded")
                    yield pool.submit(int, i).result()
            finally:
                unwound.append(True)

    def consume(it):
        # a pytest.raises traceback keeps this frame, hence ``it``
        try:
            if how == "exhausted":
                assert list(it) == list(range(8))
            else:
                assert next(it) == 0
                if how == "source_raised":
                    next(it)
            raise _Abandon()
        finally:
            it.close()

    before = set(threading.enumerate())
    it = _PrefetchIter(source(), depth=1)
    expected = RuntimeError if how == "source_raised" else _Abandon
    with pytest.raises(expected) as excinfo:
        consume(it)
    assert excinfo.traceback  # still holds consume's frame
    assert not it.alive
    assert unwound == [True]
    assert not [
        t.name for t in set(threading.enumerate()) - before
        if t.name.startswith("ThreadPoolExecutor")
    ]


def test_prefetch_leak_surfaced_on_join_timeout(tmp_path):
    """A producer wedged in parse/read (NOT on the queue) outlives the
    close() join: the leak must be SURFACED — warning, counter, and a
    ``health`` row `obs doctor` can rank — instead of silent
    (io/loader.py satellite, ISSUE 6)."""
    import json
    import threading
    import warnings

    from xflow_tpu.obs import Obs
    from xflow_tpu.obs.flight import FlightRecorder
    from xflow_tpu.utils.logging import MetricsLogger

    release = threading.Event()

    def wedged():
        yield 1
        release.wait()  # stuck mid-"parse", not on the queue
        yield 2

    path = str(tmp_path / "m.jsonl")
    logger = MetricsLogger(path)
    obs = Obs()
    obs.flight = FlightRecorder(metrics_logger=logger)
    it = _PrefetchIter(wedged(), depth=2, obs=obs)
    assert next(it) == 1
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        it.close(join_timeout=0.1)
    assert any(
        "outlived" in str(w.message) for w in caught
    ), [str(w.message) for w in caught]
    snap = obs.registry.snapshot()
    assert snap.counters.get("loader.leaked_threads") == 1
    logger.close()
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    leak = [
        r for r in rows
        if r.get("kind") == "health"
        and r.get("cause") == "prefetch_thread_leak"
    ]
    assert len(leak) == 1
    assert leak[0]["channel"] == "loader"
    # schema-valid: obs validate must accept the leak row
    from xflow_tpu.obs.schema import validate_rows

    assert validate_rows(leak) == []
    # close() is idempotent: a second close (Trainer.close reaping
    # _live_prefetch after a direct close) must neither pay another
    # join_timeout nor double-report the leak
    import time as _time

    t0 = _time.monotonic()
    it.close(join_timeout=5.0)
    assert _time.monotonic() - t0 < 1.0
    assert obs.registry.snapshot().counters.get(
        "loader.leaked_threads"
    ) == 1
    # unwedge so the daemon producer exits before the test returns
    release.set()
    assert _wait_dead(it)


def test_prefetch_clean_close_does_not_warn(toy_dataset):
    """The normal close path stays silent: no leak warning, no
    counter, no health row."""
    import warnings

    loader = ShardLoader(
        toy_dataset.train_prefix + "-00000",
        batch_size=16, max_nnz=24, table_size=1 << 14, block_mib=1,
    )
    it = loader.prefetch(depth=1)
    next(it)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        it.close()
    assert not any(
        "outlived" in str(w.message) for w in caught
    ), [str(w.message) for w in caught]


def test_trainer_close_stops_live_prefetch(toy_dataset):
    """Abandon training mid-shard; Trainer.close() must reap the
    loader's producer thread."""
    cfg = Config(
        model="lr",
        train_path=toy_dataset.train_prefix,
        batch_size=16,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
        prefetch_batches=2,
        epochs=1,
    )
    t = Trainer(cfg)
    it = t.iter_train_batches()
    next(it)  # the shard prefetch is now live
    live = list(t._live_prefetch)
    assert live and any(p.alive for p in live)
    t.close()
    for p in live:
        assert _wait_dead(p)

"""Parallel sharded input fan-out: N concurrent shard-reader streams
with a deterministic, serial-order merge (ROADMAP item 1).

One reader stream was the last measured input bottleneck (round 5's
CPU bench, CHANGES.md PR 5/14: compute far ahead of the packed e2e
feed): read, parse and host
compaction all serialized behind a single thread while the device
waited.  Parallel sharded host feeds are table stakes for sparse CTR
training at scale — Parallax's sparsity-aware data parallelism
(arXiv:1808.02621) and the terabyte-scale ads-training wire discipline
(arXiv:2201.05500) both shard the input path first.

``ShardStreamPool`` partitions an epoch's shard list across N streams
by shard index (stream ``s`` owns shards ``i % N == s``).  Each stream
is a daemon producer thread (the ``_PrefetchIter`` fabric from
io/loader.py — bounded queue, explicit close(), backpressure
heartbeats, exception propagation) running its own
read -> parse -> [compact] loop over its shards, ``depth`` batches
ahead.  The consumer-side merge walks the GLOBAL shard order and pulls
each shard's batches from its owning stream, so the merged batch
sequence is exactly the serial reader's — training under the fan-out is
bitwise-identical to ``input_streams=1`` and steady-state shapes stay
on one compiled program (``e2e_recompiles: 0``).  The parallelism is in
the lookahead: while shard ``i`` drains to the device, the other
streams are already reading/parsing/compacting shards ``i+1..i+N-1``.

``transform`` runs on the producer thread per batch — the trainer
passes ``TrainStep.precompact`` so host dictionary compaction
(io/compact.py) rides the streams instead of the staging-ring workers.

Per-stream accounting (``stream_stats``) feeds the trainer's ``stream``
metrics rows (obs/schema.py): shards/batches/examples, producer wall
seconds, and backpressure stall seconds — `obs doctor` ranks a stream
whose throughput lags its peers as a straggler
(docs/OBSERVABILITY.md).

The tiered parameter store pins the pool to one stream at config time
(Config.input_streams validation): its cold tier's read-your-writes
ordering leaves nothing for concurrent readers to feed — ROADMAP item
2's async-PS relaxation lifts that.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator

from xflow_tpu.io.loader import _PrefetchIter
from xflow_tpu.obs import NULL_OBS

# Stream worker -> merger messages ride the _PrefetchIter queue:
# (_ITEM, shard_idx, batch, resume) per batch, (_DONE, shard_idx,
# stats) after each finished shard.  No other cross-thread state
# exists — stats travel with the message, so the pool needs no locks
# of its own.
_ITEM = 0
_DONE = 1


class ShardStreamPool:
    """N concurrent shard streams merged back into serial shard order.

    ``shards`` is the epoch's full (ordered) shard path list;
    ``loader_factory(path)`` builds the per-shard loader (the trainer's
    ``_loader``).  Yields ``(batch, shard_idx, resume_offset)`` with
    the exact contract and order of the serial reader.  ``close()``
    stops every stream (bounded join — the _PrefetchIter discipline);
    the pool is a context manager and registers cleanly with
    Trainer.close()'s reap set.
    """

    def __init__(
        self,
        shards: list[str],
        loader_factory: Callable[[str], object],
        num_streams: int,
        depth: int = 2,
        start_shard: int = 0,
        start_offset: int = 0,
        parse_workers: int = 0,
        transform: Callable | None = None,
        obs=None,
    ):
        if num_streams < 1:
            raise ValueError("num_streams must be >= 1")
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._shards = shards
        self._start_shard = start_shard
        self._obs = obs if obs is not None else NULL_OBS
        remaining = len(shards) - start_shard
        # never spawn empty streams: a 2-shard epoch at N=4 runs 2
        self.num_streams = max(1, min(num_streams, remaining))
        self._streams: list[_PrefetchIter] = []
        # consumer-side per-stream accumulators (single-thread: the
        # merging consumer alone touches these)
        self._stats: list[dict] = []
        self._stall_base: list[float] = []
        for s in range(self.num_streams):
            owned = [
                (i, shards[i])
                for i in range(start_shard, len(shards))
                if (i - start_shard) % self.num_streams == s
            ]
            it = _PrefetchIter(
                self._stream_source(
                    owned, loader_factory, start_shard, start_offset,
                    parse_workers, transform,
                ),
                depth,
                obs=self._obs,
            )
            self._streams.append(it)
            self._stats.append({
                "stream": s,
                "shards": 0,
                "batches": 0,
                "examples": 0,
                "seconds": 0.0,
                "read_seconds": 0.0,
                "stall_seconds": 0.0,
            })
            self._stall_base.append(0.0)

    @staticmethod
    def _stream_source(
        owned: list[tuple[int, str]],
        loader_factory: Callable[[str], object],
        start_shard: int,
        start_offset: int,
        parse_workers: int,
        transform: Callable | None,
    ) -> Iterator[tuple]:
        """One stream's producer generator: its owned shards in global
        order, each read through a fresh loader, batches optionally
        transformed (host compaction) BEFORE they hit the queue.  Runs
        entirely on the _PrefetchIter producer thread."""
        for shard_idx, path in owned:
            loader = loader_factory(path)
            offset = start_offset if shard_idx == start_shard else 0
            t0 = time.perf_counter()
            batches = 0
            examples = 0
            read_s = 0.0  # read+parse+compact, EXCLUDING queue waits:
            # measured directly (never wall minus stall — that
            # difference cancels catastrophically for fast readers)
            it = loader.iter_batches(offset, parse_workers)
            try:
                while True:
                    t = time.perf_counter()
                    try:
                        batch, resume = next(it)
                    except StopIteration:
                        break
                    if transform is not None:
                        batch = transform(batch)
                    read_s += time.perf_counter() - t
                    yield _ITEM, shard_idx, batch, resume
                    batches += 1
                    examples += batch.num_real()
            finally:
                it.close()  # this stream closed mid-shard
            yield _DONE, shard_idx, {
                "batches": batches,
                "examples": examples,
                "seconds": time.perf_counter() - t0,
                "read_seconds": read_s,
            }

    def __iter__(self) -> Iterator[tuple]:
        """Merge: global shard order, each shard pulled from its owning
        stream.  A stream exception (quarantine budget, I/O failure)
        propagates here through its _PrefetchIter."""
        for si in range(self._start_shard, len(self._shards)):
            s = (si - self._start_shard) % self.num_streams
            stream = self._streams[s]
            for msg in stream:
                if msg[0] == _DONE:
                    self._book_done(s, msg[1], msg[2])
                    break
                _, shard_idx, batch, resume = msg
                if shard_idx != si:  # defensive: streams emit in order
                    raise RuntimeError(
                        f"stream {s} yielded shard {shard_idx} while "
                        f"the merge expected shard {si}"
                    )
                yield batch, shard_idx, resume

    def _book_done(self, s: int, shard_idx: int, stats: dict) -> None:
        acc = self._stats[s]
        acc["shards"] += 1
        acc["batches"] += stats["batches"]
        acc["examples"] += stats["examples"]
        acc["seconds"] += stats["seconds"]
        acc["read_seconds"] += stats["read_seconds"]
        # stall delta since the last finished shard: _PrefetchIter
        # accounts cumulatively across the stream's whole life
        total_stall = self._streams[s].stall_seconds()
        acc["stall_seconds"] += total_stall - self._stall_base[s]
        self._stall_base[s] = total_stall

    def stream_stats(self) -> list[dict]:
        """Per-stream accounting over the shards finished so far —
        the trainer's ``stream`` metrics rows.  ``examples_per_sec``
        divides by the DIRECTLY MEASURED read+parse+compact seconds
        (queue waits excluded), so a stream parked behind a saturated
        consumer doesn't read as a straggler and a fast reader's rate
        doesn't explode out of a wall-minus-stall cancellation."""
        out = []
        for acc in self._stats:
            row = dict(acc)
            row["seconds"] = round(acc["seconds"], 6)
            row["read_seconds"] = round(acc["read_seconds"], 6)
            row["stall_seconds"] = round(acc["stall_seconds"], 6)
            row["examples_per_sec"] = round(
                acc["examples"] / max(acc["read_seconds"], 1e-9), 1
            )
            out.append(row)
        return out

    def close(self, join_timeout: float = 5.0) -> None:
        """Stop every stream's producer (bounded join per stream; a
        wedged producer is surfaced by _PrefetchIter.close's leak
        counter + health row, never waited on forever).  Idempotent."""
        for stream in self._streams:
            stream.close(join_timeout)

    @property
    def alive(self) -> bool:
        return any(stream.alive for stream in self._streams)

    def __enter__(self) -> "ShardStreamPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

"""Shard-aware streaming minibatch loader.

Reference behavior reproduced: each data-parallel worker reads its own
file shard named ``<prefix>-%05d`` by rank (lr_worker.cc:210); training
streams the shard in fixed-size byte blocks per epoch until the loader
returns no rows (lr_worker.cc:183-189).

New capabilities (gaps filled, SURVEY §5):

* batches are FULL across text-block boundaries — parsed blocks
  accumulate in a carry buffer and only a shard's final batch is
  zero-weight padded (the reference trains on whatever each 2 MiB block
  parses to, lr_worker.cc:184-189);
* a resume cursor per batch — the byte offset of the earliest block
  holding samples not yet emitted — so training can checkpoint and
  restart mid-shard.  Replay on resume is bounded by one block plus one
  carry (< batch_size samples); see iter_batches.
"""

from __future__ import annotations

import math
import queue
import threading
import time
import warnings
from typing import Callable, Iterator

from xflow_tpu.chaos import ChaosError, emit_health, failpoint, retry_call
from xflow_tpu.io.batch import Batch, ParsedBlock, pack_batch
from xflow_tpu.io.libffm import BlockReader, parse_block
from xflow_tpu.obs import NULL_OBS


class QuarantineExceeded(RuntimeError):
    """Quarantined blocks/records exceeded the budget
    (Config.max_quarantined_frac): the stream is corrupt beyond what
    skip-and-continue can responsibly absorb — training on the
    remainder would silently fit a different dataset."""


def shard_path(prefix: str, rank: int) -> str:
    return f"{prefix}-{rank:05d}"  # reference: lr_worker.cc:210


def _concat_blocks(a: ParsedBlock, b: ParsedBlock) -> ParsedBlock:
    """CSR concatenation (carry ∥ next block)."""
    import numpy as np

    return ParsedBlock(
        labels=np.concatenate([a.labels, b.labels]),
        row_ptr=np.concatenate([a.row_ptr, b.row_ptr[1:] + a.row_ptr[-1]]),
        keys=np.concatenate([a.keys, b.keys]),
        slots=np.concatenate([a.slots, b.slots]),
        vals=np.concatenate([a.vals, b.vals]),
    )


def _slice_block(block: ParsedBlock, start: int) -> ParsedBlock:
    """CSR tail slice: samples [start, n)."""
    lo = block.row_ptr[start]
    return ParsedBlock(
        labels=block.labels[start:],
        row_ptr=block.row_ptr[start:] - lo,
        keys=block.keys[lo:],
        slots=block.slots[lo:],
        vals=block.vals[lo:],
    )


ParseFn = Callable[[bytes], ParsedBlock]


def make_parse_fn(
    table_size: int,
    hash_mode: bool = True,
    hash_seed: int = 0,
    prefer_native: bool = True,
    numeric_fields: int = 0,
) -> ParseFn:
    """Native C++ parser when built/buildable, else the Python one.
    Both are behaviorally identical (tests/test_native.py).
    ``numeric_fields`` (Config.numeric_fields): in hash mode a token of a
    field below it keeps its value; 0, the default, is the reference's
    loader, every value discarded."""
    if prefer_native:
        from xflow_tpu import native

        if native.available():
            return lambda data: native.native_parse_block(
                data, table_size, hash_mode, hash_seed, numeric_fields
            )
    return lambda data: parse_block(
        data, table_size, hash_mode, hash_seed, numeric_fields
    )


class ShardLoader:
    """Streams one text shard as padded fixed-shape Batches."""

    def __init__(
        self,
        path: str,
        batch_size: int,
        max_nnz: int,
        table_size: int,
        block_mib: int = 2,
        hash_mode: bool = True,
        hash_seed: int = 0,
        parse_fn: ParseFn | None = None,
        remap=None,  # int32 [table_size] permutation (io/freq.py), or None
        hot_size: int = 0,
        hot_nnz: int = 0,
        obs=None,  # obs.Obs: parse/pack phase seconds + byte counters
        emit_compact: bool = False,  # v2 packed shards: yield CompactBatch
        io_retries: int = 2,  # transient read/parse retries per block
        io_retry_backoff_s: float = 0.05,
        max_quarantined_frac: float = 0.05,  # quarantine budget
        # packed.RemapDigest of ``remap``, shared by whoever holds the
        # remap (Trainer: one hash for all its loaders); None = this
        # loader hashes for itself, once
        remap_digest=None,
        # Config.numeric_fields: what the default parser keeps values
        # for and what a packed shard must have been packed with.  None
        # = whatever the shard holds (a reader that only wants the
        # batches: packed records carry their own values plane)
        numeric_fields: int | None = None,
    ):
        self.path = path
        self.batch_size = batch_size
        self.max_nnz = max_nnz
        self.table_size = table_size
        self.block_bytes = block_mib << 20
        self.hash_mode = hash_mode
        self.hash_seed = hash_seed
        self.numeric_fields = numeric_fields
        if parse_fn is None:
            parse_fn = lambda data: parse_block(
                data, table_size, hash_mode, hash_seed, numeric_fields or 0
            )
        self.parse_fn = parse_fn
        self.remap = remap
        if remap_digest is None:
            from xflow_tpu.io import packed

            remap_digest = packed.RemapDigest(remap)
        self._remap_digest = remap_digest
        self.hot_size = hot_size
        self.hot_nnz = hot_nnz
        # With emit_compact, v2 packed shards (io/packed.py) yield
        # their records AS CompactBatch — the consumer (a dict-wire
        # TrainStep via put_batch) then pays ZERO per-batch host work;
        # other formats still yield padded Batches.
        self.emit_compact = emit_compact
        # Parse/pack run on worker threads under prefetch/parse_workers,
        # so their phase seconds OVERLAP the consumer's wall-clock — the
        # trainer reports them in the epoch record's "overlapped" dict,
        # never in the additive main-thread accounting.
        self.obs = obs if obs is not None else NULL_OBS
        # Native pack folds remap + hot steering + padding into one C
        # pass (xf_pack_batch); the numpy fallback applies the remap at
        # parse time and pads/steers with pack_batch.
        from xflow_tpu import native

        self._native_pack = native.available()
        # Self-healing (docs/ROBUSTNESS.md): transient read/parse
        # failures retry with backoff; a block that still fails is
        # quarantined (skipped + health row) until the budget trips.
        # Counters shared across parse workers — guarded (XF003/XF008).
        self.io_retries = io_retries
        self.io_retry_backoff_s = io_retry_backoff_s
        self.max_quarantined_frac = max_quarantined_frac
        self._q_lock = threading.Lock()
        self._blocks_seen = 0
        self._quarantined = 0

    # -- self-healing -------------------------------------------------------

    def _parse_block_healed(self, raw: bytes, offset: int) -> ParsedBlock | None:
        """One block through the failpoint + retry + quarantine fabric.
        Returns None when the block was quarantined (the stream skips
        it); raises :class:`QuarantineExceeded` past the budget.
        Failpoint sites: ``loader.read_block`` (arm as a transient —
        retries heal it with zero data loss) and ``loader.parse_record``
        (arm persistent — retries exhaust, the block quarantines)."""
        with self._q_lock:
            self._blocks_seen += 1

        def attempt() -> ParsedBlock:
            failpoint("loader.read_block")
            failpoint("loader.parse_record")
            return self._parse_remap(raw)

        try:
            return retry_call(
                attempt,
                attempts=self.io_retries,
                backoff_s=self.io_retry_backoff_s,
                channel="loader",
                site=f"{self.path}@{offset}",
                obs=self.obs,
                retry_on=(OSError, ValueError, ChaosError),
            )
        except (OSError, ValueError, ChaosError) as e:
            self._quarantine(offset, e)
            return None

    def _quarantine(self, offset: int, err: BaseException) -> None:
        """Skip one unhealable block/record: counter + ``health`` row,
        then the budget check — quarantine is for isolated corruption,
        not a license to train past a rotten stream."""
        self.obs.counter("loader.quarantined")
        with self._q_lock:
            self._quarantined += 1
            quarantined, seen = self._quarantined, self._blocks_seen
        emit_health(
            self.obs,
            cause="record_quarantined",
            channel="loader",
            detail=f"{self.path}@{offset}: skipped after "
            f"{self.io_retries} retries ({type(err).__name__}: {err})",
        )
        budget = max(1, math.ceil(self.max_quarantined_frac * seen))
        if quarantined > budget:
            emit_health(
                self.obs,
                cause="quarantine_budget_exceeded",
                channel="loader",
                detail=f"{self.path}: {quarantined} of {seen} blocks "
                f"quarantined (budget {budget})",
            )
            raise QuarantineExceeded(
                f"{self.path}: {quarantined} quarantined blocks exceed "
                f"the budget ({budget} of {seen} seen, "
                f"max_quarantined_frac={self.max_quarantined_frac}) — "
                f"last error: {type(err).__name__}: {err}"
            ) from err

    def _apply_remap(self, block: ParsedBlock) -> ParsedBlock:
        if (
            self.remap is not None
            and not self._native_pack
            and len(block.keys)
        ):
            # frequency remap: pure row-placement permutation (io/freq.py)
            block.keys = self.remap[block.keys]
        return block

    def _parse_remap(self, raw: bytes) -> ParsedBlock:
        with self.obs.phase("parse"):
            block = self._apply_remap(self.parse_fn(raw))
        self.obs.counter("loader.parse_bytes", len(raw))
        self.obs.counter("loader.blocks")
        return block

    def _pack(self, block: ParsedBlock, start: int, end: int) -> Batch:
        with self.obs.phase("pack"):
            if self._native_pack:
                from xflow_tpu.native import native_pack_batch

                return native_pack_batch(
                    block, start, end, self.batch_size, self.max_nnz,
                    self.hot_size, self.hot_nnz, self.remap,
                )
            return pack_batch(
                block, start, end, self.batch_size, self.max_nnz,
                self.hot_size, self.hot_nnz,
            )

    def iter_batches(
        self, start_offset: int = 0, parse_workers: int = 0
    ) -> Iterator[tuple[Batch, int]]:
        """Yield (batch, resume_offset) pairs for one pass over the shard.

        Batches are FULL (batch_size real examples) regardless of the
        text block size: parsed blocks accumulate in a carry buffer and
        only the shard's final batch is zero-weight padded.  (Without
        this, block_bytes ≪ batch_size lines would make every batch
        mostly padding — wasted device cycles.)

        ``resume_offset`` is the byte offset of the earliest block with
        samples not yet yielded — pass it back as ``start_offset`` to
        resume; up to one block plus one carry may replay (resume
        granularity is the block, as in the reference's block loader,
        load_data_from_disk.cc:103-124).

        With parse_workers > 1, whole blocks parse+remap concurrently on
        a thread pool, order-preserving (the native parser and numpy
        release the GIL for the heavy part) — the TPU-era replacement
        for the reference's per-minibatch ThreadPool fan-out
        (lr_worker.cc:190-196).

        Binary block-cache shards (io/binary.py, sniffed by magic) skip
        parsing entirely — records stream at memory speed; parse_workers
        is irrelevant there.  Packed-batch shards (io/packed.py) skip
        batch assembly too: records ARE finished device-ready batches.
        The (batch, resume_offset) contract is identical for all three
        formats.
        """
        from xflow_tpu.io import binary, packed

        # chaos site: shard open/sniff fault — distinct from the
        # per-record sites so open-time failures are injectable (XF018)
        failpoint("loader.open_shard")
        with open(self.path, "rb") as f:
            magic = f.read(len(binary.MAGIC))
            if magic == binary.MAGIC:
                yield from self._iter_binary(f, start_offset)
                return
            if magic == packed.MAGIC:
                yield from self._iter_packed(f, start_offset)
                return
            f.seek(start_offset)

            def parsed_blocks() -> Iterator[tuple[ParsedBlock, int, int]]:
                # every block rides _parse_block_healed (retry +
                # quarantine); a None result is a quarantined block —
                # skipped, never yielded (resume offsets stay
                # consistent: the skip consumes the block's bytes)
                offset = start_offset
                if parse_workers <= 1:
                    for raw in BlockReader(f, self.block_bytes):
                        next_offset = offset + len(raw)
                        block = self._parse_block_healed(raw, offset)
                        if block is not None:
                            yield block, offset, next_offset
                        offset = next_offset
                    return
                from collections import deque
                from concurrent.futures import ThreadPoolExecutor

                with ThreadPoolExecutor(max_workers=parse_workers) as ex:
                    pending: deque = deque()
                    for raw in BlockReader(f, self.block_bytes):
                        next_offset = offset + len(raw)
                        pending.append(
                            (
                                ex.submit(
                                    self._parse_block_healed, raw, offset
                                ),
                                offset,
                                next_offset,
                            )
                        )
                        offset = next_offset
                        while len(pending) > parse_workers + 1:
                            fut, off, noff = pending.popleft()
                            block = fut.result()
                            if block is not None:
                                yield block, off, noff
                    while pending:
                        fut, off, noff = pending.popleft()
                        block = fut.result()
                        if block is not None:
                            yield block, off, noff

            yield from self._batches_from_blocks(parsed_blocks(), start_offset)

    def _iter_binary(
        self, f, start_offset: int
    ) -> Iterator[tuple[Batch, int]]:
        """Batch stream over a binary block-cache shard (io/binary.py):
        records already hold parsed CSR; reduction to [0, table_size)
        and the remap happen at load."""
        from xflow_tpu.io import binary

        blocks = (
            (self._apply_remap(b), off, noff)
            for b, off, noff in binary.iter_blocks(
                f,
                self.table_size,
                start_offset,
                expect_hash_mode=self.hash_mode,
                expect_hash_seed=self.hash_seed,
            )
        )
        yield from self._batches_from_blocks(blocks, start_offset)

    def _iter_packed(
        self, f, start_offset: int
    ) -> Iterator[tuple[Batch, int]]:
        """Batch stream over a packed-batch shard (io/packed.py): each
        record is a finished Batch — no parse, no assembly.  The cache's
        baked-in batch geometry must match this loader exactly."""
        from xflow_tpu.io import packed

        # runs on a stream thread before the shard's first batch can be
        # read: the epoch record books it under ``overlapped``
        self.obs.counter("loader.shard_opens")
        with self.obs.phase("shard_open"):
            f.seek(0)
            meta, _ = packed.read_header(f)
            # the remap's one sha256, or the wait for the stream that
            # is computing it, or (every later open) a lookup
            with self.obs.phase("remap_digest"):
                digest = self._remap_digest.get(self.obs)
            packed.check_compat(
                meta,
                batch_size=self.batch_size,
                cold_nnz=self.max_nnz,
                hot_nnz=self.hot_nnz if self.hot_size else 0,
                hot_size=self.hot_size,
                table_size=self.table_size,
                hash_mode=self.hash_mode,
                hash_seed=self.hash_seed,
                remap_sha256=digest,
                numeric_fields=self.numeric_fields,
            )
        flight = self.obs.flight
        if self.emit_compact and meta.get("version", 1) == 2:
            records = packed.iter_compact_batches(f, start_offset)
        else:
            records = packed.iter_batches(f, start_offset)
        while True:
            # the pull itself (the record's mmap read and, for a padded
            # consumer of a v2 shard, CompactBatch.expand()), not the
            # consumer's time between yields: on a stream thread, so
            # the epoch record books it under ``overlapped``
            with self.obs.phase("batch_read"):
                record = next(records, None)
            if record is None:
                break
            batch, offset, next_offset = record
            with self._q_lock:
                self._blocks_seen += 1
            try:
                # the packed-record corruption site: a fire here
                # quarantines THIS record (skip + health row + budget
                # check) and the stream continues at the next one
                failpoint("loader.packed_record")
            except ChaosError as e:
                self._quarantine(offset, e)
                continue
            if flight is not None:
                flight.note_loader("packed_batch")
            yield batch, next_offset

    def _batches_from_blocks(
        self,
        blocks: Iterator[tuple[ParsedBlock, int, int]],
        start_offset: int,
    ) -> Iterator[tuple[Batch, int]]:
        """Shared carry/batch assembly over any (block, offset,
        next_offset) source (text parser or binary cache)."""
        carry: ParsedBlock | None = None
        end_offset = start_offset
        flight = self.obs.flight
        for block, raw_offset, next_offset in blocks:
            # watchdog heartbeat (obs/flight.py): the input pipeline is
            # alive.  A starving trainer with a BEATING loader points
            # at transfer/backpressure, not at parsing.
            if flight is not None:
                flight.note_loader("block")
            end_offset = next_offset
            if carry is not None and carry.num_samples:
                block = _concat_blocks(carry, block)
            carry = None
            n = block.num_samples
            start = 0
            while n - start >= self.batch_size:
                end = start + self.batch_size
                # resume = earliest block holding a not-yet-yielded
                # sample.  The carry is always < batch_size samples,
                # so the first batch of this loop consumes it whole:
                # unyielded samples start in this raw block (or past
                # it entirely when end == n).
                resume = next_offset if end == n else raw_offset
                yield self._pack(block, start, end), resume
                start = end
            if start < n:
                carry = _slice_block(block, start)
        if carry is not None and carry.num_samples:
            # the stream's final (partial) batch consumes everything
            yield self._pack(carry, 0, carry.num_samples), end_offset

    def prefetch(
        self, depth: int, start_offset: int = 0, parse_workers: int = 0
    ) -> Iterator[tuple[Batch, int]]:
        """iter_batches with parse/pack running on a background thread,
        ``depth`` batches ahead of the consumer."""
        return _prefetch_iter(
            self.iter_batches(start_offset, parse_workers), depth,
            obs=self.obs,
        )

    def count_examples(self) -> int:
        from xflow_tpu.io import binary, packed

        if binary.is_binary_shard(self.path):
            return binary.shard_example_count(self.path)
        if packed.is_packed_shard(self.path):
            return packed.shard_example_count(self.path)
        n = 0
        # metadata sizing pass for planners, not the streamed training
        # path — the read path carries loader.* sites (xf: ignore[XF018])
        with open(self.path, "rb") as f:
            for line in f:
                if line.strip():
                    n += 1
        return n


_SENTINEL = object()


class _PrefetchIter:
    """``it`` running on a daemon producer thread, buffering up to
    ``depth`` items.  Exceptions propagate to the consumer.

    The round-4 design relied on a queue-put timeout plus GC to stop
    the producer when a consumer abandoned the iterator — which LEAKS
    the thread (and its open shard file) until the garbage collector
    happens to run the generator's finally block.  This object makes
    shutdown explicit: ``close()`` signals the producer, drains the
    queue so a blocked put wakes immediately, and joins the thread.
    Trainer.close() closes every live prefetch it spawned; use the
    iterator as a context manager elsewhere.  ``depth <= 0`` degrades
    to a synchronous passthrough with the same close() surface."""

    def __init__(self, it: Iterator, depth: int, obs=None):
        self._source = it
        self._closed = False
        self._close_done = False
        self._close_lock = threading.Lock()
        self._obs = obs if obs is not None else NULL_OBS
        self._thread: threading.Thread | None = None
        # Producer backpressure accounting: wall seconds the producer
        # spent blocked on a FULL queue (the consumer wasn't ready).
        # The fan-out pool (io/fanout.py) reads this per stream to
        # separate a slow reader (straggler) from a saturated consumer.
        self._stats_lock = threading.Lock()
        self._stall_seconds = 0.0
        if depth <= 0:
            return
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _put_or_abort(self, item) -> bool:
        flight = self._obs.flight
        t0 = time.perf_counter()
        try:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    # XF009 heartbeat: the producer is alive but
                    # blocked on a full queue — a 'backpressure' beat
                    # lets the watchdog tell a wedged CONSUMER (loader
                    # beating, no consumption) from a dead input
                    # pipeline (no beats)
                    if flight is not None:
                        flight.note_loader("backpressure")
                    continue
            return False
        finally:
            # anything past the free-slot fast path (microseconds) was
            # the producer waiting on the consumer; the 1ms floor keeps
            # per-batch noise out of the stall ledger
            dt = time.perf_counter() - t0
            if dt > 1e-3:
                self._note_stall(dt)

    def _note_stall(self, dt: float) -> None:
        with self._stats_lock:
            self._stall_seconds += dt

    def stall_seconds(self) -> float:
        """Cumulative producer-side backpressure (blocked-on-full-queue)
        wall seconds so far.  Safe from any thread."""
        with self._stats_lock:
            return self._stall_seconds

    def _produce(self) -> None:
        try:
            for item in self._source:
                if not self._put_or_abort(item):
                    return
            self._put_or_abort(_SENTINEL)
        except BaseException as e:  # propagate to consumer
            self._put_or_abort(e)
        finally:
            # an abandoned generator stays suspended inside its `with`
            # blocks (iter_batches: the open shard file, the parse
            # pool's threads) for as long as anything references this
            # iterator, a traceback included: unwind it here, on the
            # thread that ran it
            close = getattr(self._source, "close", None)
            if close is not None:
                close()

    def __iter__(self) -> "_PrefetchIter":
        return self

    def __next__(self):
        if self._thread is None:  # synchronous passthrough
            if self._closed:
                raise StopIteration
            return next(self._source)
        if self._closed:
            raise StopIteration
        item = self._q.get()
        if item is _SENTINEL:
            self._closed = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._closed = True
            raise item
        return item

    def close(self, join_timeout: float = 5.0) -> None:
        """Stop the producer thread and release its resources.
        Idempotent; safe from any thread.  A producer that OUTLIVES
        the join (wedged in parse/read, not on the queue) is surfaced
        — warning, ``loader.leaked_threads`` counter, and a ``health``
        row — instead of silently leaking with its open shard file."""
        self._closed = True
        if self._thread is None:
            return
        with self._close_lock:
            # a second close() — sequential (consumer closed directly,
            # then Trainer.close() reaps _live_prefetch) or concurrent
            # (the "safe from any thread" contract) — must not pay
            # another join_timeout or double-report a wedged producer
            if self._close_done:
                return
            self._close_done = True
        self._stop.set()
        # drain so a producer blocked on a full queue observes the
        # stop event on its next timeout tick at the latest
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=join_timeout)
        if self._thread.is_alive():
            warnings.warn(
                "prefetch producer thread outlived its close() join "
                f"({join_timeout:.1f}s) — it is wedged in parse/read "
                "and still holds the shard file open",
                RuntimeWarning,
                stacklevel=2,
            )
            self._obs.counter("loader.leaked_threads")
            flight = self._obs.flight
            if flight is not None and flight.metrics_logger is not None:
                from xflow_tpu.obs.schema import health_row

                flight.metrics_logger.log("health", health_row(
                    cause="prefetch_thread_leak",
                    channel="loader",
                    silence_seconds=join_timeout,
                    threshold_seconds=join_timeout,
                    detail="producer outlived close() join",
                    channels=flight.snapshot()["channels"],
                ))

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def __enter__(self) -> "_PrefetchIter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _prefetch_iter(it: Iterator, depth: int, obs=None) -> _PrefetchIter:
    return _PrefetchIter(it, depth, obs=obs)

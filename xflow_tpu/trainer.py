"""Training/eval driver — the worker loop of the reference
(LRWorker::train / batch_training / predict, lr_worker.cc:73-217)
re-expressed as a host loop feeding the pjit'd step.

Shard handling: the reference gives each of M worker processes one file
shard ``prefix-%05d`` by rank (lr_worker.cc:210).  Here one SPMD process
(per host) walks every shard assigned to it (``shard index % num_hosts
== host``); device-level data parallelism happens inside the step via
the batch's sharding, not via processes.

Evaluation reproduces the rank-0-only predict pass (lr_worker.cc:212-
215): stream the test shard(s), compute pctr, accumulate (label, pctr),
report rank-sum AUC + logloss, optionally dump prediction lines (the
reference's pred_<rank>_<block>.txt, lr_worker.cc:74-78).
"""

from __future__ import annotations

import glob
import os
import sys
import time
from collections import deque
from typing import Any, Callable, Iterator

import numpy as np

import jax

from xflow_tpu.config import Config
from xflow_tpu.io.batch import Batch
from xflow_tpu.io.loader import ShardLoader, make_parse_fn, shard_path
from xflow_tpu.io.packed import RemapDigest
from xflow_tpu.models import make_model
from xflow_tpu.obs import NULL_OBS, startup
from xflow_tpu.optim import make_optimizer
from xflow_tpu.parallel.mesh import make_mesh
from xflow_tpu.parallel.step import TrainStep, abstract_like, init_state
from xflow_tpu.utils.checkpoint import (
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from xflow_tpu.utils.metrics import AucAccumulator


# Loader phases that run on stream/prefetch worker threads: the epoch
# record books them under ``overlapped`` (they hide behind input_stall),
# never under ``phases``, which sums to the epoch's wall seconds.
WORKER_PHASES = ("parse", "pack", "shard_open", "remap_digest", "batch_read")


def _ring_workers(depth: int) -> int:
    """Staging-ring worker count for a given depth: one per slot up to
    a core-bounded cap (at least 2 once the ring is deep enough for
    double buffering — compaction on one worker must be able to overlap
    a transfer on another)."""
    if depth <= 1:
        return 1
    return min(depth, max(2, min(4, (os.cpu_count() or 2) - 1)))


def find_shards(prefix: str) -> list[str]:
    """All existing ``prefix-%05d`` shards, in rank order; if none match,
    treat ``prefix`` itself as a single file."""
    shards = sorted(glob.glob(glob.escape(prefix) + "-" + "[0-9]" * 5))
    if not shards:
        if os.path.exists(prefix):
            return [prefix]
        raise FileNotFoundError(f"no shards matching {prefix}-NNNNN and no file {prefix}")
    return shards


class Trainer:
    def __init__(
        self,
        cfg: Config,
        mesh=None,
        log: Callable[[str], None] | None = None,
    ):
        # the process's start-up timeline and compile watch
        # (obs/startup.py): always on, with or without an Obs
        startup.watch_compiles()
        with startup.phase("trainer_init"):
            self._init(cfg, mesh, log)

    def _init(self, cfg: Config, mesh, log) -> None:
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg.num_devices)
        ndev = self.mesh.devices.size
        if cfg.batch_size % ndev:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by {ndev} devices"
            )
        if cfg.table_size % ndev:
            raise ValueError(
                f"table_size {cfg.table_size} not divisible by {ndev} devices"
            )
        if cfg.table_shards not in (0, ndev):
            raise ValueError(
                f"table_shards {cfg.table_shards} stated, but this mesh of "
                f"{ndev} device(s) cuts every table into {ndev} row "
                "block(s)"
            )
        with startup.phase("step_build"):
            self.model = make_model(cfg)
            self.optimizer = make_optimizer(cfg)
            self.step = TrainStep(self.model, self.optimizer, cfg, self.mesh)
        # Tiered store (Config.store_mode; store/): device state is the
        # bounded hot tier, NOT a [T, D] table — init_state at the
        # north-star 2^28 geometry would allocate the very buffers the
        # store exists to avoid.
        with startup.phase("state_init"):
            if self.step.store is not None:
                self.state = self.step.store.init_device_state()
            else:
                self.state = init_state(
                    self.model, self.optimizer, cfg, self.mesh
                )
        self.epoch = 0
        # the first train_epoch() is start-up too (the train program's
        # compile or load, the pipeline's first fill, op_scopes): it
        # runs under phase first_epoch and its stats carry ``_startup``
        self._first_epoch_done = False
        # (shard_idx, byte_offset) to start the next epoch from; set by
        # restore(), consumed by the first train_epoch() after it.
        self._resume_cursor: tuple[int, int] = (0, 0)
        self._log = log if log is not None else lambda s: print(s, file=sys.stderr)
        # Multi-host: each process reads its own shard subset.
        self.host = jax.process_index()
        self.num_hosts = jax.process_count()
        self._global_steps = 0  # across epochs; drives the profile trigger
        # train shapes whose program's instruction -> scope map has been
        # logged (TrainStep.op_scopes; live Obs only)
        self._scoped_shapes: set = set()
        # Live loader prefetch iterators (io/loader.py::_PrefetchIter),
        # closed explicitly by close() so abandoned producer threads
        # (crash, preemption, consumer break) never outlive the Trainer.
        self._live_prefetch: set = set()
        # Live transfer-ahead generators (_transfer_ahead): a mid-epoch
        # break (preemption) leaves the generator suspended inside its
        # `with ThreadPoolExecutor`, executor threads alive, until GC.
        # close() reaps them explicitly (XF006 — the _PrefetchIter leak
        # class, executor edition).
        self._live_transfer: set = set()
        # Continuous-training ingestion cursor (stream/follower.py::
        # IngestCursor), registered by the stream driver: close()
        # flushes it through the cursor's own atomic tmp+os.replace
        # path — the same discipline as checkpoints — so a preemption
        # between shard-complete and cursor-write replays at most one
        # shard (the at-least-once contract, docs/CONTINUOUS.md).
        self._stream_cursor = None
        # Observability (obs/__init__.py): a live tracer/registry bundle
        # when metrics or tracing is requested, else the shared no-op
        # NULL_OBS (zero per-step allocation).  Threaded into the step
        # (put_batch/dispatch phases) and every loader (parse/pack).
        self.obs = NULL_OBS
        if (
            cfg.metrics_out
            or cfg.obs_trace_out
            or cfg.obs_flight_out
            or cfg.obs_watchdog
        ):
            from xflow_tpu.obs import make_obs
            from xflow_tpu.utils.compile_cache import key_by_source

            # the epoch that first meets a train shape reads the xf.*
            # scopes off its running program (TrainStep.op_scopes): that
            # program must be this source's, not an older one's from
            # the persistent cache
            key_by_source()
            self.obs = make_obs(
                trace=bool(cfg.obs_trace_out),
                trace_capacity=cfg.obs_trace_capacity,
                rank=self.host,
                step_fn=lambda: self._global_steps,
            )
        self.step.obs = self.obs
        if self.step.store is not None:
            # checkpoint/export/close-path store heals report through
            # the live bundle (store/tiered.py complete_pending)
            self.step.store.obs = self.obs
        self.metrics_logger = None
        if cfg.metrics_out:
            from xflow_tpu.utils.logging import MetricsLogger

            # every host writes its own rank-suffixed file in
            # multi-host runs; `python -m xflow_tpu.obs merge` combines
            # them into one rank-tagged stream for `obs doctor`
            path = cfg.metrics_out
            if self.num_hosts > 1:
                path = f"{path}-r{self.host}"
            self.metrics_logger = MetricsLogger(
                path, run_header=self._run_header()
            )
            # the self-healing fabric's health-row sink (chaos/heal.py):
            # retries/quarantines/restarts are loud whenever a metrics
            # stream exists, flight recorder or not
            if self.obs.enabled:
                self.obs.metrics_logger = self.metrics_logger
        # Chaos fabric (xflow_tpu/chaos/; docs/ROBUSTNESS.md): arm the
        # failpoint registry from the config spec / env var, and route
        # its `chaos` audit rows into this run's metrics stream.
        from xflow_tpu import chaos

        # a config-armed schedule's lifetime is THIS trainer's: close()
        # disarms it, so a later non-chaos Trainer in the same process
        # never inherits the fault schedule.  Env-var arming is
        # process-level intent and stays.
        self._armed_chaos = bool(cfg.chaos_spec)
        if cfg.chaos_spec:
            chaos.arm(cfg.chaos_spec)
        else:
            chaos.arm_from_env()
        if chaos.armed() is not None and self.metrics_logger is not None:
            chaos.attach_logger(self.metrics_logger)
        # Flight recorder + stall watchdog (obs/flight.py, watchdog.py):
        # the recorder rides the live Obs so ShardLoader/PredictEngine
        # heartbeat it; the watchdog monitor starts now and stops in
        # close().  _flight_reason records WHY the run is ending so
        # close() writes exactly one dump on the crash/preemption paths.
        self._flight = None
        self._watchdog = None
        self._flight_reason: tuple[str, BaseException | None] | None = None
        self._last_batch_shape: tuple | None = None
        if self.obs.enabled and (cfg.obs_flight_out or cfg.obs_watchdog):
            from xflow_tpu.obs.flight import FlightRecorder

            self._flight = FlightRecorder(
                capacity=cfg.obs_flight_events,
                metrics_logger=self.metrics_logger,
                registry=self.obs.registry,
                tracer=self.obs.tracer if self.obs.tracer.enabled else None,
                rank=self.host,
            )
            self.obs.flight = self._flight
        if cfg.obs_watchdog and self._flight is not None:
            from xflow_tpu.obs.watchdog import Watchdog

            self._watchdog = Watchdog(
                self._flight,
                input_s=cfg.obs_watchdog_input_s,
                device_s=cfg.obs_watchdog_device_s,
                serve_s=cfg.obs_watchdog_serve_s,
                poll_s=cfg.obs_watchdog_poll_s,
                flight_out=self._flight_path(),
                metrics_logger=self.metrics_logger,
                tracer=self.obs.tracer if self.obs.tracer.enabled else None,
                log=self._log,
            )
            self._watchdog.start()
        # Live telemetry plane (obs/export.py, docs/OBSERVABILITY.md
        # "Operating a live fleet"): a host resource sampler emitting
        # `resource` rows into this run's metrics stream, and a
        # standalone /metrics exposition endpoint over the live
        # registry for runs with no HTTP surface of their own.  Both
        # are reaped by close() before the metrics logger shuts.
        self._resource_sampler = None
        self._exporter = None
        if cfg.obs_resource_every_s > 0 and self.metrics_logger is not None:
            from xflow_tpu.obs.export import ResourceSampler

            self._resource_sampler = ResourceSampler(
                metrics_logger=self.metrics_logger,
                registry=self.obs.registry if self.obs.enabled else None,
                interval_s=cfg.obs_resource_every_s,
            )
            self._resource_sampler.start()
        if cfg.obs_export_port:
            from xflow_tpu.obs.export import MetricsExporter

            # rank offsets the port so N single-box trainers coexist
            self._exporter = MetricsExporter(
                self.obs.registry,
                port=cfg.obs_export_port + self.host,
            )
            self._exporter.start()
            self._log(
                f"metrics exporter serving {self._exporter.address}"
                "/metrics"
            )
        # Lock-order sanitizer (analysis/sanitizer.py): when armed —
        # Config flag or XFLOW_LOCK_SANITIZER env — the obs-stack locks
        # are swapped for instrumented wrappers so real acquisition
        # orders can be cross-checked against the static XF007 graph
        # (scripts/check_concurrency.py).  The bare env-var presence
        # check only gates the IMPORT (off = nothing imported or
        # allocated); armed() is the one authoritative parse.
        if cfg.obs_lock_sanitizer or os.environ.get("XFLOW_LOCK_SANITIZER"):
            from xflow_tpu.analysis.sanitizer import armed, global_sanitizer

            if cfg.obs_lock_sanitizer or armed():
                san = global_sanitizer()
                for obj in (
                    self.metrics_logger,
                    self._flight,
                    self._watchdog,
                    self.obs.registry,
                ):
                    if obj is not None and hasattr(obj, "_lock"):
                        san.instrument(obj, "_lock")
        self._profiled = False
        self._preempted = False
        self._preempt_agreed = False
        # Hot-table frequency remap (io/freq.py): loaded from the
        # checkpoint dir when present, else measured from a deterministic
        # sample of the training data (identical on every host).
        self.remap = None
        if cfg.hot_size_log2:
            with startup.phase("remap_init"):
                self._init_remap()
            # never written after this: its digest is taken once
            self.remap.flags.writeable = False
        else:
            # guard the reverse of _init_remap's table_size check: a
            # checkpoint trained WITH a hot table stores rows in the
            # permuted space; resuming it hot-off would read wrong rows
            path = self._remap_path()
            if path is not None:
                if os.path.exists(path):
                    raise ValueError(
                        f"{path} exists: this checkpoint_dir was trained "
                        "with a hot table; set hot_size_log2 to match "
                        "(or use a fresh checkpoint_dir)"
                    )
        # what a packed shard's header is checked against: hashed at the
        # first packed open, once for every loader of this trainer
        self._remap_digest = RemapDigest(self.remap)

    # -- observability lifecycle -------------------------------------------

    def _run_header(self) -> dict:
        """Contents of the metrics file's ``run_start`` delimiter row:
        enough to tell two appended runs apart (the file opens in append
        mode) and to check their configs match without any log parsing."""
        return {
            "run_id": f"{int(time.time() * 1000):x}-{os.getpid():x}",
            "config_digest": self.cfg.digest(),
            "rank": self.host,
            "num_hosts": self.num_hosts,
            "model": self.cfg.model,
        }

    def _flight_path(self) -> str:
        path = self.cfg.obs_flight_out
        if path and self.num_hosts > 1:
            path = f"{path}-r{self.host}"
        return path

    def _pulse(self, phase: str) -> None:
        """Trainer heartbeat: the main loop just entered ``phase``.
        Feeds the flight recorder's ring AND the watchdog's liveness
        view — one clock read + locked dict store, nothing device-side
        (XF002)."""
        if self._flight is not None:
            self._flight.note_phase(phase, self._global_steps)

    def _note_batch_shape(self, batch: Batch, shard_idx: int) -> None:
        """Record the in-flight batch geometry, but only when it
        CHANGES (static loader shapes mean ~one note per run; a new
        shape right before a hang is exactly the forensic that points
        at a recompile or a mis-sized external batch)."""
        if self._flight is None:
            return
        shape = (batch.batch_size, batch.max_nnz, batch.hot_nnz)
        if shape != self._last_batch_shape:
            self._last_batch_shape = shape
            self._flight.note_batch({
                "rows": batch.batch_size,
                "cold_nnz": batch.max_nnz,
                "hot_nnz": batch.hot_nnz,
                "shard": shard_idx,
            })

    def flight_dump(self, reason: str, exc: BaseException | None = None) -> None:
        """Mark the run as dying for ``reason``; close() writes the
        dump (once) as part of the flush path, so metrics flush and
        dump ordering stay on the one exit road."""
        if self._flight_reason is None:
            self._flight_reason = (reason, exc)

    def close(self) -> None:
        """Flush-and-close observability outputs: stop the watchdog,
        write the flight dump when a crash/preemption was recorded,
        then the metrics JSONL and (when tracing) the Chrome trace
        export.  Idempotent.  train() calls it on its exception and
        preemption paths; use the Trainer as a context manager (or
        call this) to cover every other exit."""
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._resource_sampler is not None:
            # joins the sampler thread and emits the final resource
            # row — must precede metrics_logger.close() below
            self._resource_sampler.close()
        if self._exporter is not None:
            self._exporter.close()
        for gen in list(self._live_transfer):
            # GeneratorExit at the suspended yield -> _transfer_ahead's
            # abandon path -> shutdown(wait=False, cancel_futures=True):
            # idle ring workers exit on the signal, and a WEDGED one
            # cannot hang this (crash/preemption) cleanup path
            gen.close()
        self._live_transfer.clear()
        for it in list(self._live_prefetch):
            it.close()
        self._live_prefetch.clear()
        if self.step.store is not None:
            # flush the pending miss write-back and reap the promotion
            # worker (bounded join; a leak lands as a health row before
            # the metrics logger closes below)
            self.step.store.close()
        if self._stream_cursor is not None:
            # durable ingestion position on EVERY exit road (the
            # checkpoint discipline): a graceful preemption mid-shard
            # resumes at the exact batch offset; only a hard kill
            # falls back to the shard-boundary flush (<= 1 shard
            # replayed — at-least-once, docs/CONTINUOUS.md)
            try:
                self._stream_cursor.flush()
            except OSError as e:
                self._log(f"stream cursor flush failed: {e}")
        if (
            self._flight is not None
            and self._flight_reason is not None
            and self._flight_path()
        ):
            reason, exc = self._flight_reason
            self._flight_reason = None  # one dump per incident
            path = self._flight_path()
            if self._watchdog is not None and self._watchdog.dump_count:
                # the watchdog already dumped DURING the stall (stuck
                # thread stacks — the forensic that matters); the
                # exit-time dump must not overwrite it
                path = f"{path}.exit"
            self._flight.dump(path, reason, exc=exc)
        self._export_trace()
        from xflow_tpu import chaos

        if self.metrics_logger is not None:
            # an armed registry must not keep logging through a closed
            # logger (detach is a no-op for anyone else's logger)
            chaos.detach_logger(self.metrics_logger)
            self.metrics_logger.close()
        if self._armed_chaos:
            # the schedule this trainer armed from its config dies with
            # it (idempotent; env-armed registries are left alone)
            chaos.disarm()

    def _export_trace(self) -> None:
        if not (self.cfg.obs_trace_out and self.obs.tracer.enabled):
            return
        path = self.cfg.obs_trace_out
        if self.num_hosts > 1:
            path = f"{path}-r{self.host}"
        try:
            self.obs.tracer.export_chrome(path)
        except OSError as e:
            self._log(f"trace export failed: {e}")

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    def _remap_path(self) -> str | None:
        if not self.cfg.checkpoint_dir:
            return None
        return os.path.join(self.cfg.checkpoint_dir, "remap.npy")

    def _init_remap(self) -> None:
        cfg = self.cfg
        from xflow_tpu.io import freq

        path = self._remap_path()
        if path is not None:
            existing = freq.load_remap(path)
            if existing is not None:
                if len(existing) != cfg.table_size:
                    raise ValueError(
                        f"saved remap at {path} has {len(existing)} rows "
                        f"but table_size is {cfg.table_size} — "
                        "table_size_log2 changed between runs?"
                    )
                self.remap = existing
                return
        if path is not None and latest_checkpoint(cfg.checkpoint_dir):
            raise ValueError(
                "hot table enabled but this checkpoint_dir holds a "
                "checkpoint trained WITHOUT one (no remap.npy): the table "
                "rows live in the unpermuted key space — set "
                "hot_size_log2=0 to resume it, or use a fresh "
                "checkpoint_dir"
            )
        if not cfg.train_path:
            raise ValueError(
                "hot table enabled but no train_path to sample key "
                "frequencies from and no saved remap in checkpoint_dir"
            )
        # Global shard list (not this host's subset) so every host
        # computes the identical permutation without communication.
        shards = find_shards(cfg.train_path)
        counts = freq.count_keys(
            shards,
            self._parse_fn(),
            cfg.table_size,
            cfg.freq_sample_mib << 20,
            cfg.block_mib << 20,
        )
        self.remap = freq.build_remap(counts, cfg.hot_size)
        mass = freq.hot_mass(counts, self.remap, cfg.hot_size)
        self._log(
            f"hot remap: {cfg.hot_size} rows capture {mass:.1%} of "
            f"sampled feature occurrences"
        )
        if path is not None and self.host == 0:
            os.makedirs(cfg.checkpoint_dir, exist_ok=True)
            freq.save_remap(path, self.remap)

    # -- data --------------------------------------------------------------

    def _parse_fn(self):
        cfg = self.cfg
        return make_parse_fn(
            cfg.table_size,
            cfg.hash_mode,
            cfg.seed,
            prefer_native=cfg.native_parser,
            numeric_fields=cfg.numeric_fields,
        )

    def _loader(self, path: str) -> ShardLoader:
        cfg = self.cfg
        return ShardLoader(
            path,
            batch_size=cfg.batch_size,
            max_nnz=cfg.max_nnz,
            table_size=cfg.table_size,
            block_mib=cfg.block_mib,
            hash_mode=cfg.hash_mode,
            hash_seed=cfg.seed,
            parse_fn=self._parse_fn(),
            remap=self.remap,
            hot_size=cfg.hot_size,
            hot_nnz=cfg.hot_nnz,
            numeric_fields=cfg.numeric_fields,
            obs=self.obs,
            # v2 packed shards skip expansion AND re-compaction when
            # the step consumes the dict wire (io/compact.py)
            emit_compact=self.step.dict_wire,
            io_retries=cfg.io_retries,
            io_retry_backoff_s=cfg.io_retry_backoff_s,
            max_quarantined_frac=cfg.max_quarantined_frac,
            remap_digest=self._remap_digest,
        )

    def _tracked_prefetch(self, loader: ShardLoader, depth, offset, workers):
        """loader.prefetch registered for explicit shutdown: the
        producer thread (and its open shard file) dies on
        Trainer.close() even if the consumer abandoned the iterator
        mid-shard (crash/preemption) — not whenever the GC notices."""
        it = loader.prefetch(depth, offset, workers)
        self._live_prefetch.add(it)
        return it

    def _parse_workers(self) -> int:
        w = self.cfg.parse_workers
        if w < 0:
            w = max(1, min(6, (os.cpu_count() or 1) - 1))
        return w

    def _my_shards(self, prefix: str) -> list[str]:
        shards = find_shards(prefix)
        return [s for i, s in enumerate(shards) if i % self.num_hosts == self.host]

    def iter_train_batches(
        self, start_shard: int = 0, start_offset: int = 0
    ) -> Iterator[tuple[Batch, int, int]]:
        """Yields (batch, shard_index, resume_offset) over one epoch.

        With ``Config.input_streams > 1`` the epoch's shard list fans
        out over N concurrent reader streams (io/fanout.py) — same
        batch sequence, same resume contract, parallel host work.

        When metrics are on, each finished shard logs a ``shard`` row
        with its observed loader throughput — wall-clock measured at
        the consumer, so it includes parse + pack + any consumer
        backpressure: the rate the training loop actually saw."""
        shards = self._my_shards(self.cfg.train_path)
        if self.cfg.input_streams > 1:
            yield from self._iter_fanout(shards, start_shard, start_offset)
            return
        depth = self.cfg.prefetch_batches
        for si, path in enumerate(shards):
            if si < start_shard:
                continue
            offset = start_offset if si == start_shard else 0
            loader = self._loader(path)
            workers = self._parse_workers()
            it = (
                self._tracked_prefetch(loader, depth, offset, workers)
                if depth
                else loader.iter_batches(offset, workers)
            )
            t_shard = time.perf_counter()
            examples = 0
            try:
                for batch, resume in it:
                    examples += batch.num_real()
                    self._note_batch_shape(batch, si)
                    yield batch, si, resume
            finally:
                if depth:
                    it.close()
                    self._live_prefetch.discard(it)
            self._log_shard_row(
                si, path, examples, time.perf_counter() - t_shard
            )

    def _log_shard_row(
        self, si: int, path: str, examples: int, dt: float
    ) -> None:
        if self.metrics_logger is None:
            return
        self.metrics_logger.log("shard", {
            "epoch": self.epoch,
            "shard": os.path.basename(path),
            "index": si,
            "examples": examples,
            "seconds": round(dt, 3),
            "examples_per_sec": round(examples / max(dt, 1e-9), 1),
        })

    def _log_stream_rows(self, pool) -> None:
        """Per-stream fan-out accounting (``stream`` rows,
        obs/schema.py): one row per reader stream per epoch with its
        finished-shard totals and backpressure stall — the input of
        `obs doctor`'s stream-straggler diagnosis and `obs summarize`'s
        throughput-spread line."""
        if self.metrics_logger is None:
            return
        for row in pool.stream_stats():
            self.metrics_logger.log("stream", {"epoch": self.epoch, **row})

    def _iter_fanout(
        self, shards: list[str], start_shard: int, start_offset: int
    ) -> Iterator[tuple[Batch, int, int]]:
        """iter_train_batches through the N-stream fan-out
        (io/fanout.py): stream s reads shards i % N == s concurrently,
        each with its own parse workers and host compaction
        (TrainStep.precompact), and the merge restores serial shard
        order — training is bitwise-identical to the one-stream path.
        Per-shard ``shard`` rows keep the serial path's consumer-side
        timing semantics; per-stream ``stream`` rows land when the
        epoch's pool winds down (including the preemption break)."""
        from xflow_tpu.io.fanout import ShardStreamPool

        cfg = self.cfg
        workers = self._parse_workers()
        n_eff = max(1, min(cfg.input_streams, len(shards) - start_shard))
        pool = ShardStreamPool(
            shards,
            self._loader,
            num_streams=cfg.input_streams,
            depth=max(1, cfg.prefetch_batches),
            start_shard=start_shard,
            start_offset=start_offset,
            # the serial path's parse fan-out divides across streams so
            # N streams don't multiply the thread budget
            parse_workers=max(1, workers // n_eff) if workers > 1 else workers,
            transform=self.step.precompact,
            obs=self.obs,
        )
        self._live_prefetch.add(pool)
        cur: int | None = None
        examples = 0
        t_shard = time.perf_counter()
        try:
            for batch, si, resume in pool:
                if cur is None:
                    cur = si
                elif si != cur:
                    self._log_shard_row(
                        cur, shards[cur], examples,
                        time.perf_counter() - t_shard,
                    )
                    cur = si
                    examples = 0
                    t_shard = time.perf_counter()
                examples += batch.num_real()
                self._note_batch_shape(batch, si)
                yield batch, si, resume
            if cur is not None:
                self._log_shard_row(
                    cur, shards[cur], examples,
                    time.perf_counter() - t_shard,
                )
        finally:
            pool.close()
            self._live_prefetch.discard(pool)
            self._log_stream_rows(pool)

    def _empty_batch(self) -> Batch:
        """All-padding batch (weights/mask 0): a no-op training step with
        the same static shapes the loader produces."""
        from xflow_tpu.io.batch import make_batch

        cfg = self.cfg
        b = cfg.batch_size
        k = cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
        z_i = np.zeros((b, k), np.int32)
        z_f = np.zeros((b, k), np.float32)
        return make_batch(
            z_i, z_i, z_f, z_f,
            np.zeros(b, np.float32), np.zeros(b, np.float32),
            cfg.hot_size, cfg.hot_nnz,
        )

    def _synced_batches(
        self,
        it: Iterator[tuple[Batch, int, int]],
        vote_preempt: bool = False,
    ) -> Iterator[tuple[Batch, int, int]]:
        """SPMD step-count agreement across hosts.

        Every pjit'd step is collective over the global mesh, so all
        processes MUST call it the same number of times — but hosts own
        different shard subsets (``i % num_hosts``) whose sizes differ
        when shards don't divide evenly (the reference had no such
        constraint: its workers were fully async, SURVEY §2 parallelism
        table).  A host whose local data ran out keeps feeding
        zero-weight padding batches (no-op updates: FTRL/SGD are
        idempotent at g=0) until every host votes done; the vote rides a
        1-int allgather per step.

        With ``vote_preempt`` the same allgather carries this host's
        preemption flag (vote 2): ANY host's SIGTERM stops every host at
        the same step, and the caller sees ``self._preempt_agreed`` —
        required because the subsequent checkpoint save is itself
        collective.  Single-host runs skip the voting entirely (the
        caller checks ``self._preempted`` directly).
        """
        if self.num_hosts == 1:
            yield from it
            return
        from jax.experimental import multihost_utils

        local_done = False
        last = (0, 0)
        pad: Batch | None = None
        while True:
            item = None
            if not local_done:
                try:
                    item = next(it)
                except StopIteration:
                    local_done = True
            mine = 2 if (vote_preempt and self._preempted) else (
                0 if local_done else 1
            )
            votes = np.asarray(
                multihost_utils.process_allgather(np.int32(mine))
            )
            if votes.max() == 2:
                self._preempt_agreed = True
                return  # a host was preempted: stop everyone at this step
            if votes.max() == 0:
                return  # every host is out of data
            if item is not None:
                last = (item[1], item[2])
                yield item
            else:
                # keep collectives aligned while other hosts still train
                if pad is None:
                    pad = self._empty_batch()
                yield pad, last[0], last[1]

    def _transfer_ahead(
        self, it: Iterator[tuple[Batch, int, int]], depth: int | None = None
    ) -> Iterator[tuple[Any, int, int]]:
        """Device staging ring: run put_batch (host-side compaction +
        h2d transfer) up to ``depth`` (Config.transfer_ahead_depth,
        >= 2 for double buffering) items ahead on worker threads so
        h2d transfers AND per-batch compaction overlap device
        compute.  Worker count scales with the ring depth
        (capped by the host's cores) so a deep ring can compact one
        batch while others are on the wire; the pending deque preserves
        submission order, so batch order — and training — is identical
        at ANY depth.  Single-host only: multi-host put_batch is
        collective (host_local_array_to_global_array) and must stay on
        the voting thread."""
        from concurrent.futures import ThreadPoolExecutor

        if depth is None:
            depth = self.cfg.transfer_ahead_depth
        ex = ThreadPoolExecutor(_ring_workers(depth))
        try:
            pending: deque = deque()
            for batch, si, resume in it:
                pending.append(
                    (ex.submit(self.step.put_batch, batch), si, resume)
                )
                # queue occupancy: steadily == depth+1 means the device
                # is the bottleneck; hovering at 0-1 means the consumer
                # drains transfers as fast as they arrive (input-bound)
                self.obs.observe("transfer_ahead_depth", len(pending))
                if len(pending) > depth:
                    fut, psi, presume = pending.popleft()
                    yield fut.result(), psi, presume
            while pending:
                fut, psi, presume = pending.popleft()
                yield fut.result(), psi, presume
            ex.shutdown()  # normal path: workers idle, returns fast
        except BaseException:
            # abandon (GeneratorExit from close(), a worker raising, a
            # consumer exception): do NOT wait — a worker wedged in a
            # put_batch h2d transfer would otherwise hang the caller's
            # cleanup path forever (XF006: shutdown must be bounded).
            # cancel_futures drops the un-started queue; idle workers
            # exit on the shutdown signal; a wedged in-flight worker is
            # left to finish on its own rather than held against.
            ex.shutdown(wait=False, cancel_futures=True)
            raise

    def prepare_batch(self, batch: Batch) -> Batch:
        """Bring an externally built Batch (raw hash-space keys, see
        io/batch.py) into this model's key space: apply the hot remap
        and re-steer the hot/cold sections.  Loader-produced batches are
        already prepared; this is for user-supplied batches.  Delegates
        to the shared io/batch.py::remap_batch (also the serving
        engine's prepare path — serve/engine.py)."""
        from xflow_tpu.io.batch import remap_batch

        return remap_batch(
            batch, self.remap, self.cfg.hot_size, self.cfg.hot_nnz
        )

    # -- training ----------------------------------------------------------

    def _stop_profile(self, flush_metric) -> None:
        """The ONE jax.profiler.stop_trace site.  The flush-then-stop
        invariant lives here: dispatch is async, so without blocking on
        a step metric first the trace would close before the profiled
        steps' device work ran."""
        if flush_metric is not None:
            # this block IS the flush-then-stop invariant: it must run
            # unconditionally, span or no span (xf: ignore[XF002])
            jax.device_get(flush_metric["logloss"])  # flush pending work
        jax.profiler.stop_trace()
        self._profiled = True

    def _timed_save(self, shard_idx: int, offset: int) -> float:
        """save() booked as the 'checkpoint' phase; returns the seconds
        so train_epoch reports checkpoint_seconds separately instead of
        letting saves silently deflate examples_per_sec.  A FAILED save
        (I/O error, ckpt.* failpoint) leaves a ``health`` row before
        re-raising — the crash-atomic protocol guarantees the previous
        complete generation survives for ``--resume auto``."""
        t0 = time.perf_counter()
        try:
            with self.obs.phase("checkpoint"):
                self.save(shard_idx, offset)
        except BaseException as e:
            if self.metrics_logger is not None:
                from xflow_tpu.obs.schema import health_row

                self.metrics_logger.log("health", health_row(
                    cause="checkpoint_save_failed",
                    channel="train",
                    silence_seconds=0.0,
                    threshold_seconds=0.0,
                    detail=f"{type(e).__name__}: {e} — previous "
                    "complete generation remains restorable",
                ))
            raise
        return time.perf_counter() - t0

    def train_epoch(self, start_shard: int = 0, start_offset: int = 0) -> dict:
        if self._first_epoch_done:
            return self._train_epoch(start_shard, start_offset)
        self._first_epoch_done = True
        with startup.phase("first_epoch"):
            stats = self._train_epoch(start_shard, start_offset)
        # the process's timeline up to its first steady step: train()
        # pops it into the ``startup`` metrics row
        stats["_startup"] = startup.snapshot()
        return stats

    def _train_epoch(self, start_shard: int, start_offset: int) -> dict:
        cfg = self.cfg
        obs = self.obs
        obs.registry.reset()  # epoch-scoped phase accounting
        compiles_before = startup.compile_totals()
        t0 = time.time()
        steps = 0
        ckpt_seconds = 0.0
        preempted = False
        device_metrics = []  # fetched once at epoch end to keep dispatch async
        first_wait = None  # seconds the loop waited for its first batch
        # shapes met for the first time this epoch (live Obs only): their
        # programs are mapped instruction -> scope at the epoch's end
        map_scopes = obs.enabled and self.step.store is None
        new_shapes: list = []
        profiling = False
        self._preempt_agreed = False
        last_cursor = (start_shard, start_offset)
        stream = self._synced_batches(
            self.iter_train_batches(start_shard, start_offset),
            vote_preempt=True,
        )
        # single-host: overlap host->device transfer with device compute
        # (multi-host keeps put_batch on the voting thread — collective;
        # the tiered store pins the ring OFF so the cold store keeps
        # read-your-writes order — a ring worker planning batch N+1
        # would otherwise cold-fetch keys whose batch-N write-back is
        # still in flight; docs/STORE.md "Ordering")
        ahead = self.num_hosts == 1 and self.step.store is None
        if ahead:
            stream = self._transfer_ahead(stream)
            # reaped below on the normal path; by Trainer.close() when
            # an exception (or an unclosed preemption) abandons it
            self._live_transfer.add(stream)
        it = iter(stream)
        with obs.span("train_epoch", {"epoch": self.epoch}):
            while True:
                t_step = time.perf_counter()
                try:
                    # waiting on the input iterator IS the input stall:
                    # with transfer-ahead/prefetch on, parse, pack and
                    # h2d all hide behind this wait; whatever doesn't
                    # overlap device time surfaces here
                    self._pulse("input_stall")
                    with obs.phase("input_stall") as stall:
                        batch, shard_idx, resume = next(it)
                except StopIteration:
                    break
                if steps == 0 and obs.enabled:
                    first_wait = stall.seconds
                self._pulse("dispatch")
                last_cursor = (shard_idx, resume)
                if (
                    cfg.profile_dir
                    and not self._profiled
                    and self._global_steps >= cfg.profile_start_step
                    and not profiling
                ):
                    jax.profiler.start_trace(cfg.profile_dir)
                    profiling = True
                    profile_end = self._global_steps + cfg.profile_steps
                arrays = batch if ahead else self.step.put_batch(batch)
                if map_scopes:
                    shape = tuple(sorted(
                        (k, a.shape, a.dtype.name) for k, a in arrays.items()
                    ))
                    if shape not in self._scoped_shapes:
                        self._scoped_shapes.add(shape)
                        new_shapes.append(abstract_like(arrays))
                self.state, metrics = self.step.dispatch_train(
                    self.state, arrays
                )
                obs.observe("step_seconds", time.perf_counter() - t_step)
                steps += 1
                self._global_steps += 1
                device_metrics.append(metrics)
                if self.step.store is not None and (
                    steps % cfg.store_promote_every == 0
                ):
                    # between-steps tier maintenance: flush the miss
                    # write-back, apply the promotion worker's plan
                    # (store/tiered.py::maintain — in-flight batches
                    # never see a moving key->slot map)
                    self.state = self.step.store.maintain(
                        self.state, obs=obs
                    )
                if profiling and self._global_steps >= profile_end:
                    self._stop_profile(metrics)
                    profiling = False
                if cfg.checkpoint_dir and cfg.checkpoint_every_steps and (
                    steps % cfg.checkpoint_every_steps == 0
                ):
                    ckpt_seconds += self._timed_save(shard_idx, resume)
                if self.num_hosts == 1 and self._preempted:
                    ckpt_seconds += self._timed_save(shard_idx, resume)
                    preempted = True
                    break
            if self._preempt_agreed:
                # multi-host: every process left the loop at the same
                # step; the (collective) save is safe here
                ckpt_seconds += self._timed_save(*last_cursor)
                preempted = True
            if profiling:  # epoch ended inside the profile window
                self._stop_profile(
                    device_metrics[-1] if device_metrics else None
                )
            if self.step.store is not None:
                # epoch-end flush: the LAST step's miss write-back must
                # land before eval/save/export reads the cold store
                self.state = self.step.store.maintain(self.state, obs=obs)
            self._pulse("device_block")
            with obs.phase("device_block"):
                host_metrics = jax.device_get(device_metrics)
            scope_rows = None
            if new_shapes:
                # once per new shape, in the epoch that first met it:
                # the text of the program that just ran, no compile
                with obs.phase("op_scopes"):
                    scope_rows = sorted({
                        tuple(row)
                        for shapes in new_shapes
                        for row in self.step.op_scopes(self.state, shapes)
                    })
            self._pulse("idle")  # epoch compute over — silence is benign
        if ahead:
            # no-op when the stream ran dry; on a preemption break it
            # shuts the staging-ring executor down NOW instead of
            # leaving its threads to the garbage collector
            self._live_transfer.discard(stream)
            stream.close()
        seen = float(sum(m["count"] for m in host_metrics))
        ll_sum = float(
            sum(m["logloss"] * m["count"] for m in host_metrics)
        )
        dt = time.time() - t0
        stats = self._epoch_stats(
            seen, ll_sum, steps, dt, ckpt_seconds, preempted, ahead,
            compiles_before,
        )
        if first_wait is not None:
            stats["first_batch_wait_s"] = round(first_wait, 6)
        if scope_rows is not None:
            stats["_scopes"] = {
                "epoch": self.epoch, "ops": [list(r) for r in scope_rows],
            }
        return stats

    def _epoch_stats(
        self,
        seen: float,
        ll_sum: float,
        steps: int,
        dt: float,
        ckpt_seconds: float,
        preempted: bool,
        ahead: bool,
        compiles_before: dict,
    ) -> dict:
        """Epoch record assembly: throughput (checkpoint time excluded),
        per-phase wall-second accounting, stall fraction, step-time
        percentiles.  Phase semantics (docs/OBSERVABILITY.md): `phases`
        holds main-thread-EXCLUSIVE intervals whose sum accounts for
        (nearly all of) `seconds`; `overlapped` holds worker-thread
        phases (parse/pack, and h2d under transfer-ahead) that hide
        behind input_stall and must not be added to the wall-clock."""
        snap = self.obs.registry.snapshot(reset=True)
        phases = snap.phase_seconds()
        overlapped = {
            k: round(phases.pop(k), 6)
            for k in WORKER_PHASES if k in phases
        }
        if ahead and "h2d" in phases:
            overlapped["h2d"] = round(phases.pop("h2d"), 6)
        phases = {k: round(v, 6) for k, v in phases.items()}
        step_hist = snap.hists.get("step_seconds", {})
        stats = {
            "epoch": self.epoch,
            "examples": seen,
            "steps": steps,
            "train_logloss": ll_sum / max(seen, 1.0),
            "examples_per_sec": seen / max(dt - ckpt_seconds, 1e-9),
            "seconds": dt,
            "checkpoint_seconds": round(ckpt_seconds, 6),
            "preempted": preempted,
            "phases": phases,
            "overlapped": overlapped,
            "input_stall_frac": round(
                phases.get("input_stall", 0.0) / max(dt, 1e-9), 6
            ),
            "step_time_p50": round(step_hist.get("p50", 0.0), 6),
            "step_time_p90": round(step_hist.get("p90", 0.0), 6),
            "step_time_p99": round(step_hist.get("p99", 0.0), 6),
            # programs this process asked XLA for during the epoch
            # (obs/startup.py): compiled or loaded, of those loaded from
            # the persistent cache, and their seconds; 0 once steady
            # (``compiles_before``: the watch's totals at its start)
            **startup.compile_delta(
                compiles_before, startup.compile_totals()
            ),
        }
        occ = snap.hists.get("transfer_ahead_depth")
        if occ:
            stats["transfer_ahead_depth_mean"] = round(occ["mean"], 3)
        if "wire.bytes" in snap.counters:
            # host->device wire accounting (parallel/step.py::_book_wire)
            # -> the epoch's `wire` metrics row; compaction_ratio = cold
            # occurrences per big-table touch the dict wire left (1.0 =
            # no dedup happened / plain wire)
            touched = snap.counters.get("wire.cold_touched", 0)
            occ_in = snap.counters.get("wire.cold_occ", 0)
            stats["_wire"] = {
                "epoch": self.epoch,
                "format": self.step.wire_format,
                "wire_bytes_per_example": round(
                    snap.counters["wire.bytes"]
                    / max(snap.counters.get("wire.examples", 0), 1),
                    2,
                ),
                "compaction_ratio": round(
                    occ_in / touched if touched else 1.0, 3
                ),
                # of those bytes, the planes of field ids (0 where the
                # wire ships none: a model that reads no field)
                "slots_bytes_per_example": round(
                    snap.counters.get("wire.slots_bytes", 0)
                    / max(snap.counters.get("wire.examples", 0), 1),
                    2,
                ),
            }
            if "wire.values_bytes" in snap.counters:
                # and the plane of the numeric fields' values
                # (Config.numeric_fields): absent where none shipped
                stats["_wire"]["values_bytes_per_example"] = round(
                    snap.counters["wire.values_bytes"]
                    / max(snap.counters.get("wire.examples", 0), 1),
                    2,
                )
            batches = max(snap.counters.get("wire.batches", 0), 1)
            if "wire.cold_slots" in snap.counters:
                # what the batches asked of the [T, D] tables, a batch,
                # from shapes: indices of the cold gather beside the
                # padded cold slots (equal unless the step read the
                # dictionary wire's plan: TrainStep._book_wire)
                stats["_wire"]["table_gather_indices_per_step"] = round(
                    snap.counters["wire.table_gather_indices"] / batches
                )
                stats["_wire"]["padded_cold_slots_per_step"] = round(
                    snap.counters["wire.cold_slots"] / batches
                )
                # the way back, summed over the tables: indices the cold
                # scatter-adds handed the gradient buffers (the padded
                # slots times the tables unless a table's gradients left
                # through the dictionary: step.py::dict_cold_grads)
                stats["_wire"]["table_scatter_indices_per_step"] = round(
                    snap.counters["wire.table_scatter_indices"] / batches
                )
                # and what those slots move in bytes of table rows, with
                # the hot slots of a table that opted out of the MXU head;
                # the head's own slots by the form its gather read them in
                # (ops/hot.py::gather_form) and by the form its scatter
                # summed them in (scatter_form); and the elements of the
                # one-column tables whose optimizer pass ran on the flat
                # view (step.py::_optimizer_pass); and the indices the
                # dense update handed the touched-rows application in
                # place of a gradient buffer and a table pass
                # (step.py::_touched_rows_pass; 0 where no table is
                # selected); and the elements of the tables whose dense
                # pass ran on the layout the chip keeps them in
                # (step.py::resident_pass_selects)
                for name in (
                    "gather_row_bytes", "scatter_row_bytes", "plain_hot_slots",
                    "hot_plain_slots", "hot_scan_slots",
                    "hot_scatter_plain_slots", "hot_scatter_scan_slots",
                    "flat_pass_elements", "touched_rows_indices",
                    "resident_pass_elements",
                ):
                    stats["_wire"][f"{name}_per_step"] = round(
                        snap.counters[f"wire.{name}"] / batches
                    )
                if "wire.cold_row_layout_slots" in snap.counters:
                    # the step read the dictionary wire's plan: padded
                    # cold slots of the tables whose rows it laid out by
                    # row gathers (step.py::dict_cold_rows)
                    stats["_wire"]["cold_row_layout_slots_per_step"] = round(
                        snap.counters["wire.cold_row_layout_slots"] / batches
                    )
            if "dense.param_bytes" in snap.counters:
                # a family with replicated dense parameters, a batch and
                # from shapes: their bytes, and the operations of the
                # products with them (TrainStep._book_wire)
                stats["_wire"]["dense_param_bytes"] = round(
                    snap.counters["dense.param_bytes"] / batches
                )
                stats["_wire"]["dense_matmul_flops_per_step"] = round(
                    snap.counters["dense.matmul_flops"] / batches
                )
            # what else a family books of its dense half, a batch and
            # from shapes (Model.dense_counters), under its own names
            for name in sorted(snap.counters):
                if name.startswith("dense.") and name not in (
                    "dense.param_bytes", "dense.matmul_flops"
                ):
                    stats["_wire"][name.replace(".", "_")] = round(
                        snap.counters[name] / batches
                    )
            if "exchange.bytes" in snap.counters:
                # a mesh of more than one device: what the step's pull
                # and push moved between the chips, from shapes
                # (TrainStep.exchange_bytes)
                stats["_wire"]["exchange_bytes_per_step"] = round(
                    snap.counters["exchange.bytes"] / max(steps, 1)
                )
        if "store.hit_occ" in snap.counters or (
            "store.miss_occ" in snap.counters
        ):
            # tiered-store accounting (store/tiered.py::plan_batch +
            # maintain) -> the epoch's `store` metrics row; hit rate is
            # occurrence-weighted (the share of feature occurrences the
            # hot tier served without a cold fetch)
            hits = snap.counters.get("store.hit_occ", 0)
            misses = snap.counters.get("store.miss_occ", 0)
            stats["_store"] = {
                "epoch": self.epoch,
                "hot_hit_rate": round(
                    hits / max(hits + misses, 1), 6
                ),
                "promotions": int(
                    snap.counters.get("store.promotions", 0)
                ),
                "demotions": int(
                    snap.counters.get("store.demotions", 0)
                ),
                "cold_fetch_seconds": round(
                    snap.counters.get("store.cold_fetch_seconds", 0.0), 6
                ),
                "hot_occupancy": round(
                    self.step.store.occupancy_frac()
                    if self.step.store is not None
                    else 0.0,
                    6,
                ),
            }
        if "loader.shard_opens" in snap.counters:
            stats["shard_opens"] = int(snap.counters["loader.shard_opens"])
            stats["remap_hashes"] = int(
                snap.counters.get("loader.remap_hashes", 0)
            )
        if "loader.parse_bytes" in snap.counters:
            stats["parse_mb_per_sec"] = round(
                snap.counters["loader.parse_bytes"] / 2**20
                / max(overlapped.get("parse", 0.0), 1e-9),
                2,
            )
        return stats

    def train(self) -> list[dict]:
        """Full training run (reference batch_training loop over epochs,
        lr_worker.cc:179-205, with epoch banner every 30 at :202).

        Graceful preemption (capability gap vs the reference, whose only
        recovery story was ``pkill -9`` + full restart — SURVEY §5):
        with checkpointing enabled, SIGTERM/SIGINT during training
        finishes the in-flight step, saves weights + optimizer state +
        data cursor, and returns cleanly; a later run with --resume
        continues mid-shard.
        """
        history = []
        restore_handlers = self._install_preemption_handler()
        try:
            while self.epoch < self.cfg.epochs:
                start_shard, start_offset = self._resume_cursor
                self._resume_cursor = (0, 0)
                stats = self.train_epoch(start_shard, start_offset)
                wire_stats = stats.pop("_wire", None)
                store_stats = stats.pop("_store", None)
                scope_stats = stats.pop("_scopes", None)
                startup_stats = stats.pop("_startup", None)
                history.append(stats)
                if self.metrics_logger is not None:
                    self.metrics_logger.log("train_epoch", stats)
                    if wire_stats is not None:
                        self.metrics_logger.log("wire", wire_stats)
                    if store_stats is not None:
                        self.metrics_logger.log("store", store_stats)
                    if scope_stats is not None:
                        self.metrics_logger.log("scopes", scope_stats)
                    if startup_stats is not None:
                        self.metrics_logger.log("startup", startup_stats)
                self._log_device_mem()
                if self.epoch % 30 == 0 or self.epoch == self.cfg.epochs - 1:
                    self._log(
                        f"epoch {self.epoch}: logloss={stats['train_logloss']:.6f} "
                        f"examples/s={stats['examples_per_sec']:.0f}"
                    )
                if stats.get("preempted"):
                    # the process is about to exit for a restart: dump
                    # the flight record and flush metrics + trace NOW
                    self.flight_dump("preemption")
                    self.close()
                    break
                self.epoch += 1
                if self.cfg.checkpoint_dir:
                    # _timed_save: a failed epoch-end save emits its
                    # checkpoint_save_failed health row before the
                    # crash path takes over
                    self._timed_save(0, 0)
                if (
                    self.cfg.eval_every_epochs
                    and self.cfg.test_path
                    and self.epoch < self.cfg.epochs  # final eval is the caller's
                    and self.epoch % self.cfg.eval_every_epochs == 0
                ):
                    self.evaluate()
        except BaseException as e:
            # crash path: flight-dump the black box (active phase,
            # thread stacks, recent state), then never lose buffered
            # metrics rows or the trace
            self.flight_dump("exception", exc=e)
            self.close()
            raise
        finally:
            restore_handlers()
        return history

    def _log_device_mem(self) -> None:
        """Per-epoch jax.local_devices() memory gauge (``device_mem``
        row).  memory_stats() is unsupported on some backends (CPU
        returns None/raises) — the row still lands with whatever fields
        exist, so the schema stays uniform across backends."""
        if self.metrics_logger is None or not self.cfg.obs_device_memory:
            return
        devices = []
        for d in jax.local_devices():
            entry: dict[str, Any] = {
                "id": int(d.id), "platform": str(d.platform),
            }
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if ms:
                for key in (
                    "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                ):
                    if key in ms:
                        entry[key] = int(ms[key])
            devices.append(entry)
        self.metrics_logger.log(
            "device_mem", {"epoch": self.epoch, "devices": devices}
        )

    def _install_preemption_handler(self) -> Callable[[], None]:
        """Install SIGTERM/SIGINT → checkpoint-and-stop handlers (only
        with checkpointing on, only from the main thread).  Returns a
        restore function.  The handler fires ONCE and then restores the
        previous handlers, so a second signal escalates normally (e.g.
        a second Ctrl-C kills a wedged step instead of being swallowed).
        """
        self._preempted = False
        self._preempt_agreed = False
        if not self.cfg.checkpoint_dir:
            return lambda: None
        import signal

        prev = {}

        def restore():
            for sig, h in prev.items():
                signal.signal(sig, h)
            prev.clear()

        def on_signal(signum, frame):
            self._log(
                f"signal {signum}: finishing step, checkpointing, stopping "
                "(send again to force)"
            )
            self._preempted = True
            restore()

        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(sig, on_signal)
        except ValueError:  # not the main thread — no handler possible
            return lambda: None
        return restore

    # -- continuous training (stream/; docs/CONTINUOUS.md) -----------------

    def register_stream_cursor(self, cursor) -> None:
        """Attach a stream ingestion cursor (stream/follower.py::
        IngestCursor) so close() flushes it durably on every exit road
        — crash, preemption, normal return."""
        self._stream_cursor = cursor

    def train_stream(self, batches) -> Iterator[tuple[int, Any]]:
        """Iterator-driven training for the continuous loop: consume
        ``(batch, meta)`` pairs (stream/follower.py ShardFollower) and
        dispatch one train step each, yielding ``(steps_so_far, meta)``
        AFTER the step so the driver can cut delta exports / drive
        rollouts between steps against a consistent state.

        Phase accounting, heartbeats, and store maintenance match
        train_epoch's hot loop; epoch semantics (multi-host shard
        voting, the transfer-ahead ring) deliberately do not apply —
        the stream is unbounded and single-host by construction (the
        continuous driver's topology, stream/driver.py)."""
        if self.num_hosts > 1:
            raise RuntimeError(
                "train_stream is single-host: continuous ingestion has "
                "no shard-count voting (docs/CONTINUOUS.md)"
            )
        cfg = self.cfg
        obs = self.obs
        steps = 0
        it = iter(batches)
        while True:
            t_step = time.perf_counter()
            self._pulse("input_stall")
            with obs.phase("input_stall"):
                try:
                    batch, meta = next(it)
                except StopIteration:
                    break
            self._pulse("dispatch")
            arrays = self.step.put_batch(batch)
            self.state, _ = self.step.dispatch_train(self.state, arrays)
            obs.observe("step_seconds", time.perf_counter() - t_step)
            steps += 1
            self._global_steps += 1
            if self.step.store is not None and (
                steps % cfg.store_promote_every == 0
            ):
                self.state = self.step.store.maintain(self.state, obs=obs)
            yield steps, meta
        if self.step.store is not None:
            # stream-end flush: the last step's miss write-back must
            # land before any export reads the cold store
            self.state = self.step.store.maintain(self.state, obs=obs)
        self._pulse("idle")

    # -- evaluation --------------------------------------------------------

    def evaluate(self, pred_out: str | None = None) -> dict:
        cfg = self.cfg
        obs = self.obs
        obs.registry.reset()  # eval-scoped phase accounting
        t0 = time.time()
        acc = AucAccumulator()
        pred_file = None
        out_path = pred_out if pred_out is not None else cfg.pred_out
        per_block = bool(out_path) and cfg.pred_style == "per_block"
        if per_block:
            os.makedirs(out_path, exist_ok=True)
            # clear THIS host's stale artifacts: a previous eval with
            # more blocks would otherwise leave old pred files mixed
            # into the new set ('single' mode truncates on open)
            for f in glob.glob(
                os.path.join(out_path, f"pred_{self.host}_*.txt")
            ):
                os.remove(f)
        elif out_path and self.host == 0:
            pred_file = open(out_path, "w")
        def batches() -> Iterator[tuple[Batch, int, int]]:
            workers = self._parse_workers()
            for path in self._my_shards(cfg.test_path):
                # Reference predict uses doubled block size (lr_worker.cc:80).
                loader = self._loader(path)
                loader.block_bytes = (cfg.block_mib * 2) << 20
                it = self._tracked_prefetch(
                    loader, cfg.prefetch_batches, 0, workers
                )
                try:
                    for batch, resume in it:
                        yield batch, 0, resume
                finally:
                    it.close()
                    self._live_prefetch.discard(it)

        try:
            # predict is collective too — keep hosts step-aligned
            block_idx = 0
            it = iter(self._synced_batches(batches()))
            while True:
                try:
                    self._pulse("input_stall")
                    with obs.phase("input_stall"):
                        batch, _, _ = next(it)
                except StopIteration:
                    break
                self._pulse("h2d")
                # books 'h2d' inline; predict=True lets the tiered
                # store ship param-only miss blocks
                arrays = self.step.put_batch(batch, predict=True)
                self._pulse("dispatch")
                with obs.phase("dispatch"):
                    garr = self.step.predict(self.state, arrays)
                if self.num_hosts > 1:
                    # inverse of put_batch's host-local→global assembly:
                    # this host's rows of the sharded pctr
                    from jax.experimental import multihost_utils

                    garr = multihost_utils.global_array_to_host_local_array(
                        garr, self.mesh, self.step._bsharding.spec
                    )
                self._pulse("device_block")
                with obs.phase("device_block"):
                    pctr = np.asarray(jax.device_get(garr))
                acc.add(batch.labels, pctr, batch.weights)
                if per_block and batch.weights.sum() > 0:
                    # reference artifact granularity: one
                    # pred_<rank>_<block>.txt per worker per block
                    # (lr_worker.cc:74-78); padding batches (multi-host
                    # step alignment) produce no file
                    with obs.phase("pred_write"), open(
                        os.path.join(
                            out_path, f"pred_{self.host}_{block_idx}.txt"
                        ),
                        "w",
                    ) as f:
                        for y, p, w in zip(batch.labels, pctr, batch.weights):
                            if w > 0:
                                f.write(f"{int(y)}\t{p:.6f}\n")
                    block_idx += 1
                elif pred_file is not None:
                    with obs.phase("pred_write"):
                        for y, p, w in zip(batch.labels, pctr, batch.weights):
                            if w > 0:
                                # "(label, pctr)" lines, lr_worker.cc:62-68.
                                pred_file.write(f"{int(y)}\t{p:.6f}\n")
        finally:
            if pred_file is not None:
                pred_file.close()
        with obs.phase("metrics_compute"):
            if self.num_hosts > 1:
                # Rank-sum AUC is not decomposable over shard subsets.  The
                # round-1 design allgathered every host's (label, pctr)
                # pairs — O(test set) memory on EVERY host.  Now each host
                # folds its pairs into fixed-size histograms (utils.metrics
                # .HistAuc) and only those reduce across hosts: O(buckets)
                # traffic/memory regardless of test-set size.  Logloss stays
                # exact; AUC uses midrank ties on BOTH the single- and
                # multi-host paths (AucAccumulator.compute is auc_midrank),
                # so host count never changes the reported AUC beyond
                # histogram quantization (< 1e-6 bucket width).
                from xflow_tpu.parallel.multihost import allgather_exact
                from xflow_tpu.utils.metrics import HistAuc

                hist = HistAuc()
                labels, pctr = acc.pairs()
                hist.add(labels, pctr)
                # bit-exact gather: the float64 histograms/sums must not be
                # canonicalized to float32 (counts > 2^24 would drift)
                summed = {
                    k: allgather_exact(v).sum(axis=0)
                    for k, v in hist.state().items()
                }
                hist = HistAuc.from_state(summed)
                ll, auc = hist.compute()
                n = hist.count()
                pos = hist.num_pos()
            else:
                ll, auc = acc.compute()
                n = acc.count()
                pos = int(acc.pairs()[0].sum()) if n else 0
        snap = obs.registry.snapshot(reset=True)
        phases = snap.phase_seconds()
        # parse/pack run on the eval loader's prefetch thread, h2d is
        # inline here — same exclusive/overlapped split as train_epoch
        overlapped = {
            k: round(phases.pop(k), 6)
            for k in WORKER_PHASES if k in phases
        }
        result = {
            "epoch": self.epoch,
            "logloss": ll,
            "auc": auc,
            "examples": n,
            "tp": pos,
            "fp": n - pos,
            "seconds": round(time.time() - t0, 3),
            "phases": {k: round(v, 6) for k, v in phases.items()},
            "overlapped": overlapped,
        }
        self._log(f"logloss: {ll:.6f}\tauc = {auc:.6f}\ttp = {pos} fp = {n - pos}")
        if self.metrics_logger is not None:
            self.metrics_logger.log("eval", result)
        self._pulse("idle")  # eval over — watchdog silence is benign
        return result

    # -- checkpointing -----------------------------------------------------

    def save(
        self,
        shard_idx: int = 0,
        offset: int = 0,
        extra: dict | None = None,
    ) -> str | None:
        """``extra`` merges additional keys into the manifest's cursor
        dict — the continuous driver embeds the stream ingestion
        cursor snapshot there (``{"stream": ...}``) so restore() hands
        it back and model state + stream position rewind together
        (docs/CONTINUOUS.md)."""
        if not self.cfg.checkpoint_dir:
            return None
        self._pulse("checkpoint")
        # Per-host cursors: shard_idx/offset are HOST-LOCAL (each host
        # walks its own ``i % num_hosts`` shard subset), so the manifest
        # records every host's position; a host restores its own.
        cursors = [{"shard": int(shard_idx), "offset": int(offset)}]
        if self.num_hosts > 1:
            # allgather_exact: byte offsets are int64 (shards can exceed
            # 2 GiB) and must not pass through JAX's 32-bit
            # canonicalization
            from xflow_tpu.parallel.multihost import allgather_exact

            pairs = allgather_exact(
                np.asarray([shard_idx, offset], np.int64)
            ).reshape(self.num_hosts, 2)
            cursors = [
                {"shard": int(s), "offset": int(o)} for s, o in pairs
            ]
        cursor = {
            "epoch": self.epoch,
            "num_hosts": self.num_hosts,
            "cursors": cursors,
            # rank-0 view kept for human inspection of the manifest
            "shard": cursors[0]["shard"],
            "offset": cursors[0]["offset"],
        }
        if extra:
            cursor.update(extra)
        if self.step.store is not None:
            # tier-erased fold (store/tiered.py): touched rows from
            # BOTH tiers, key-sorted, in the row-range shard format
            path = self.step.store.save_checkpoint(
                self.cfg.checkpoint_dir,
                self.state,
                cursor,
                self.cfg.to_json(),
                keep=self.cfg.checkpoint_keep,
            )
        else:
            path = save_checkpoint(
                self.cfg.checkpoint_dir,
                self.state,
                cursor,
                self.cfg.to_json(),
                keep=self.cfg.checkpoint_keep,
            )
        if self._flight is not None:
            self._flight.note_checkpoint(self._global_steps)
        # close the 'checkpoint' activity: after a post-epoch save the
        # trainer may sit in caller code indefinitely, and lingering
        # 'checkpoint' as the last note would read as checkpoint_stall
        self._pulse("idle")
        return path

    def restore(self, auto: bool = False) -> dict | None:
        """Resume from a checkpoint if one exists; returns the cursor
        or None.  Each host resumes from ITS OWN saved cursor; if the
        host count changed since the save, the shard→host assignment
        (``i % num_hosts``) no longer matches and the epoch restarts
        from the beginning instead of silently skipping or replaying
        data.

        ``auto`` (``--resume auto``, docs/ROBUSTNESS.md): walk EVERY
        generation newest-first and restore the newest *complete,
        loadable* one — a generation with no manifest (killed or
        corrupted mid-commit) or a transiently unreadable one is
        skipped with a ``checkpoint_fallback`` health row instead of
        crashing the resume.  Plain mode keeps the LATEST-marker fast
        path and treats an unusable checkpoint as "start fresh"."""
        if not self.cfg.checkpoint_dir:
            return None
        with startup.phase("restore", self.obs):
            return self._restore(auto)

    def _restore(self, auto: bool) -> dict | None:
        from xflow_tpu.chaos import ChaosError
        from xflow_tpu.utils.checkpoint import (
            IncompatibleCheckpoint,
            checkpoint_candidates,
        )

        if auto:
            candidates = checkpoint_candidates(self.cfg.checkpoint_dir)
        else:
            path = latest_checkpoint(self.cfg.checkpoint_dir)
            candidates = [path] if path is not None else []
        cursor = None
        for path in candidates:
            try:
                if self.step.store is not None:
                    self.state, cursor = self.step.store.load_checkpoint(
                        path, self.state
                    )
                else:
                    self.state, cursor = load_checkpoint(path, self.state)
                break
            except IncompatibleCheckpoint as e:
                if not auto:
                    self._log(
                        f"ignoring unusable checkpoint: {e} — starting "
                        "fresh"
                    )
                    return None
                self._fallback_health(path, e)
            except (OSError, ValueError, ChaosError) as e:
                if not auto:
                    raise
                self._fallback_health(path, e)
        if cursor is None:
            return None
        self.epoch = int(cursor.get("epoch", 0))
        cursors = cursor.get("cursors")
        saved_hosts = int(cursor.get("num_hosts", 1))
        if cursors is not None and saved_hosts == self.num_hosts:
            mine = cursors[self.host]
            self._resume_cursor = (int(mine["shard"]), int(mine["offset"]))
        elif cursors is not None:
            self._log(
                f"checkpoint was saved with {saved_hosts} hosts, now "
                f"{self.num_hosts}: shard assignment changed — restarting "
                f"epoch {self.epoch} from the beginning"
            )
            self._resume_cursor = (0, 0)
        else:
            self._resume_cursor = (
                int(cursor.get("shard", 0)),
                int(cursor.get("offset", 0)),
            )
        return cursor

    def _fallback_health(self, path: str, err: BaseException) -> None:
        """One skipped restore candidate (auto mode): log + health row
        so `obs doctor` sees the fallback instead of a silent rewind."""
        self._log(
            f"resume auto: skipping unusable checkpoint {path} "
            f"({type(err).__name__}: {err}) — falling back to the next "
            "newest complete generation"
        )
        if self.metrics_logger is not None:
            from xflow_tpu.obs.schema import health_row

            self.metrics_logger.log("health", health_row(
                cause="checkpoint_fallback",
                channel="train",
                silence_seconds=0.0,
                threshold_seconds=0.0,
                detail=f"{os.path.basename(path)}: "
                f"{type(err).__name__}: {err}",
            ))

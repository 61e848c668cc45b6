"""PredictEngine — the low-latency scoring tier.

Loads a frozen artifact (serve/artifact.py) or wraps a live trainer
state, with **no Trainer, no ShardLoader, no optimizer state**: the
engine owns a mesh, the model's predict computation, and param-only
tables.  Two properties make it serving-grade:

* **Shape-bucketed AOT compilation.**  ``XFlow.predict_batch``
  historically re-traced/re-compiled for every distinct batch shape —
  deadly under concurrent traffic where request batches are all sizes.
  The engine snaps every request batch onto a small fixed set of padded
  batch-size buckets (default 1/8/64/512, rounded up to mesh-divisible
  sizes) and compiles the predict step **ahead of time, exactly once
  per bucket** (``jax.jit(...).lower(...).compile()``), warmed at load.
  ``compile_count`` is the hook: after ``warm()`` it equals
  ``len(buckets)`` and MUST stay there under any traffic mix — a test
  regression here means latency cliffs in production.

* **Digest-checked identity.**  The engine refuses an artifact whose
  manifest digest doesn't match its embedded config, and refuses to
  load when the caller's expected config digests differently — scoring
  through the wrong geometry fails loudly at load, not silently with
  garbage pctr.

The hot-table remap (io/freq.py) is folded in: artifacts carry it and
``predict`` applies it to raw hash-space request keys via the shared
io/batch.py::remap_batch, so external callers never see the permuted
key space.
"""

from __future__ import annotations

import os
import time
from typing import Any, Iterable, Sequence

import numpy as np

import jax

from xflow_tpu.chaos import failpoint
from xflow_tpu.config import Config
from xflow_tpu.io.batch import Batch, pad_batch_rows, remap_batch
from xflow_tpu.obs import NULL_OBS, profiler_span, startup
from xflow_tpu.parallel.mesh import make_mesh, replicated, table_sharding
from xflow_tpu.parallel.step import (
    _SLOT_PLANES,
    _VALUE_PLANES,
    TrainStep,
    pack_wire_np,
    unpack_wire,
)

DEFAULT_BUCKETS = (1, 8, 64, 512)
# default top-k compile width for retrieval engines (attach_item_index):
# the executable is compiled ONCE for this k (capped at the index
# size); smaller request ks slice the result on the host, so mixed-k
# traffic never compiles
DEFAULT_TOPK = 16


def _slice_rows(batch: Batch, start: int, stop: int) -> Batch:
    return Batch(
        keys=batch.keys[start:stop],
        slots=batch.slots[start:stop],
        vals=batch.vals[start:stop],
        mask=batch.mask[start:stop],
        labels=batch.labels[start:stop],
        weights=batch.weights[start:stop],
        hot_keys=batch.hot_keys[start:stop],
        hot_slots=batch.hot_slots[start:stop],
        hot_vals=batch.hot_vals[start:stop],
        hot_mask=batch.hot_mask[start:stop],
    )


class PredictEngine:
    """Compiled, bucketed predict over a frozen (or live) model state.

    Construct directly from an in-memory state (the ``XFlow.predict_batch``
    path wraps the live trainer state this way) or via ``load`` from an
    exported artifact.  ``state`` may be a full training state — it is
    stripped to param-only tables so the compiled executables never
    carry optimizer aux arrays.
    """

    def __init__(
        self,
        cfg: Config,
        state: dict[str, Any],
        remap: np.ndarray | None = None,
        mesh=None,
        buckets: Sequence[int] | None = None,
        obs=None,
        digest: str | None = None,
        warm: bool = False,
    ):
        from xflow_tpu.models import make_model

        self.cfg = cfg
        self.digest = digest if digest is not None else cfg.digest()
        self.mesh = mesh if mesh is not None else make_mesh(1)
        ndev = self.mesh.devices.size
        if cfg.table_size % ndev:
            raise ValueError(
                f"table_size {cfg.table_size} not divisible by the "
                f"serving mesh's {ndev} devices"
            )
        if cfg.hot_size_log2 and remap is None:
            raise ValueError(
                "model was trained with a hot table but no remap was "
                "provided — raw request keys cannot be translated"
            )
        self.model = make_model(cfg)
        # The predict path never touches the optimizer; TrainStep is
        # reused purely for its wire/gather/logit machinery.  Serving
        # pins the dictionary wire OFF (Config.wire_dedup is a
        # training-feed lever): its plane capacities are content-sized
        # (io/compact.py plane_cap), which would key the AOT executable
        # cache on per-request nnz totals and break the
        # one-compile-per-bucket guarantee compile_count enforces.
        # Request batches are tiny — the plain compact wire is already
        # ~free at serving sizes; the hot-impl platform pick (ops/hot.py)
        # still applies to the featurize->predict path.  The override
        # rides the STEP's config copy (self.cfg — the artifact's
        # digest-locked identity — is untouched) so a wire_dedup='on'
        # training config still serves on any mesh, where TrainStep's
        # single-device eligibility check would otherwise refuse it.
        # store_mode is pinned to 'dense' the same way: a tiered
        # artifact is exported as the FOLDED logical [T, D] table
        # (serve/artifact.py), so serving always sees a dense store —
        # and must not build the trainer's hot tier / cold store /
        # promotion worker.
        self.step = TrainStep(
            self.model,
            None,
            cfg.replace(wire_dedup="off", store_mode="dense"),
            self.mesh,
        )
        self.remap = remap
        self.obs = obs if obs is not None else NULL_OBS
        self.step.obs = self.obs
        # Bucket sizes must divide over the mesh's batch axis: round
        # each up to a multiple of ndev, dedupe, sort.
        raw = tuple(buckets) if buckets else DEFAULT_BUCKETS
        if any(b < 1 for b in raw):
            raise ValueError(f"bucket sizes must be >= 1, got {raw}")
        self.buckets = tuple(
            sorted({-(-b // ndev) * ndev for b in raw})
        )
        self.state = self._strip_state(state)
        # Servable identity for the continuous-training delta chain
        # (docs/CONTINUOUS.md): (config digest, train step), shared
        # with full exports and deltas via serve/artifact.py::
        # servable_digest — apply_delta refuses a delta whose base is
        # not this.  Resolved LAZILY: the step scalar's device_get
        # would otherwise serialize every live-state update_state()
        # (XFlow.predict_batch calls it per batch) against pending
        # dispatch.
        self._servable_step: int | None = None
        # AOT executables keyed by (batch_rows, cold_nnz, hot_nnz) —
        # canonical traffic only ever sees len(buckets) keys.  The dict
        # may be SHARED across ``clone()`` replicas: executables are
        # immutable once built, so ``compile_count`` is derived from it
        # and counts compiles fleet-wide, exactly what the
        # no-recompile-under-any-traffic guarantee wants to watch.
        self._compiled: dict[tuple[int, int, int], Any] = {}
        # retrieval-leg jit bindings (TrainStep idiom): _run_aot lowers
        # THESE per bucket into _compiled (never retraces at serve
        # time), and the explicit binding makes the impls visible to
        # the static memory pass (shapeflow jit-entry discovery →
        # XF014 budgets in memory-budget.json)
        self.predict_jit = jax.jit(
            self._predict_impl, static_argnames="layout"
        )
        self.topk_jit = jax.jit(self._topk_impl, static_argnames="layout")
        self.item_embed_jit = jax.jit(
            self._item_embed_impl, static_argnames="layout"
        )
        self.warm_seconds = 0.0
        self._parse_fn = None
        # serve-time item index (retrieval families, docs/SERVING.md
        # "Retrieval→ranking cascade"): attached by ``load`` from the
        # artifact's item_index.* files or by ``attach_item_index``;
        # ``topk`` refuses until one is attached
        self.item_index: dict | None = None
        self._index_arr = None
        self.topk_k = 0
        # per-call device split: {"h2d": s, "dispatch": s, "fetch": s}
        # of the LAST prepared call (_put_dispatch_fetch).  Written and
        # read on the one batcher worker thread that drives this engine
        # clone, so the batcher's registry and the batch span
        # (obs/reqtrace.py) can carve the device phase without a lock.
        self.last_device_phases: dict | None = None
        # beside it, what the h2d leg of that call shipped: the
        # host->device transfer calls it made and their bytes
        # ({"transfers": n, "bytes": b}; serve.h2d_* in the batcher's
        # registry)
        self.last_h2d: dict | None = None
        if warm:
            self.warm()

    @property
    def compile_count(self) -> int:
        return len(self._compiled)

    @property
    def row_nnz(self) -> int:
        """Features one row may carry: the training loader's per-row
        capacity (io/batch.py::pack_batch) — the cold section plus,
        with a hot table, the hot section.  Rows featurized to this
        width and steered by ``_prepare`` are the batches the loader
        would have built, so served and trained scores agree."""
        cfg = self.cfg
        return cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)

    @property
    def servable_step(self) -> int:
        """Train step of the served state (one cached scalar fetch,
        booked — XF002)."""
        if self._servable_step is None:
            with self.obs.phase("serve_state_sync"):
                self._servable_step = int(
                    jax.device_get(self.state["step"])
                )
        return self._servable_step

    @servable_step.setter
    def servable_step(self, step: int) -> None:
        self._servable_step = int(step)

    @property
    def servable_digest(self) -> str:
        """Identity of the model VERSION being served — (config digest,
        train step), the continuous-training chain anchor
        (serve/artifact.py::servable_digest).  Distinct from
        ``digest``: that is the config/geometry identity (unchanged by
        a delta), this advances with every applied refresh."""
        from xflow_tpu.serve.artifact import servable_digest

        return servable_digest(self.digest, self.servable_step)

    def apply_delta(self, directory: str) -> "PredictEngine":
        """Fold an incremental delta export (stream/delta.py) onto
        this servable; returns a NEW engine at the delta's step with
        shared AOT executables (zero recompiles) — this engine keeps
        serving untouched, so fleets canary the result through the
        staged-rollout gate before committing traffic to it."""
        from xflow_tpu.stream.delta import apply_delta

        return apply_delta(self, directory)

    # -- construction ------------------------------------------------------

    @classmethod
    def load(
        cls,
        directory: str,
        config: Config | None = None,
        num_devices: int = 1,
        buckets: Sequence[int] | None = None,
        obs=None,
        warm: bool = True,
        topk_k: int | None = None,
    ) -> "PredictEngine":
        """Load an exported artifact.  ``config``, when given, is the
        caller's expectation: its digest must equal the artifact's or
        the load is refused (never score through the wrong model).
        ``num_devices`` sizes the serving mesh (default 1 — the lean
        scoring tier; the row-range shard files assemble onto any
        mesh).  An item index beside the artifact (export_item_index)
        is attached automatically, arming the ``topk`` mode compiled
        for ``topk_k`` results (default ``DEFAULT_TOPK``, capped at
        the index size).

        On the process's start-up timeline (obs/startup.py) the load is
        ``engine_load``, and inside it ``artifact_read`` (manifest and
        digest check), ``weights_put`` (the shard files read through
        onto the serving mesh, each range read and handed to the device
        in one call; a transfer still in flight at its end is waited
        for by the first warm-up call) and ``bucket_warm``."""
        import jax.numpy as jnp

        from xflow_tpu.models import make_model
        from xflow_tpu.serve.artifact import (
            REMAP_FILE,
            load_item_index,
            load_manifest,
        )
        from xflow_tpu.utils.checkpoint import RangeReader

        with startup.phase("engine_load", obs if obs is not None else NULL_OBS):
            # chaos site: artifact-load fault — the manifest/digest
            # refusal chain below is what it exercises (XF018)
            failpoint("artifact.load")
            with startup.phase("artifact_read"):
                manifest = load_manifest(directory)
                cfg = Config.from_json(manifest["config"])
                digest = manifest["config_digest"]
                if config is not None and config.digest() != digest:
                    raise ValueError(
                        f"artifact {directory} was exported from config "
                        f"{digest}, but the expected config digests to "
                        f"{config.digest()} — refusing to serve a "
                        "mismatched model"
                    )
            with startup.phase("weights_put"):
                mesh = make_mesh(num_devices)
                sharding = table_sharding(mesh)
                tables: dict[str, Any] = {}
                for spec in make_model(cfg).tables():
                    key = f"{spec.name}.param"
                    meta = manifest["arrays"].get(key)
                    if meta is None:
                        raise ValueError(f"artifact {directory} missing {key}")
                    shape = tuple(meta["shape"])
                    reader = RangeReader(
                        directory, key, shape, np.dtype(meta["dtype"])
                    )
                    tables[spec.name] = {
                        "param": jax.make_array_from_callback(
                            shape, sharding, reader.read
                        )
                    }
                dense: dict[str, Any] = {}
                for dname in manifest.get("dense", []):
                    host = np.load(os.path.join(directory, f"dense.{dname}.npy"))
                    dense[dname] = jax.device_put(host, replicated(mesh))
                remap = None
                if manifest.get("remap"):
                    remap = np.load(os.path.join(directory, REMAP_FILE))
                state = {
                    "tables": tables,
                    "dense": dense,
                    "step": jnp.asarray(manifest["step"], jnp.int32),
                }
            engine = cls(
                cfg,
                state,
                remap=remap,
                mesh=mesh,
                buckets=buckets,
                obs=obs,
                digest=digest,
                warm=False,  # warm AFTER the index attach so topk buckets warm too
            )
            index = load_item_index(directory)
            if index is not None:
                engine.attach_item_index(index, topk_k=topk_k)
            if warm:
                with startup.phase("bucket_warm"):
                    engine.warm()
            return engine

    def clone(self) -> "PredictEngine":
        """A replica view over the SAME weights and the SAME compiled
        executables — how serve/fleet.py fans one loaded artifact out
        to N replicas without paying N× the XLA compiles or N× the
        table HBM.

        What is shared: ``state`` (device arrays — immutable on the
        predict path), ``_compiled`` (AOT executables are immutable
        once built; a rare concurrent non-canonical-shape miss at worst
        compiles twice and last-write-wins), mesh, remap, digest.  What
        is NOT shared: the ``TrainStep`` wire machinery and the
        per-call records of the put (``last_device_phases``,
        ``last_h2d``) — each fleet replica is driven by its own
        MicroBatcher worker thread, so sharing them would race."""
        replica = PredictEngine(
            self.cfg,
            self.state,
            remap=self.remap,
            mesh=self.mesh,
            buckets=self.buckets,
            obs=self.obs,
            digest=self.digest,
            warm=False,
        )
        replica.state = self.state  # share, don't re-strip-copy
        replica._compiled = self._compiled
        replica._servable_step = self._servable_step
        replica.warm_seconds = self.warm_seconds
        # item index: host planes and the device scan operand are
        # immutable once attached — shared like the weights
        replica.item_index = self.item_index
        replica._index_arr = self._index_arr
        replica.topk_k = self.topk_k
        return replica

    @staticmethod
    def _strip_state(state: dict[str, Any]) -> dict[str, Any]:
        """Param-only view of a (possibly full training) state: the
        compiled executables should never ship FTRL n/z."""
        return {
            "tables": {
                name: {"param": t["param"]}
                for name, t in state["tables"].items()
            },
            "dense": state["dense"],
            "step": state["step"],
        }

    def update_state(self, state: dict[str, Any]) -> None:
        """Swap in newer weights (same shapes/shardings — e.g. the live
        trainer state after more steps).  The AOT executables take the
        state as an argument, so no recompilation happens."""
        self.state = self._strip_state(state)
        self._servable_step = None  # re-resolve lazily on next use

    # -- warmup / compilation ----------------------------------------------

    def warm(self) -> float:
        """Compile every bucket now (one all-padding batch each) so the
        first real request never pays an XLA compile; returns and
        records the warmup seconds.  Retrieval engines (item index
        attached) warm the top-k executables the same way — after
        warm, ``compile_count`` covers BOTH modes and must stay there
        under any single-row/top-k traffic mix."""
        t0 = time.perf_counter()
        for b in self.buckets:
            self.predict(self._empty_batch(b))
            if self._index_arr is not None:
                self.topk(self._empty_batch(b))
        self.warm_seconds = time.perf_counter() - t0
        return self.warm_seconds

    def _empty_batch(self, rows: int) -> Batch:
        k = self.row_nnz
        return Batch(
            keys=np.zeros((rows, k), np.int32),
            slots=np.zeros((rows, k), np.int32),
            vals=np.zeros((rows, k), np.float32),
            mask=np.zeros((rows, k), np.float32),
            labels=np.zeros(rows, np.float32),
            weights=np.zeros(rows, np.float32),
        )

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (the largest bucket for oversized
        requests — predict() chunks those)."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    # -- featurize ---------------------------------------------------------

    def featurize_raw(self, rows: Sequence) -> Batch:
        """Build a RAW-key-space Batch from single-row requests —
        feed it to ``predict`` (which remaps/pads).  Each row is either
        a 1-D key array or a ``(keys, slots, vals)`` tuple (slots/vals
        may be None → 0 / 1.0, the hash-mode convention).  Features
        beyond ``row_nnz`` are truncated, like the training loader."""
        n = len(rows)
        k = self.row_nnz
        keys = np.zeros((n, k), np.int32)
        slots = np.zeros((n, k), np.int32)
        vals = np.zeros((n, k), np.float32)
        mask = np.zeros((n, k), np.float32)
        for i, row in enumerate(rows):
            if isinstance(row, tuple):
                rk, rs, rv = row
            else:
                rk, rs, rv = row, None, None
            rk = np.asarray(rk)
            m = min(len(rk), k)
            keys[i, :m] = rk[:m]
            if rs is not None:
                slots[i, :m] = np.asarray(rs)[:m]
            vals[i, :m] = 1.0 if rv is None else np.asarray(rv)[:m]
            mask[i, :m] = 1.0
        return Batch(
            keys=keys, slots=slots, vals=vals, mask=mask,
            labels=np.zeros(n, np.float32),
            weights=np.ones(n, np.float32),
        )

    def featurize(self, rows: Sequence) -> Batch:
        """``featurize_raw`` + prepare (remap/steer) + pad to the
        covering bucket: the Batch is ready for ``predict_prepared``
        (the batcher's featurize leg).  ``rows`` must fit the largest
        bucket — callers with bigger batches use ``predict``, which
        chunks.  Never feed the result to ``predict``: that would
        apply the remap twice."""
        n = len(rows)
        if n > self.buckets[-1]:
            raise ValueError(
                f"featurize: {n} rows exceed the largest bucket "
                f"{self.buckets[-1]} — use predict(featurize_raw(rows))"
            )
        return pad_batch_rows(
            self._prepare(self.featurize_raw(rows)), self.bucket_for(n)
        )

    def score_text(self, lines: Iterable[str]) -> np.ndarray:
        """pctr for libffm-format text lines (``label\\tfgid:fid:val``,
        label ignored) — the CLI ``score`` and C-ABI ``XFEngineScore``
        featurize path.  Uses the training parse fn (same hashing/seed,
        from the artifact config) but NO ShardLoader."""
        from xflow_tpu.io.batch import pack_batch
        from xflow_tpu.io.loader import make_parse_fn

        if self._parse_fn is None:
            cfg = self.cfg
            self._parse_fn = make_parse_fn(
                cfg.table_size,
                cfg.hash_mode,
                cfg.seed,
                prefer_native=cfg.native_parser,
                numeric_fields=cfg.numeric_fields,
            )
        data = "".join(
            line if line.endswith("\n") else line + "\n" for line in lines
        ).encode()
        block = self._parse_fn(data)
        n = block.num_samples
        if n == 0:
            return np.zeros(0, np.float32)
        out = []
        cap = self.buckets[-1]
        for s in range(0, n, cap):
            e = min(s + cap, n)
            raw = pack_batch(block, s, e, e - s, self.row_nnz)
            out.append(self.predict(raw))
        return np.concatenate(out)

    # -- retrieval: item index + top-k --------------------------------------

    def attach_item_index(
        self, index: dict, topk_k: int | None = None
    ) -> None:
        """Arm the top-k mode with an item-embedding index
        (serve/artifact.py::load_item_index's dict, or any dict with
        ``item_index`` [N, D] / ``item_ids`` [N] plus the feature
        planes).  The scan operand goes to the device once,
        replicated; ``topk_k`` fixes the compiled result width
        (DEFAULT_TOPK, capped at N)."""
        from xflow_tpu.parallel.mesh import replicated

        if not hasattr(self.model, "user_embed"):
            raise ValueError(
                f"model {self.cfg.model!r} has no user tower "
                "(models/__init__.py registry: retrieval=False) — "
                "top-k retrieval needs a two-tower-factored family"
            )
        emb = np.asarray(index["item_index"], np.float32)
        if emb.ndim != 2 or not len(emb):
            raise ValueError(
                f"item index must be [N, index_dim], got {emb.shape}"
            )
        want = getattr(self.model, "index_dim", None)
        if want is not None and emb.shape[1] != want:
            raise ValueError(
                f"item index rows are {emb.shape[1]} wide but model "
                f"{self.cfg.model!r} scans {want} lanes (tower_dim "
                f"{self.cfg.tower_dim} + 2 bias lanes) — the index was "
                "exported from a different tower geometry; re-run "
                "export_item_index"
            )
        # own copy + the precomputed id sort order: the cascade's
        # per-request id->row resolution must not pay an O(N log N)
        # argsort over the catalog on the retrieval worker thread
        self.item_index = dict(index)
        self.item_index["ids_order"] = np.argsort(
            np.asarray(index["item_ids"]), kind="stable"
        )
        self._index_arr = jax.device_put(emb, replicated(self.mesh))
        self.topk_k = min(
            topk_k if topk_k is not None else DEFAULT_TOPK, len(emb)
        )
        if self.topk_k < 1:
            raise ValueError("topk_k must be >= 1")

    def _predict_impl(self, state, buf, layout):
        """pctr of one packed request batch (``_put_packed``): the
        planes out of the buffer, then the trainer's own predict."""
        return self.step._predict_impl(state, unpack_wire(buf, layout))

    def _topk_impl(self, state, index, buf, layout):
        """User-tower pass + dot-product scan + device top-k — the
        whole retrieval scoring path as ONE jitted program (AOT per
        bucket like predict).  ``index`` [N, D] rides as an argument,
        so a rollout's new index needs zero recompiles."""
        batch = self.step._expand_wire(unpack_wire(buf, layout))
        rows = self.step._gather_model_rows(state["tables"], batch)
        u = self.model.user_embed(
            rows, self.step._model_view(batch), state["dense"]
        )  # [B, D]
        scores = u @ index.T  # [B, N]
        vals, idx = jax.lax.top_k(scores, self.topk_k)
        return vals, idx, u

    def _item_embed_impl(self, state, buf, layout):
        """Item-tower pass [B, D] — export_item_index's batch leg."""
        batch = self.step._expand_wire(unpack_wire(buf, layout))
        rows = self.step._gather_model_rows(state["tables"], batch)
        return self.model.item_embed(
            rows, self.step._model_view(batch), state["dense"]
        )

    def _put_packed(self, batch: Batch, validate: bool):
        """The engine's own transfer in: the planes the step's wire
        format ships for ``batch`` (``TrainStep.host_wire_np``; with
        ``validate``, its compact-wire check of the batch first), packed
        into one byte buffer (``step.pack_wire_np``) that crosses in ONE
        host->device call, batch axis sharded over the mesh; the
        program unpacks it (``step.unpack_wire``).  ``TrainStep.
        put_batch`` is the trainer's: a call a plane, on worker threads
        that hide them; on the batcher's worker thread each call is
        serial latency.  Returns the device buffer and its layout."""
        step = self.step
        with self.obs.phase("h2d"):
            # TrainStep validates compact-wire invariants only on its
            # FIRST batch (fine for uniform loader traffic); serving
            # traffic is heterogeneous, so a value-carrying request
            # after warmup would otherwise have its vals silently
            # replaced by 1.0 — ``validate`` checks every batch (O(B·K)
            # numpy, noise next to the device call at serving batch
            # sizes).
            wire, _ = step.host_wire_np(batch, check=validate)
            step._book_wire(
                sum(int(v.nbytes) for v in wire.values()),
                batch.num_real(),
                cold_slots=batch.batch_size * batch.max_nnz,
                hot_slots=batch.batch_size * batch.hot_nnz,
                slots_bytes=sum(
                    int(v.nbytes) for k, v in wire.items()
                    if k in _SLOT_PLANES
                ),
                values_bytes=sum(
                    int(v.nbytes) for k, v in wire.items()
                    if k in _VALUE_PLANES
                ),
            )
            host, layout = pack_wire_np(wire)
            if jax.process_count() > 1:
                # each host packed its own rows: one global buffer
                from jax.experimental import multihost_utils

                buf = multihost_utils.host_local_array_to_global_array(
                    host, self.mesh, step._bsharding.spec
                )
            else:
                buf = jax.device_put(host, step._bsharding)
        self.last_h2d = {"transfers": 1, "bytes": host.nbytes}
        return buf, layout

    def _put_dispatch_fetch(
        self, key, jitted, batch: Batch, extra=(), *, beat: str,
        validate: bool = False, host_local: bool = False,
    ):
        """One bucketed device call in its three host legs, shared by
        predict_prepared and the topk / item-embed legs.  Each leg is
        an ``xf.serve_*`` span on the profiler's timeline (obs/__init__
        .py::profiler_span: there with or without a live ``Obs``) and
        a clock of ``last_device_phases``: ``h2d`` the transfer in
        (``_put_packed``: with ``validate``, the compact-wire check of
        the request batch before it), ``dispatch`` the executable's call
        until it RETURNS (the host enqueueing the program), ``fetch`` the
        wait for the device and the copy out.  Compiled once per ``key``
        (``jitted`` lowered over state, ``extra`` leading arrays, the
        packed batch and its layout); a compile is the phase
        ``serve_compile`` and no leg's.  ``host_local`` gathers a
        multi-host result to this host's rows first."""
        t_call = time.perf_counter()
        with profiler_span("serve_h2d"):
            buf, layout = self._put_packed(batch, validate)
        t_run = t_h2d = time.perf_counter()
        exe = self._compiled.get(key)
        if exe is None:
            with self.obs.phase("serve_compile"):
                exe = jitted.lower(
                    self.state, *extra, buf, layout=layout
                ).compile()
            self._compiled[key] = exe
            self.obs.counter("serve.compiles")
            t_run = time.perf_counter()
        with profiler_span("serve_dispatch"):
            out = exe(self.state, *extra, buf)
        t_ret = time.perf_counter()
        with profiler_span("serve_fetch"):
            if host_local and jax.process_count() > 1:
                from jax.experimental import multihost_utils

                out = multihost_utils.global_array_to_host_local_array(
                    out, self.mesh, self.step._bsharding.spec
                )
            # booked: the leg's clock below is serve.fetch_seconds in
            # the batcher's registry, Obs or no Obs (xf: ignore[XF002])
            out = jax.tree.map(np.asarray, jax.device_get(out))
        self.last_device_phases = {
            "h2d": t_h2d - t_call,
            "dispatch": t_ret - t_run,
            "fetch": time.perf_counter() - t_ret,
        }
        if self.obs.flight is not None:
            # serve-channel heartbeat (obs/flight.py): one device call
            # completed — the watchdog's "is scoring moving?" signal,
            # tagged with the bucket it ran in (forensics for "which
            # shape was in flight when serving wedged")
            self.obs.flight.note_serve(f"{beat}:b{batch.batch_size}")
        return out

    def _run_aot(self, tag: str, jitted, batch: Batch, extra=()):
        """Compile-once-per-bucket execution of the topk and item-embed
        legs.  ``extra`` arrays ride as leading executable arguments
        after state."""
        key = (tag, self.topk_k, batch.batch_size, batch.max_nnz,
               batch.hot_nnz)
        return self._put_dispatch_fetch(key, jitted, batch, extra, beat=tag)

    def topk_prepared(
        self, batch: Batch
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(item_ids [B, k], scores [B, k], user_emb [B, D]) for one
        already-prepared bucket-sized batch — the batcher's top-k leg.
        ``user_emb`` is returned so parity checks (the cascade smoke
        gate's numpy full-scan argsort) can verify the device scan
        independently."""
        if self._index_arr is None:
            raise ValueError(
                "top-k refused: no item index attached — export one "
                "with serve.artifact.export_item_index (retrieval "
                "families only) or attach_item_index(...)"
            )
        vals, idx, u = self._run_aot(
            "topk", self.topk_jit, batch, extra=(self._index_arr,)
        )
        ids = self.item_index["item_ids"][idx]
        return ids, vals, u

    def topk(
        self, batch: Batch, k: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """(item_ids [B, k], scores [B, k]) for an externally built
        raw-key-space batch of USER-side features.  Any batch size
        (pad/chunk like ``predict``); any ``k <= topk_k`` slices the
        one compiled result width — mixed-k traffic never compiles."""
        kk = self.topk_k if k is None else int(k)
        if kk < 1 or kk > self.topk_k:
            raise ValueError(
                f"k={kk} outside (0, topk_k={self.topk_k}] — the "
                "engine compiles ONE top-k width; raise topk_k at "
                "load/attach time for deeper candidate sets"
            )
        n = batch.batch_size
        batch = self._prepare(batch)
        cap = self.buckets[-1]
        ids_out, score_out = [], []
        for s in range(0, n, cap):
            e = min(s + cap, n)
            chunk = pad_batch_rows(
                _slice_rows(batch, s, e), self.bucket_for(e - s)
            )
            ids, vals, _ = self.topk_prepared(chunk)
            ids_out.append(ids[: e - s, :kk])
            score_out.append(vals[: e - s, :kk])
        return np.concatenate(ids_out), np.concatenate(score_out)

    def item_embeddings(self, rows: Sequence) -> np.ndarray:
        """Item-tower embeddings [len(rows), model.index_dim] (the
        tower_dim core lanes + the two bias-augmentation lanes,
        models/two_tower.py) for featurize_raw-protocol catalog rows —
        export_item_index's compute leg, bucket-chunked through the
        same AOT path."""
        if not hasattr(self.model, "item_embed"):
            raise ValueError(
                f"model {self.cfg.model!r} has no item tower "
                "(registry: retrieval=False)"
            )
        cap = self.buckets[-1]
        out = []
        for s in range(0, len(rows), cap):
            chunk = rows[s : s + cap]
            b = pad_batch_rows(
                self._prepare(self.featurize_raw(chunk)),
                self.bucket_for(len(chunk)),
            )
            emb = self._run_aot("item_embed", self.item_embed_jit, b)
            out.append(emb[: len(chunk)])
        return np.concatenate(out)

    # -- predict -----------------------------------------------------------

    def _prepare(self, batch: Batch) -> Batch:
        """Canonicalize an external raw-key-space batch: widen the cold
        section so the total feature width is the loader's per-row
        capacity ``row_nnz`` (narrower batches get zero-mask columns —
        no new compile shapes), then apply the hot remap + steering
        with the training geometry's cold capacity, so the prepared
        batch is the one the loader would have packed.

        Batches WIDER than ``row_nnz`` keep their width (truncating
        would silently drop features the caller passed) and compile
        one extra executable per distinct width — counted in
        ``serve.noncanonical_shape``.  The batcher/featurize tier only
        ever produces canonical widths, so the no-recompile guarantee
        holds for serving traffic; a direct ``predict`` caller who
        wants it too must stay within ``row_nnz``."""
        cfg = self.cfg
        if batch.hot_nnz and not cfg.hot_size:
            raise ValueError(
                "batch carries hot planes but the model has no hot table"
            )
        total = batch.hot_nnz + batch.max_nnz
        wide = total > self.row_nnz
        if wide:
            self.obs.counter("serve.noncanonical_shape")
        elif total < self.row_nnz:
            pad = self.row_nnz - total
            b = batch.batch_size
            z_i = np.zeros((b, pad), np.int32)
            z_f = np.zeros((b, pad), np.float32)
            batch = Batch(
                keys=np.concatenate([batch.keys, z_i], axis=1),
                slots=np.concatenate([batch.slots, z_i], axis=1),
                vals=np.concatenate([batch.vals, z_f], axis=1),
                mask=np.concatenate([batch.mask, z_f], axis=1),
                labels=batch.labels,
                weights=batch.weights,
                hot_keys=batch.hot_keys,
                hot_slots=batch.hot_slots,
                hot_vals=batch.hot_vals,
                hot_mask=batch.hot_mask,
            )
        return remap_batch(
            batch, self.remap, cfg.hot_size, cfg.hot_nnz,
            cold_nnz=None if wide else cfg.max_nnz,
        )

    def predict(self, batch: Batch) -> np.ndarray:
        """pctr for one externally built Batch (raw hash key space —
        the remap is applied here).  Any batch size: rows pad up to the
        smallest covering bucket; oversized batches chunk by the
        largest bucket.  Returns exactly ``batch.batch_size`` values."""
        n = batch.batch_size
        batch = self._prepare(batch)
        cap = self.buckets[-1]
        if n <= cap:
            padded = pad_batch_rows(batch, self.bucket_for(n))
            return self.predict_prepared(padded)[:n]
        out = []
        for s in range(0, n, cap):
            e = min(s + cap, n)
            chunk = pad_batch_rows(
                _slice_rows(batch, s, e), self.bucket_for(e - s)
            )
            out.append(self.predict_prepared(chunk)[: e - s])
        return np.concatenate(out)

    def predict_prepared(self, batch: Batch) -> np.ndarray:
        """Run one already-prepared, bucket-sized batch on the device;
        returns pctr for every row (padding included).  This is the
        'device' leg of the batcher's latency accounting: h2d +
        dispatch + fetch."""
        key = (batch.batch_size, batch.max_nnz, batch.hot_nnz)
        return self._put_dispatch_fetch(
            key, self.predict_jit, batch, beat="execute",
            validate=self.step.compact_wire, host_local=True,
        )

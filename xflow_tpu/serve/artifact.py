"""Exportable inference artifacts — the training→serving handoff.

An artifact is a directory holding everything prediction needs and
NOTHING training needs:

* ``<table>.param.r<start>-<stop>.npy`` — frozen weight-table rows in
  the checkpoint row-range shard format (utils/checkpoint.py): each
  process writes only the rows its devices own, and a later load can
  assemble ANY target sharding from whatever ranges exist via mmap —
  an artifact exported on a pod restores onto a 1-chip scoring tier.
  Optimizer aux arrays (FTRL n/z) are deliberately absent: they are
  ~2/3 of a checkpoint's bytes and serve no inference purpose.
* ``dense.<name>.npy`` — replicated dense params (MLP models).
* ``remap.npy`` — the hot-table frequency remap (io/freq.py), present
  iff the model was trained with a hot table.  The remap is part of
  the model: raw hash-space keys are addressed through it, so it ships
  inside the artifact instead of living beside checkpoints.
* ``manifest.json`` — format version, model name, the FULL training
  config JSON plus its digest (config.Config.digest), array metadata,
  and the train-step counter.  PredictEngine refuses artifacts whose
  stored digest doesn't match the embedded config (tampering/drift)
  or a caller-expected config (serving the wrong model).

Multi-host protocol: identical to save_checkpoint — all processes
write into a temp dir, every stage votes through ``all_ok`` (a barrier
that propagates local failures instead of deadlocking), process 0
writes the manifest and atomically renames.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Any

import numpy as np

import jax

from xflow_tpu.chaos import failpoint
from xflow_tpu.utils.checkpoint import all_ok, iter_owned_shards

MANIFEST = "manifest.json"
FORMAT = 1
REMAP_FILE = "remap.npy"
# serve-time item-embedding index (retrieval families — models with
# user/item towers, models/two_tower.py): written ALONGSIDE an
# exported artifact by export_item_index, read back by
# PredictEngine.load for the top-k mode.  The meta file is the
# presence marker and is written LAST (tmp + atomic replace per file),
# so a crashed export never leaves a half-index that loads.
ITEM_INDEX_META = "item_index.json"


def servable_digest(config_digest: str, step: int) -> str:
    """Identity of one SERVABLE — a (config, train-step) point in the
    continuous-training chain (docs/CONTINUOUS.md).  A full export at
    step S and base + deltas applied up to step S are the same model
    by the delta round-trip guarantee, so both carry this digest:
    incremental deltas chain on it (``base_digest`` → ``delta_digest``,
    stream/delta.py) and ``PredictEngine``/``ReplicaFleet`` refuse a
    delta whose base is not the servable they currently hold."""
    import hashlib

    return hashlib.sha256(
        f"{config_digest}@{int(step)}".encode()
    ).hexdigest()[:16]


def export_artifact(trainer, directory: str) -> str:
    """Freeze ``trainer``'s model into a serving artifact at
    ``directory`` (replaced atomically if it exists); returns the path.

    Multi-host: COLLECTIVE — all processes call together; each writes
    its own table row ranges (module docstring)."""
    from xflow_tpu.obs import NULL_OBS, startup

    # book the export's device fetches as an obs phase so a slow export
    # shows up in phase accounting instead of vanishing (XF002)
    obs = getattr(trainer, "obs", None) or NULL_OBS
    # the whole export on the process's start-up timeline: a serving
    # process that trains or restores first pays it before its fleet
    with startup.phase("export_artifact", obs):
        return _export_artifact(trainer, directory, obs)


def _export_artifact(trainer, directory: str, obs) -> str:
    state = trainer.state
    cfg = trainer.cfg
    # chaos site: a fault anywhere in the export — the all_ok voting +
    # tmp-dir/rename-aside recovery below is what it exercises (XF018)
    failpoint("artifact.export")
    with obs.phase("export_fetch"):
        step = int(jax.device_get(state["step"]))
    proc = jax.process_index()
    parent = os.path.dirname(os.path.abspath(directory))
    tmp = os.path.join(
        parent, f".tmp-artifact-{os.path.basename(directory)}"
    )
    err: BaseException | None = None
    try:
        if proc == 0:
            os.makedirs(parent, exist_ok=True)
            if os.path.exists(tmp):  # leftover from a crashed attempt
                shutil.rmtree(tmp)
            os.makedirs(tmp)
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if err is not None:
            raise err
        raise RuntimeError("artifact mkdir failed on process 0")
    try:
        arrays_meta: dict[str, Any] = {}
        store = getattr(getattr(trainer, "step", None), "store", None)
        for tname in sorted(state["tables"]):
            key = f"{tname}.param"
            if store is not None:
                # tiered store (Config.store_mode): fold BOTH tiers
                # into the logical [T, D] table, materialized in
                # bounded chunks (store/tiered.py::
                # iter_logical_param_shards) — the artifact is
                # indistinguishable from a dense-mode export, so
                # PredictEngine loads it unchanged
                dim = store.cold.tables[tname].dim
                arrays_meta[key] = {
                    "shape": [cfg.table_size, dim],
                    "dtype": "float32",
                }
                with obs.phase("export_fetch"):
                    for start, stop, block in (
                        store.iter_logical_param_shards(state, tname)
                    ):
                        np.save(
                            os.path.join(
                                tmp,
                                f"{key}.r{start:012d}-{stop:012d}.npy",
                            ),
                            block,
                        )
                continue
            arr = state["tables"][tname]["param"]
            arrays_meta[key] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
            }
            for start, stop, host_data in iter_owned_shards(arr):
                np.save(
                    os.path.join(
                        tmp, f"{key}.r{start:012d}-{stop:012d}.npy"
                    ),
                    host_data,
                )
        if proc == 0:
            for dname in sorted(state.get("dense", {})):
                with obs.phase("export_fetch"):
                    host_dense = np.asarray(
                        jax.device_get(state["dense"][dname])
                    )
                np.save(
                    os.path.join(tmp, f"dense.{dname}.npy"), host_dense
                )
            if trainer.remap is not None:
                np.save(os.path.join(tmp, REMAP_FILE), trainer.remap)
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if proc == 0:
            shutil.rmtree(tmp, ignore_errors=True)
        if err is not None:
            raise err
        raise RuntimeError("artifact export failed on another process")
    try:
        if proc == 0:
            manifest = {
                "format": FORMAT,
                "model": cfg.model,
                "step": step,
                "config": cfg.to_json(),
                "config_digest": cfg.digest(),
                "arrays": arrays_meta,
                "dense": sorted(state.get("dense", {})),
                "remap": trainer.remap is not None,
                "created_unix": round(time.time(), 3),
            }
            with open(os.path.join(tmp, MANIFEST), "w") as f:
                json.dump(manifest, f, indent=2)
            # never leave the target path without a loadable artifact:
            # move the old one ASIDE first, rename the new one in, THEN
            # delete — a crash in between still leaves either the old
            # or the new artifact at (or recoverable next to) the path
            old = None
            if os.path.exists(directory):
                old = directory + ".old"
                if os.path.exists(old):
                    shutil.rmtree(old)
                os.rename(directory, old)
            os.rename(tmp, directory)
            if old is not None:
                shutil.rmtree(old)
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if proc == 0:
            shutil.rmtree(tmp, ignore_errors=True)
        if err is not None:
            raise err
        raise RuntimeError("artifact finalize failed on process 0")
    return directory


def _atomic_save(directory: str, name: str, arr: np.ndarray) -> None:
    tmp = os.path.join(directory, f".tmp-{name}")
    with open(tmp, "wb") as f:  # file object: np.save never re-suffixes
        np.save(f, arr)
    os.replace(tmp, os.path.join(directory, name))


def item_catalog_from_block(
    block, split_field: int, max_items: int = 0
) -> list[tuple]:
    """Deduplicated item catalog in the featurize_raw row protocol
    from one parsed libffm block: each sample's ITEM-side features
    (slots >= ``split_field``) form a candidate, identified by its
    sorted key set.  The ONE copy of the catalog-identity rule, shared
    by the ``serve index`` CLI and the cascade smoke gate so the
    shipped tool and the tier-1 gate cannot diverge."""
    import numpy as np  # local: the module-level import exists; keep explicit

    items: list[tuple] = []
    seen: set[tuple] = set()
    for i in range(block.num_samples):
        lo, hi = int(block.row_ptr[i]), int(block.row_ptr[i + 1])
        ks = block.keys[lo:hi].astype(np.int64)
        ss = block.slots[lo:hi].astype(np.int32)
        sel = ss >= split_field
        ident = tuple(sorted(ks[sel]))
        if ident and ident not in seen:
            seen.add(ident)
            items.append((ks[sel], ss[sel], None))
        if max_items and len(items) >= max_items:
            break
    return items


def export_item_index(
    engine,
    directory: str,
    item_rows: list,
    item_ids=None,
) -> dict:
    """Freeze the item-tower embeddings of a retrieval model into a
    serve-time index inside an already-exported artifact directory.

    ``item_rows`` is the catalog in the ``featurize_raw`` row protocol
    (item-side features: slots in [tower_split_field, max_fields) and
    raw hash-space keys); ``item_ids`` the external item identity per
    row (default: the row ordinal).  ``engine`` must be a
    PredictEngine loaded from — or digest-identical to — ``directory``
    (a mismatched engine would bake embeddings from a different model
    into this artifact's index).

    Written files: ``item_index.npy`` [N, model.index_dim] embeddings
    (tower_dim core + 2 bias lanes — the top-k scan operand),
    ``item_ids.npy`` [N] int64, and the padded
    raw feature planes ``item_keys/item_slots/item_vals.npy`` [N, nnz]
    + ``item_nnz.npy`` [N] — the cascade (serve/cascade.py) reads
    those to assemble user+candidate rows for the ranking stage.
    Meta (``item_index.json``) carries count/dim/config digest and the
    servable step, so a stale index against a re-exported artifact is
    refused at load."""
    failpoint("artifact.export")
    manifest = load_manifest(directory)
    if engine.digest != manifest["config_digest"]:
        raise ValueError(
            f"export_item_index: engine digest {engine.digest} != "
            f"artifact {directory} digest {manifest['config_digest']} "
            "— the index must be computed by the model it ships with"
        )
    if not hasattr(engine.model, "item_embed"):
        raise ValueError(
            f"model {engine.cfg.model!r} has no item tower "
            "(models/__init__.py registry: retrieval=False) — only "
            "two-tower-factored families export an item index"
        )
    n = len(item_rows)
    if n < 1:
        raise ValueError("export_item_index: empty item catalog")
    emb = engine.item_embeddings(item_rows)  # [N, tower_dim]
    ids = (
        np.arange(n, dtype=np.int64)
        if item_ids is None
        else np.asarray(item_ids, dtype=np.int64)
    )
    if len(ids) != n:
        raise ValueError(
            f"export_item_index: {n} rows but {len(ids)} item_ids"
        )
    k = engine.row_nnz  # what item_embeddings' featurize kept
    keys = np.zeros((n, k), np.int64)
    slots = np.zeros((n, k), np.int32)
    vals = np.zeros((n, k), np.float32)
    nnz = np.zeros(n, np.int32)
    for i, row in enumerate(item_rows):
        rk, rs, rv = row if isinstance(row, tuple) else (row, None, None)
        rk = np.asarray(rk)
        m = min(len(rk), k)
        nnz[i] = m
        keys[i, :m] = rk[:m]
        if rs is not None:
            slots[i, :m] = np.asarray(rs)[:m]
        vals[i, :m] = 1.0 if rv is None else np.asarray(rv)[:m]
    _atomic_save(directory, "item_index.npy", emb.astype(np.float32))
    _atomic_save(directory, "item_ids.npy", ids)
    _atomic_save(directory, "item_keys.npy", keys)
    _atomic_save(directory, "item_slots.npy", slots)
    _atomic_save(directory, "item_vals.npy", vals)
    _atomic_save(directory, "item_nnz.npy", nnz)
    meta = {
        "count": n,
        "dim": int(emb.shape[1]),
        "nnz": int(k),
        "config_digest": engine.digest,
        "servable": engine.servable_digest,
        "created_unix": round(time.time(), 3),
    }
    tmp = os.path.join(directory, ".tmp-" + ITEM_INDEX_META)
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=2)
    os.replace(tmp, os.path.join(directory, ITEM_INDEX_META))
    return meta


def load_item_index(directory: str) -> dict | None:
    """The index exported by :func:`export_item_index`, or None when
    the artifact has no index.  Refuses (ValueError) an index whose
    config digest does not match the artifact manifest — that is a
    stale index left behind by a re-export under a different config,
    and serving it would retrieve with the wrong geometry."""
    failpoint("artifact.load")
    path = os.path.join(directory, ITEM_INDEX_META)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        meta = json.load(f)
    manifest = load_manifest(directory)
    if meta.get("config_digest") != manifest["config_digest"]:
        raise ValueError(
            f"{directory}: item index was built for config "
            f"{meta.get('config_digest')!r} but the artifact is "
            f"{manifest['config_digest']!r} — re-run export_item_index "
            "against the current artifact"
        )
    out = dict(meta)
    for name in (
        "item_index", "item_ids", "item_keys", "item_slots",
        "item_vals", "item_nnz",
    ):
        out[name] = np.load(os.path.join(directory, f"{name}.npy"))
    if out["item_index"].shape != (meta["count"], meta["dim"]):
        raise ValueError(
            f"{directory}: item_index.npy shape "
            f"{out['item_index'].shape} does not match meta "
            f"({meta['count']}, {meta['dim']})"
        )
    return out


def load_manifest(directory: str) -> dict:
    """Parse + integrity-check an artifact manifest.  Raises ValueError
    on a missing/foreign/future-format manifest or when the stored
    config digest doesn't match the embedded config (tampering or a
    digest-scheme drift — either way the artifact identity is void)."""
    from xflow_tpu.config import Config

    failpoint("artifact.load")
    path = os.path.join(directory, MANIFEST)
    if not os.path.exists(path):
        raise ValueError(f"{directory}: no artifact manifest ({MANIFEST})")
    with open(path) as f:
        manifest = json.load(f)
    if manifest.get("format") != FORMAT:
        raise ValueError(
            f"{directory}: unsupported artifact format "
            f"{manifest.get('format')!r} (expected {FORMAT})"
        )
    cfg = Config.from_json(manifest["config"])
    if cfg.digest() != manifest.get("config_digest"):
        raise ValueError(
            f"{directory}: manifest config_digest "
            f"{manifest.get('config_digest')!r} does not match the "
            f"embedded config ({cfg.digest()}) — artifact corrupt or "
            "tampered"
        )
    return manifest

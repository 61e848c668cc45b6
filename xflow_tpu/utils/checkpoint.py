"""Checkpoint / resume — a capability gap the reference lacks entirely
(SURVEY §5: weights live only in server RAM; training ends, weights
vanish).  Here: atomic directory checkpoints holding every table array
(param + optimizer state, e.g. FTRL n/z), the step counter, and a JSON
manifest with per-host data cursors (epoch, shard index, byte offset)
so training resumes mid-shard at block granularity.

Sharded I/O (round-2 redesign): each process writes ONLY the table row
ranges its devices own — no allgather, so peak host memory and network
traffic are O(T / num_processes) per process instead of O(T) everywhere
(at the 2^28-row north star with FM that allgather was ~35 GB per
process per checkpoint).  A row-range file is named
``<table>.<array>.r<start>-<stop>.npy``; restore assembles any target
sharding from whichever ranges exist via mmap, so a checkpoint written
on one mesh restores onto another (including different process counts).

Multi-host protocol (shared checkpoint filesystem assumed, the normal
arrangement): all processes write into a deterministic temp dir, a
barrier ensures completeness, then process 0 writes the manifest and
atomically renames.  Format: plain .npy + manifest.json — no orbax
dependency, trivially inspectable.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
from typing import Any

import numpy as np

import jax

from xflow_tpu.chaos import failpoint

MANIFEST = "manifest.json"

_RANGE_RE = re.compile(r"\.r(\d+)-(\d+)\.npy$")


def all_ok(local_ok: bool) -> bool:
    """True iff every process reports success.  Doubles as a barrier, so
    a process that FAILED its local I/O still reaches this point and the
    others learn about the failure instead of deadlocking in a plain
    sync (every code path on every process must call this the same
    number of times).  Public: serve/artifact.py runs the same
    write-shards/vote/finalize protocol for inference artifacts."""
    if jax.process_count() == 1:
        return local_ok
    from jax.experimental import multihost_utils

    flags = np.asarray(
        multihost_utils.process_allgather(np.int32(1 if local_ok else 0))
    )
    return bool(flags.min() == 1)


class IncompatibleCheckpoint(ValueError):
    """Checkpoint exists but cannot be loaded by this version (e.g. a
    pre-sharded-format manifest).  Trainer.restore treats it as
    'no usable checkpoint' rather than crashing."""


def iter_owned_shards(arr: jax.Array):
    """(start_row, stop_row, host_data) for every addressable shard this
    process is responsible for writing (replica 0 of each distinct row
    range — replicated copies on other devices/processes skip).
    Public: shared with serve/artifact.py's export."""
    seen: set[tuple[int, int]] = set()
    nrows = arr.shape[0]
    for shard in arr.addressable_shards:
        idx = shard.index
        rows = idx[0] if idx else slice(None)
        start = rows.start or 0
        stop = rows.stop if rows.stop is not None else nrows
        if len(idx) > 1:
            cols = idx[1]
            if not (cols.start in (None, 0) and cols.stop in (None, arr.shape[1])):
                raise NotImplementedError(
                    "checkpointing assumes column-replicated tables"
                )
        if shard.replica_id != 0 or (start, stop) in seen:
            continue
        seen.add((start, stop))
        yield start, stop, np.asarray(shard.data)


def _flat_arrays(state: dict[str, Any]) -> list[tuple[str, jax.Array]]:
    """(key, array) for every table array, in deterministic order."""
    out = []
    for tname in sorted(state["tables"]):
        for aname in sorted(state["tables"][tname]):
            out.append((f"{tname}.{aname}", state["tables"][tname][aname]))
    return out


def save_checkpoint(
    directory: str,
    state: dict[str, Any],
    cursor: dict[str, Any],
    config_json: str | None = None,
    keep: int = 0,
) -> str:
    """Write one checkpoint; returns its path.  ``state`` is the train
    step's pytree; ``cursor`` is loader-position metadata — pass
    per-host cursors under ``cursor["cursors"]`` (trainer.save does).
    ``keep`` > 0 deletes all but the newest ``keep`` ckpt-* dirs after a
    successful save (0 = keep everything).

    Multi-host: COLLECTIVE — all processes call together; each writes
    its own shards (see module docstring)."""
    step = int(jax.device_get(state["step"]))
    final = os.path.join(directory, f"ckpt-{step:010d}")
    tmp = os.path.join(directory, f".tmp-ckpt-{step:010d}")
    proc = jax.process_index()
    # Every process passes through ALL THREE all_ok gates on every
    # path, so a local I/O failure at any stage — including process 0's
    # mkdir, which runs before any peer has work to do — is reported to
    # the peers instead of leaving them deadlocked (a bare barrier here
    # would hang: the failing process would enter all_ok's allgather
    # while the others sit in sync_global_devices).
    err: BaseException | None = None
    try:
        if proc == 0:
            os.makedirs(directory, exist_ok=True)
            if os.path.exists(tmp):  # leftover from a crashed attempt
                shutil.rmtree(tmp)
            os.makedirs(tmp)
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if err is not None:
            raise err
        raise RuntimeError(
            f"checkpoint mkdir failed on process 0 (step {step})"
        )
    try:
        # chaos site: a fire mid-write takes the all_ok error path —
        # the half-written .tmp dir is cleaned and the previous
        # committed generation stays the newest complete one
        failpoint("ckpt.write_shard")
        arrays_meta: dict[str, Any] = {}
        for key, arr in _flat_arrays(state):
            arrays_meta[key] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
            }
            for start, stop, host_data in iter_owned_shards(arr):
                np.save(
                    os.path.join(tmp, f"{key}.r{start:012d}-{stop:012d}.npy"),
                    host_data,
                )
        if proc == 0:
            for dname in sorted(state.get("dense", {})):
                arr = state["dense"][dname]
                np.save(
                    os.path.join(tmp, f"dense.{dname}.npy"),
                    np.asarray(jax.device_get(arr)),
                )
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if proc == 0:
            shutil.rmtree(tmp, ignore_errors=True)
        if err is not None:
            raise err
        raise RuntimeError(
            f"checkpoint save failed on another process (step {step})"
        )
    try:
        if proc == 0:
            manifest = {
                "format": 2,
                "step": step,
                "arrays": arrays_meta,
                "dense": sorted(state.get("dense", {})),
                "cursor": cursor,
                "config": config_json,
            }
            with open(os.path.join(tmp, MANIFEST), "w") as f:
                json.dump(manifest, f, indent=2)
            # chaos site: a "kill mid-commit" — the manifest is written
            # (inside tmp; manifest-last ordering means no final dir
            # ever exists without one) but the rename never runs, so
            # the generation is invisible to latest_complete()
            failpoint("ckpt.finalize")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            _write_latest(directory, os.path.basename(final))
            if keep > 0:
                gc_checkpoints(directory, keep)
    except BaseException as e:
        err = e
    if not all_ok(err is None):
        if proc == 0:
            shutil.rmtree(tmp, ignore_errors=True)
        if err is not None:
            raise err
        raise RuntimeError(
            f"checkpoint finalize failed on process 0 (step {step})"
        )
    return final


def gc_checkpoints(directory: str, keep: int) -> list[str]:
    """Delete all but the newest ``keep`` COMPLETE ckpt-* dirs (by
    step number — the zero-padded name sorts chronologically); returns
    the deleted paths.  Only complete generations (manifest present)
    count toward the keep budget and only they are deleted: a
    manifest-less dir is external corruption, not a generation — it
    must neither occupy a keep slot (which would leave fewer than
    ``keep`` restorable generations for ``--resume auto``) nor be
    silently destroyed (it is evidence).  The dir LATEST points at is
    never deleted even if a clock anomaly makes it sort old.
    Process-0-only in multi-host runs (save_checkpoint calls it inside
    the rank-0 finalize block)."""
    assert keep > 0
    cands = sorted(
        d
        for d in os.listdir(directory)
        if d.startswith("ckpt-")
        and os.path.isdir(os.path.join(directory, d))
        and is_complete(os.path.join(directory, d))
    )
    latest = None
    marker = os.path.join(directory, "LATEST")
    if os.path.exists(marker):
        with open(marker) as f:
            latest = f.read().strip()
    doomed = [d for d in cands[:-keep] if d != latest]
    removed = []
    for d in doomed:
        path = os.path.join(directory, d)
        shutil.rmtree(path, ignore_errors=True)
        removed.append(path)
    return removed


def _write_latest(directory: str, name: str) -> None:
    tmp = os.path.join(directory, ".latest.tmp")
    with open(tmp, "w") as f:
        f.write(name)
    os.replace(tmp, os.path.join(directory, "LATEST"))


def latest_checkpoint(directory: str) -> str | None:
    marker = os.path.join(directory, "LATEST")
    if os.path.exists(marker):
        # metadata peek: a torn/missing marker falls through to the
        # directory scan; restore itself carries ckpt.restore
        # (xf: ignore[XF018])
        with open(marker) as f:
            name = f.read().strip()
        path = os.path.join(directory, name)
        if os.path.exists(path):
            return path
    if not os.path.isdir(directory):
        return None
    cands = sorted(
        d for d in os.listdir(directory) if d.startswith("ckpt-")
    )
    return os.path.join(directory, cands[-1]) if cands else None


def checkpoint_candidates(directory: str) -> list[str]:
    """Every ckpt-* generation path, NEWEST first (zero-padded step in
    the name sorts chronologically).  .tmp-ckpt-* leftovers from
    crashed saves are never candidates."""
    if not os.path.isdir(directory):
        return []
    cands = sorted(
        (
            d
            for d in os.listdir(directory)
            if d.startswith("ckpt-")
            and os.path.isdir(os.path.join(directory, d))
        ),
        reverse=True,
    )
    return [os.path.join(directory, d) for d in cands]


def is_complete(path: str) -> bool:
    """A generation is COMPLETE iff its manifest exists — the commit
    protocol writes the manifest into the tmp dir and renames last, so
    a committed generation always has one; a manifest-less ckpt-* dir
    is external corruption (truncated copy, partial delete)."""
    return os.path.exists(os.path.join(path, MANIFEST))


def latest_complete(directory: str) -> str | None:
    """Newest COMPLETE generation, ignoring the LATEST marker (which a
    crash or external tamper can leave stale/corrupt) — the fallback
    `--resume auto` restores from after a kill mid-checkpoint
    (docs/ROBUSTNESS.md)."""
    for path in checkpoint_candidates(directory):
        if is_complete(path):
            return path
    return None


class RangeReader:
    """Assembles arbitrary row/col slices of one array from its
    row-range .npy files via mmap — peak memory O(requested slice)."""

    def __init__(self, path: str, key: str, shape, dtype):
        self.files: list[tuple[int, int, str]] = []
        for f in sorted(glob.glob(os.path.join(path, glob.escape(key) + ".r*.npy"))):
            m = _RANGE_RE.search(f)
            if m:
                self.files.append((int(m.group(1)), int(m.group(2)), f))
        self.files.sort()
        covered = 0
        for start, stop, _ in self.files:
            if start > covered:
                break
            covered = max(covered, stop)
        if covered < shape[0]:
            raise ValueError(
                f"checkpoint {path}: array {key} rows [{covered}, {shape[0]}) "
                f"missing (found {len(self.files)} range files)"
            )
        self.shape = tuple(shape)
        self.dtype = dtype

    def read(self, idx: tuple) -> np.ndarray:
        # chaos site: per-shard mmap read fault during restore/artifact
        # load — distinct from ckpt.restore so mid-assembly faults are
        # injectable (XF018)
        failpoint("ckpt.read_shard")
        rows = idx[0] if idx else slice(None)
        a = rows.start or 0
        b = rows.stop if rows.stop is not None else self.shape[0]
        out = np.empty((b - a, *self.shape[1:]), dtype=self.dtype)
        for start, stop, fname in self.files:
            lo, hi = max(a, start), min(b, stop)
            if lo >= hi:
                continue
            data = np.load(fname, mmap_mode="r")
            out[lo - a : hi - a] = data[lo - start : hi - start]
        if len(idx) > 1 and idx[1] != slice(None):
            out = out[:, idx[1]]
        return out


def load_checkpoint(
    path: str, state: dict[str, Any]
) -> tuple[dict[str, Any], dict[str, Any]]:
    """Restore into the (freshly initialized, correctly sharded) ``state``
    template; returns (new_state, cursor).  Each process reads only the
    row ranges its devices need (mmap), so restore memory is
    O(addressable rows), not O(T)."""
    failpoint("ckpt.restore")
    if not is_complete(path):
        # refuse, don't crash mid-load: a manifest-less generation is
        # an incomplete/corrupt commit — Trainer.restore treats this
        # as "try the next newest complete generation" (auto mode) or
        # "no usable checkpoint" rather than a FileNotFoundError
        raise IncompatibleCheckpoint(
            f"checkpoint {path} has no {MANIFEST} — incomplete or "
            "externally corrupted generation (the commit protocol "
            "writes the manifest before the rename, so this was never "
            "fully committed)"
        )
    with open(os.path.join(path, MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != 2:
        raise IncompatibleCheckpoint(
            f"checkpoint {path} has unsupported format "
            f"{manifest.get('format')!r} (expected 2)"
        )
    if "store" in manifest:
        raise IncompatibleCheckpoint(
            f"checkpoint {path} was written by store_mode='tiered' "
            "(tier-erased fold; store/tiered.py) — set "
            "store_mode='tiered' to restore it"
        )

    new_tables: dict[str, Any] = {}
    for tname, table in state["tables"].items():
        new_tables[tname] = {}
        for aname, arr in table.items():
            key = f"{tname}.{aname}"
            meta = manifest["arrays"].get(key)
            if meta is None:
                raise ValueError(f"checkpoint {path} missing array {key}")
            if tuple(meta["shape"]) != arr.shape:
                raise ValueError(
                    f"checkpoint array {key} shape {tuple(meta['shape'])} "
                    f"!= state {arr.shape}"
                )
            reader = RangeReader(path, key, arr.shape, np.dtype(meta["dtype"]))
            new_tables[tname][aname] = jax.make_array_from_callback(
                arr.shape, arr.sharding, reader.read
            )
    new_dense: dict[str, Any] = {}
    for dname, arr in state.get("dense", {}).items():
        fname = os.path.join(path, f"dense.{dname}.npy")
        if not os.path.exists(fname):
            raise ValueError(f"checkpoint {path} missing dense array {dname}")
        host = np.load(fname)
        if host.shape != arr.shape:
            raise ValueError(
                f"checkpoint dense {dname} shape {host.shape} != {arr.shape}"
            )
        new_dense[dname] = jax.device_put(host, arr.sharding)
    new_state = {
        "tables": new_tables,
        "dense": new_dense,
        # on the template's sharding, like every other leaf (a resumed
        # run must not retrace when its own output comes back in)
        "step": jax.device_put(
            np.int32(manifest["step"]), state["step"].sharding
        ),
    }
    return new_state, manifest["cursor"]

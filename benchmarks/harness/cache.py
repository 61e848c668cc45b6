"""What set-up makes from (configuration, mix, seed) alone — a corpus, a hot
remap, a served artifact — kept between runs under the gitignored
``.bench_cache/<cell>-<seed>-<digest>/``, so that only the first run of a
cell with a seed in a checkout pays for it.

A run never hands the program a path inside the cache: every file of the
entry is hard-linked into the run's own work directory, which is new for
every run and removed after it.  Whatever the program writes beside its
inputs (a checkpoint, a side file) goes with that directory, and a later run
finds the entry as it was built.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import shutil

from benchmarks.harness import manifest
from benchmarks.harness.context import Ctx

KEEP = 2  # entries a cell keeps: a packed corpus at 2^28 is 1.8 GB
META = "meta.json"


def entry(ctx: Ctx, build) -> dict:
    """The meta of this run's entry, its files linked into ``ctx.work``.

    ``build(root) -> dict`` writes the entry's files under ``root`` and
    returns what ``meta.json`` is to hold (paths relative to ``root``); it is
    called only when no run in this checkout has built the same entry.  The
    meta comes back with ``"cache": "hit" | "miss"``."""
    key = json.dumps(
        {"fields": ctx.fields, "traffic": ctx.traffic, "seed": ctx.seed},
        sort_keys=True,
    )
    base = os.path.join(manifest.ROOT, ".bench_cache")
    digest = hashlib.sha256(key.encode()).hexdigest()[:10]
    root = os.path.join(base, f"{ctx.workload}-{ctx.seed}-{digest}")
    found = os.path.exists(os.path.join(root, META))
    if not found:
        _make_room(base, ctx.workload)
        shutil.rmtree(root, ignore_errors=True)
        tmp = root + ".tmp"
        os.makedirs(tmp)
        meta = build(tmp)
        with open(os.path.join(tmp, META), "w") as f:
            json.dump(meta, f)
        os.rename(tmp, root)  # an entry is whole or absent
    os.utime(os.path.join(root, META))  # the newest entries are the ones kept
    for base_dir, _, files in os.walk(root):
        target = os.path.join(ctx.work, os.path.relpath(base_dir, root))
        os.makedirs(target, exist_ok=True)
        for name in files:
            os.link(os.path.join(base_dir, name), os.path.join(target, name))
    with open(os.path.join(root, META)) as f:
        return {**json.load(f), "cache": "hit" if found else "miss"}


def _make_room(base: str, workload: str) -> None:
    """Before a new entry is built: drop what a killed run left half-built,
    and all but the ``KEEP - 1`` newest entries of this cell."""
    for tmp in glob.glob(os.path.join(base, f"{workload}-*.tmp")):
        shutil.rmtree(tmp, ignore_errors=True)
    metas = glob.glob(os.path.join(base, f"{workload}-*", META))
    metas.sort(key=os.path.getmtime, reverse=True)
    for meta in metas[KEEP - 1:]:
        shutil.rmtree(os.path.dirname(meta), ignore_errors=True)

"""BENCHMARK.json and the files it names, found by name.

A cell is ``{name, config, traffic, chips, why}``.  Everything that belongs
to one configuration, one traffic mix, one kind of traffic or one per-layer
metric is a file of its own:

    config   -> the ``file`` of its ``configs`` entry
    traffic  -> benchmarks/traffic/<mix>.json
    kind     -> benchmarks/drivers/<kind>.py        (the mix's ``kind``)
    metric   -> benchmarks/layer_metrics/<name>.py

so a later PR adds a cell by adding files and entries, and no list in code
has to learn of them.  A name that resolves to nothing is an error that
names the path it looked for.
"""

from __future__ import annotations

import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")

# The seven Config fields that choose a code path in the step, the wire or
# the store (ROADMAP C1).  A configuration file never sets one: they stay at
# the program's defaults, so a PR that changes what ``auto`` chooses is seen
# by the cell.
PATH_SELECTORS = frozenset({
    "update_mode", "sequential_inner", "hot_windowend", "hot_impl",
    "wire_mode", "wire_dedup", "store_mode",
})
# keys of a configuration file that describe it and are not Config fields
CONFIG_META = frozenset({
    "source", "family", "deployment", "assumed", "reduced", "cut", "rehearsal",
})


class ManifestError(Exception):
    pass


def _read_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"), "manifest")


def _entry(manifest: dict, section: str, name: str) -> dict:
    for entry in manifest[section]:
        if entry["name"] == name:
            return entry
    known = ", ".join(e["name"] for e in manifest[section])
    raise ManifestError(f"no {section} entry named {name!r} (have: {known})")


def cell(manifest: dict, name: str) -> dict:
    return _entry(manifest, "workloads", name)


def config(manifest: dict, name: str) -> dict:
    """The configuration ``name`` of the manifest, from its ``file``."""
    return config_file(_entry(manifest, "configs", name)["file"])


def config_file(path: str) -> dict:
    """A configuration file (path relative to the checkout): Config fields at
    its top level beside the keys of CONFIG_META."""
    doc = _read_json(os.path.join(ROOT, path), "configuration")
    chosen = PATH_SELECTORS & set(doc)
    if chosen:
        raise ManifestError(
            f"{path} sets path selector(s) {sorted(chosen)}: a "
            "configuration fixes geometry and operating fields only"
        )
    return doc


def traffic(name: str) -> dict:
    path = os.path.join(BENCH_DIR, "traffic", f"{name}.json")
    doc = _read_json(path, f"traffic mix {name}")
    if "kind" not in doc:
        raise ManifestError(f"traffic mix {name}: no 'kind'")
    return doc


def _module(subdir: str, name: str, what: str):
    if not NAME_RE.match(name):
        raise ManifestError(f"{what}: bad name {name!r}")
    path = os.path.join(BENCH_DIR, subdir, f"{name}.py")
    if not os.path.exists(path):
        raise ManifestError(
            f"{what} {name!r}: no file {os.path.relpath(path, ROOT)}"
        )
    return importlib.import_module(f"benchmarks.{subdir}.{name}")


def driver(kind: str):
    """The module that runs one kind of traffic: ``run(ctx) -> Outcome``."""
    return _module("drivers", kind, "traffic kind")


def layer_metric(name: str):
    """The reader of one per-layer metric: ``read(run) -> number | None``."""
    return _module("layer_metrics", name, "per-layer metric")


def reference(family: str):
    """The plain reference of one model family."""
    return _module("reference", family, "reference")


def metrics_of(manifest: dict, section: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    without a ``workloads`` list, and those that list it."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def apply_rehearsal(doc: dict, rehearsal: bool) -> dict:
    """``doc`` without its ``rehearsal`` block, which is merged over it when
    rehearsing: the toy sizes of a file live in that file."""
    out = {k: v for k, v in doc.items() if k != "rehearsal"}
    if rehearsal:
        out.update(doc.get("rehearsal", {}))
    return out

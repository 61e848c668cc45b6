"""What set-up keeps between runs: built once per (cell, seed), linked into
each run's own directory, bounded in number."""

import os
import types

import pytest

from benchmarks.harness import cache, manifest


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    return tmp_path


def _ctx(checkout, seed: int, run: int, **fields):
    work = checkout / ".bench_cache" / f"cell.run{run}"
    work.mkdir(parents=True)
    return types.SimpleNamespace(
        workload="cell", seed=seed, work=str(work),
        fields={"table_size_log2": 10, **fields}, traffic={"kind": "k", "batches": 2},
    )


def _build(calls: list):
    def build(root: str) -> dict:
        calls.append(root)
        os.makedirs(os.path.join(root, "packed"))
        with open(os.path.join(root, "packed", "train-00000"), "w") as f:
            f.write("rows")
        return {"shards": ["packed/train-00000"], "rows": 7}

    return build


def test_built_once_and_linked_into_every_run(checkout):
    calls: list = []
    first = cache.entry(_ctx(checkout, 1, 0), _build(calls))
    again = _ctx(checkout, 1, 1)
    second = cache.entry(again, _build(calls))
    assert (first["cache"], second["cache"], len(calls)) == ("miss", "hit", 1)
    assert second["rows"] == 7 and second["shards"] == ["packed/train-00000"]
    linked = os.path.join(again.work, "packed", "train-00000")
    assert open(linked).read() == "rows" and os.stat(linked).st_nlink == 3
    # what a run writes beside its inputs stays in its own directory
    open(os.path.join(again.work, "packed", "side-file"), "w").close()
    third = _ctx(checkout, 1, 2)
    cache.entry(third, _build(calls))
    assert os.listdir(os.path.join(third.work, "packed")) == ["train-00000"]


def test_another_seed_geometry_or_mix_is_another_entry(checkout):
    calls: list = []
    cache.entry(_ctx(checkout, 1, 0), _build(calls))
    cache.entry(_ctx(checkout, 2, 1), _build(calls))
    cache.entry(_ctx(checkout, 2, 2, max_nnz=8), _build(calls))
    assert len(calls) == 3


def test_a_cell_keeps_its_newest_entries_only(checkout):
    calls: list = []
    for run, seed in enumerate([1, 2, 3, 4]):
        cache.entry(_ctx(checkout, seed, run), _build(calls))
    kept = sorted(
        d for d in os.listdir(checkout / ".bench_cache") if d.startswith("cell-")
    )
    assert len(kept) == cache.KEEP and all(d[5] in "34" for d in kept)


def test_a_half_built_entry_is_built_again(checkout):
    def dies(root: str) -> dict:
        os.makedirs(os.path.join(root, "packed"))
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        cache.entry(_ctx(checkout, 1, 0), dies)
    calls: list = []
    assert cache.entry(_ctx(checkout, 1, 1), _build(calls))["cache"] == "miss"
    assert not [d for d in os.listdir(checkout / ".bench_cache") if d.endswith(".tmp")]

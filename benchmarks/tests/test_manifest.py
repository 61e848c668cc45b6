"""BENCHMARK.json against the files it names, and the proof that the harness
takes a later cell as data: new files and new entries, no edit."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest, refcheck

DOC = manifest.load()
KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
    "per_layer",
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_shape_of_the_manifest():
    assert set(DOC) == KEYS
    assert DOC["paths"] == ["benchmarks"]
    assert DOC["command"][-1].startswith("benchmarks/")
    assert 1 <= DOC["run_seconds"] <= 51
    names = [
        e["name"] for section in ("configs", "workloads", "end_to_end", "per_layer")
        for e in DOC[section]
    ]
    assert len(names) == len(set(names))
    assert all(manifest.NAME_RE.match(n) for n in names)
    assert all(len(e["why"]) <= 200 for e in DOC["configs"] + DOC["workloads"])
    four = [w for w in DOC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in DOC["workloads"])
    assert len(four) <= max(1, len(DOC["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in DOC["workloads"]} == {c["name"] for c in DOC["configs"]}
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 << 10


def test_metrics_are_well_formed():
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    cells = {w["name"] for w in DOC["workloads"]}
    for m in DOC["per_layer"]:
        assert "bound" not in m and m["source"] in SOURCES
        moved = e2e[m["moves"]]
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for cell in cells:
        mine = [m["name"] for m in manifest.metrics_of(DOC, "end_to_end", cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert manifest.metrics_of(DOC, "per_layer", cell)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    entry = manifest.cell(DOC, cell)
    config = manifest.config(DOC, entry["config"])
    mix = manifest.traffic(entry["traffic"])
    assert callable(manifest.driver(mix["kind"]).run)
    family = manifest.reference(config["family"])
    # a family with dense parameters writes its logit alone (reference/ftrl.py)
    assert callable(family.logit)
    assert callable(family.matmuls if hasattr(family, "DENSE") else family.grad_logit)
    assert config["num_devices"] == entry["chips"]
    assert not manifest.PATH_SELECTORS & set(config)
    listed = next(c for c in DOC["configs"] if c["name"] == entry["config"])
    assert listed["source"] == config["source"]
    assert set(listed["reduced"]) == set(config["reduced"])
    assert listed["file"].startswith("benchmarks/configs/")
    # what is left after the describing keys must be fields of Config
    from xflow_tpu.config import Config

    Config(**{k: v for k, v in config.items() if k not in manifest.CONFIG_META})
    Config(**{
        k: v for k, v in manifest.apply_rehearsal(config, True).items()
        if k not in manifest.CONFIG_META
    })


@pytest.mark.parametrize("metric", [m["name"] for m in DOC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    entry = next(m for m in DOC["per_layer"] if m["name"] == metric)
    reader = manifest.layer_metric(metric)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"]
    )
    assert reader.read({}) is None  # nothing to read: nothing returned


def test_unresolvable_names_say_which_path_is_missing():
    with pytest.raises(manifest.ManifestError, match="benchmarks/traffic/nope.json"):
        manifest.traffic("nope")
    with pytest.raises(manifest.ManifestError, match="benchmarks/drivers/nope.py"):
        manifest.driver("nope")
    with pytest.raises(manifest.ManifestError, match="benchmarks/layer_metrics/nope.py"):
        manifest.layer_metric("nope")
    with pytest.raises(manifest.ManifestError, match="no workloads entry"):
        manifest.cell(DOC, "nope")


def test_a_config_may_not_choose_a_path(tmp_path):
    bad = dict(manifest.config(DOC, DOC["configs"][0]["name"]), wire_dedup="off")
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    with pytest.raises(manifest.ManifestError, match="wire_dedup"):
        manifest.config_file(str(tmp_path / "bad.json"))


def _digests(root: str) -> dict:
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def _copy_of_the_tree(tmp_path) -> tuple[str, dict]:
    root = str(tmp_path / "checkout")
    shutil.copytree(
        os.path.join(manifest.ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    os.symlink(os.path.join(manifest.ROOT, "xflow_tpu"), os.path.join(root, "xflow_tpu"))
    return root, _digests(root)


def _rehearse(root: str, doc: dict, cell: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", "4",
         "--seconds", "0.5", "--trace", "1", "--rehearsal"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last and "correct" not in last
    assert all(last["checks"].values()), last["checks"]
    return last


def _with_cell(doc: dict, config: str, cell: str, traffic: str) -> dict:
    """``doc`` with a made-up configuration and its one training cell."""
    doc = json.loads(json.dumps(doc))
    doc["configs"].append({
        "name": config, "source": "https://example.org/made-up",
        "file": f"benchmarks/configs/{config}.json", "reduced": [], "why": "made up",
    })
    doc["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": 1,
        "why": "made up",
    })
    for m in doc["end_to_end"]:
        if m["name"] == "train_examples_per_s":
            m["workloads"].append(cell)
    return doc


def test_a_later_cell_is_new_files_and_entries_only(tmp_path):
    """A made-up configuration, a made-up mix (of an existing kind, so it
    needs no code) and a made-up per-layer metric, added to a copy of the
    tree as new files and new manifest entries, run as a rehearsal."""
    root, before = _copy_of_the_tree(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs", "made_up_lr.json"), "w") as f:
        json.dump({
            "source": "https://example.org/made-up", "family": "lr",
            "deployment": "made up", "model": "lr", "optimizer": "ftrl",
            "table_size_log2": 14, "batch_size": 512, "max_nnz": 40,
            "num_devices": 1, "assumed": {}, "reduced": {},
        }, f)
    with open(os.path.join(root, "benchmarks", "traffic", "made_up_lowskew.json"), "w") as f:
        json.dump({
            "kind": "train_text",
            "rows": {"zipf_a": 0.8, "cat_vocab_max": 100000},
            "batches": 2, "reference_steps": 1, "warmup_epochs": 1,
            "step_probe_steps": 2,
        }, f)
    with open(os.path.join(root, "benchmarks", "layer_metrics", "made_up_steps.py"), "w") as f:
        f.write(
            'LAYER, UNIT, MOVES, SOURCE = "input", "steps", '
            '"train_examples_per_s", "program_counter"\n\n\n'
            "def read(run):\n"
            '    return sum(e["steps"] for e in run.get("epochs", [])) or None\n'
        )
    doc = _with_cell(DOC, "made_up_lr", "made_up.train_lowskew", "made_up_lowskew")
    doc["per_layer"].append({
        "name": "made_up_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "input",
        "moves": "train_examples_per_s", "workloads": ["made_up.train_lowskew"],
    })
    last = _rehearse(root, doc, "made_up.train_lowskew")
    assert last["per_layer_reported"] == ["made_up_steps"]
    assert last["counts"]["rows_per_epoch"] == 1024
    assert last["counts"]["corpus_cache"] == "miss"
    # the same seed again finds the corpus the first run built
    assert _rehearse(root, doc, "made_up.train_lowskew")["counts"]["corpus_cache"] == "hit"
    after = _digests(root)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert set(after) - set(before) == {
        "benchmarks/configs/made_up_lr.json",
        "benchmarks/traffic/made_up_lowskew.json",
        "benchmarks/layer_metrics/made_up_steps.py",
    }


def test_a_later_family_that_reads_field_ids_is_a_configuration_file(tmp_path):
    """The room PR 31 made: a family whose forward reads which field an entry
    belongs to (``reference/mvm.py``; the program's ``uses_slots`` models)
    gets a cell as ONE new file and manifest entries, under a mix that is
    there: the slots plane rides the wire, the reference step is handed the
    field ids, and the rehearsal ends with ``steps_match_reference`` true."""
    root, before = _copy_of_the_tree(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs", "made_up_mvm.json"), "w") as f:
        json.dump({
            "source": "https://example.org/made-up", "family": "mvm",
            "deployment": "made up", "model": "mvm", "optimizer": "ftrl",
            "v_dim": 10, "v_init_scale": 0.01, "max_fields": 40,
            "table_size_log2": 25, "batch_size": 131072, "max_nnz": 8,
            "hot_size_log2": 14, "hot_nnz": 32, "num_devices": 1,
            "assumed": {}, "reduced": {},
            "rehearsal": {
                "table_size_log2": 14, "batch_size": 512, "hot_size_log2": 8,
                "max_nnz": 40,
            },
        }, f)
    doc = _with_cell(DOC, "made_up_mvm", "made_up_mvm.train_packed", "replay_packed_zipf")
    last = _rehearse(root, doc, "made_up_mvm.train_packed")
    assert last["checks"]["steps_match_reference"] is True
    assert last["counts"]["reference_ok"] is True
    rows = last["compared"]["rows_rel_err"]
    assert rows["value"] <= rows["limit"] == refcheck.ROWS_RTOL
    after = _digests(root)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert set(after) - set(before) == {"benchmarks/configs/made_up_mvm.json"}


def test_a_later_family_with_dense_parameters_is_a_configuration_file(tmp_path):
    """The room PR 38 made: a family that owns dense replicated parameters
    (``reference/wide_deep.py``: the ``DENSE`` protocol) gets a cell as ONE
    new file and manifest entries, under a mix that is there: the reference
    step is handed the program's dense arrays, each comes back in
    ``compared`` beside its limit, the step's matmuls are counted, and the
    rehearsal ends with ``steps_match_reference`` true."""
    root, before = _copy_of_the_tree(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs", "made_up_wide_deep.json"), "w") as f:
        json.dump({
            "source": "https://example.org/made-up", "family": "wide_deep",
            "deployment": "made up", "model": "wide_deep", "optimizer": "ftrl",
            "emb_dim": 8, "hidden_dim": 64, "v_init_scale": 0.01, "max_fields": 40,
            "table_size_log2": 24, "batch_size": 16384, "max_nnz": 8,
            "hot_size_log2": 14, "hot_nnz": 32, "num_devices": 1,
            "assumed": {}, "reduced": {},
            "rehearsal": {
                "table_size_log2": 14, "batch_size": 512, "hot_size_log2": 8,
                "max_nnz": 40,
            },
        }, f)
    doc = _with_cell(
        DOC, "made_up_wide_deep", "made_up_wide_deep.train_packed", "replay_packed_zipf"
    )
    last = _rehearse(root, doc, "made_up_wide_deep.train_packed")
    assert last["checks"]["steps_match_reference"] is True
    compared = last["compared"]
    for array in ("w1", "b1", "w2", "b2"):
        one = compared[f"dense_rel_err.{array}"]
        assert one["value"] <= one["limit"] == refcheck.DENSE_RTOL
        assert compared[f"dense_update_ulps.{array}"]["value"] > 0.0
    assert compared["dense_update_max"]["value"] > 0.0
    # its forward calls ``reference/wide_deep.py::relu``, so the share of
    # examples left out on a kink is on the line beside its limit
    share = compared["relu_tie_share"]
    assert share["value"] <= share["limit"] == refcheck.TIE_SHARE_MAX
    with open(os.path.join(root, ".bench_cache", "made_up_wide_deep.train_packed.last.json")) as f:
        last = json.load(f)["run"]
    costs = last["costs"]
    assert all(len(s["relu"]["threshold"]) == 1 for s in last["reference"]["steps"])
    assert all(s["relu"]["compiles"] == 0 for s in last["reference"]["steps"])
    assert costs["flops"] == 6.0 * 512 * (320 * 64 + 64)
    after = _digests(root)
    assert {k: after[k] for k in before} == before  # no existing file edited
    assert set(after) - set(before) == {"benchmarks/configs/made_up_wide_deep.json"}


def _names(subdir: str, ext: str) -> set:
    return {
        f[: -len(ext)] for f in os.listdir(os.path.join(manifest.BENCH_DIR, subdir))
        if f.endswith(ext)
    }


def test_no_file_waits_for_a_cell():
    """A reader, a mix or a configuration that no cell uses comes with the
    PR that lists its cell.  The one kind without a cell, ``train_text``, is
    code a data-only PR could not bring, and the first made-up cell above
    runs it.  (``reference/`` is not counted: a family's reference is code
    too, and ``test_reference.py`` holds each to the program's step.)"""
    assert _names("layer_metrics", ".py") == {m["name"] for m in DOC["per_layer"]}
    mixes = {w["traffic"] for w in DOC["workloads"]}
    assert _names("traffic", ".json") == mixes
    assert {f"benchmarks/configs/{n}.json" for n in _names("configs", ".json")} == {
        c["file"] for c in DOC["configs"]
    }
    kinds = {manifest.traffic(m)["kind"] for m in mixes}
    assert _names("drivers", ".py") == kinds | {"train_text"}


def test_no_result_without_a_tpu():
    """Here JAX is held to the CPU: the command must refuse, as it must on
    any machine without the chips the cell asks for."""
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         DOC["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no CPU fallback" in done.stderr

"""XF010–XF014 memory/sharding rules + the shapeflow symbolic
shape/dtype dataflow under them (docs/ANALYSIS.md): per-rule
positive/negative fixtures, symbolic-propagation units (call-edge and
Config-cap resolution, reshape(-1), scan carries), the
memory-budget.json round-trip incl. stale-entry failure, the
narrow_keys_i32 choke point, and the repo-tree-clean + tier-1 gate
acceptance — following the tests/test_analysis.py pattern.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from xflow_tpu.analysis import run_analysis
from xflow_tpu.analysis.core import PackageIndex

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MEM_RULES = ["XF010", "XF011", "XF012", "XF013", "XF014"]


def scan(tmp_path, files: dict[str, str], select=None):
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
    findings, suppressed = run_analysis([str(tmp_path)], select=select)
    return findings, suppressed


def flows(tmp_path, files: dict[str, str]):
    """The shapeflow transient map for a fixture tree."""
    from xflow_tpu.analysis.rules_memory import memory_context
    from xflow_tpu.analysis.shapeflow import shape_str

    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(source)
    mem = memory_context(PackageIndex([str(tmp_path)]))
    return {
        key: [(t.sf.rel, t.line, shape_str(t.shape), t.kind) for t in ts]
        for key, ts in mem.flows.items()
    }


# -- shapeflow units -------------------------------------------------------


def test_shapeflow_config_caps_and_state_seeds(tmp_path):
    """cfg.table_size resolves to the T symbol and the state pytree
    seed makes tables [T, D] — the foundation every rule stands on."""
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(state, batch, cfg):\n"
        "    t = state['tables']['w']['param']\n"
        "    g = jnp.zeros_like(t)\n"
        "    oh = jax.nn.one_hot(batch['slots'], cfg.max_fields)\n"
        "    return g, oh\n"
    )})
    shapes = {s for _, _, s, _ in out["mod.py::step"]}
    assert "[T, D]" in shapes


def test_shapeflow_interprocedural_call_edge(tmp_path):
    """Shapes flow through an in-package call edge: the callee's
    allocation is sized from the CALLER's arguments (Config cap +
    table row width)."""
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def helper(t, n):\n"
        "    return jnp.zeros((n, t.shape[1]))\n"
        "@jax.jit\n"
        "def step(state, cfg):\n"
        "    t = state['tables']['w']['param']\n"
        "    return helper(t, cfg.batch_size)\n"
    )})
    shapes = {s for _, _, s, _ in out["mod.py::step"]}
    assert "[B, D]" in shapes


def test_shapeflow_reshape_minus_one(tmp_path):
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(batch):\n"
        "    flat = batch['keys'].reshape(-1)\n"
        "    return jnp.zeros((flat.shape[0], 3))\n"
    )})
    shapes = {s for _, _, s, _ in out["mod.py::step"]}
    assert "[(B*K), 3]" in shapes


def test_shapeflow_scan_carry(tmp_path):
    """lax.scan bodies are analyzed with carry bound from the init —
    the _train_sequential shape (tables ride the carry)."""
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(state, batch):\n"
        "    def body(carry, x):\n"
        "        tabs, acc = carry\n"
        "        g = {n: jnp.zeros_like(t['param'])\n"
        "             for n, t in tabs.items()}\n"
        "        return (tabs, acc), None\n"
        "    return jax.lax.scan(body, (state['tables'], 0),\n"
        "                        batch['keys'])\n"
    )})
    shapes = {s for _, _, s, _ in out["mod.py::step"]}
    assert "[T, D]" in shapes


def test_shapeflow_same_line_allocs_both_counted(tmp_path):
    """Two distinct same-shape allocations on ONE source line must both
    count toward the XF014 upper bound (dedup is per column, not per
    line)."""
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(state):\n"
        "    t = state['tables']['w']['param']\n"
        "    a, b = jnp.zeros_like(t), jnp.zeros_like(t)\n"
        "    return a, b\n"
    )})
    table_allocs = [e for e in out["mod.py::step"] if e[2] == "[T, D]"]
    assert len(table_allocs) == 2


def test_shapeflow_gather_records_transient(tmp_path):
    out = flows(tmp_path, {"mod.py": (
        "import jax\n"
        "@jax.jit\n"
        "def step(state, batch):\n"
        "    return state['tables']['w']['param'][batch['keys']]\n"
    )})
    entries = out["mod.py::step"]
    assert ("mod.py", 4, "[B, K, D]", "gather") in entries


# -- XF010: full-table transients ------------------------------------------

_XF010_POSITIVE = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "@jax.jit\n"
    "def step(state, batch):\n"
    "    return {n: jnp.zeros_like(t['param'])\n"
    "            for n, t in state['tables'].items()}\n"
)


def test_xf010_zeros_like_table_in_jit_fires(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": _XF010_POSITIVE},
                       select=["XF010"])
    assert len(findings) == 1
    assert findings[0].rule == "XF010"
    assert "full-table" in findings[0].message
    assert "[T, D]" in findings[0].message


def test_xf010_one_hot_into_t_dim_fires(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        "import jax\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.train = jax.jit(self._impl)\n"
        "    def _impl(self, batch):\n"
        "        return jax.nn.one_hot(batch['keys'],\n"
        "                              self.cfg.table_size)\n"
    )}, select=["XF010"])
    assert len(findings) == 1
    assert "one-hot" in findings[0].message


def test_xf010_silent_on_head_scale_and_host_code(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(state, batch, cfg):\n"
        "    heads = {n: t['param'][:cfg.hot_size]\n"
        "             for n, t in state['tables'].items()}\n"
        "    g = {n: jnp.zeros_like(h) for n, h in heads.items()}\n"
        "    oh = jax.nn.one_hot(batch['slots'], cfg.max_fields)\n"
        "    return g, oh\n"
        "def host_init(state):\n"  # not jitted: allocation is fine
        "    return {n: jnp.zeros_like(t['param'])\n"
        "            for n, t in state['tables'].items()}\n"
    )}, select=["XF010"])
    assert findings == []


def test_xf010_pragma_suppresses(tmp_path):
    findings, suppressed = scan(tmp_path, {"mod.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(state, batch):\n"
        "    # dense-mode design buffer (xf: ignore[XF010])\n"
        "    return {n: jnp.zeros_like(t['param'])\n"
        "            for n, t in state['tables'].items()}\n"
    )}, select=["XF010"])
    assert findings == [] and len(suppressed) == 1


# -- XF011: dtype discipline -----------------------------------------------


def test_xf011_adhoc_key_astype_fires(tmp_path):
    findings, _ = scan(tmp_path, {"io/pack.py": (
        "import numpy as np\n"
        "def pack(keys):\n"
        "    return keys.astype(np.int32)\n"
    )}, select=["XF011"])
    assert len(findings) == 1
    assert "narrow_keys_i32" in findings[0].message


def test_xf011_np_int32_coercion_of_keys_fires(tmp_path):
    findings, _ = scan(tmp_path, {"io/pack.py": (
        "import numpy as np\n"
        "def pack(batch):\n"
        "    return np.int32(batch.hot_keys)\n"
    )}, select=["XF011"])
    assert len(findings) == 1
    assert "np.int32" in findings[0].message


def test_xf011_silent_on_helper_and_non_keys(tmp_path):
    findings, _ = scan(tmp_path, {"io/pack.py": (
        "import numpy as np\n"
        "def narrow_keys_i32(keys):\n"  # THE choke point itself
        "    return keys.astype(np.int32)\n"
        "def counts(rows):\n"  # not a key plane
        "    return rows.astype(np.int32)\n"
        "def widen(keys):\n"  # widening is fine
        "    return keys.astype(np.int64)\n"
        "def sentinel():\n"  # constant coercion is fine
        "    return np.int32(-1)\n"
    )}, select=["XF011"])
    assert findings == []


def test_xf011_float64_in_traced_fires_host_silent(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        "import jax\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return jnp.zeros((4,), dtype=np.float64)\n"
        "def host(x):\n"
        "    return np.zeros((4,), dtype=np.float64)\n"
    )}, select=["XF011"])
    assert len(findings) == 1
    assert findings[0].line == 6
    assert "float64" in findings[0].message


# -- XF012: sharding coverage ----------------------------------------------

_MESH_FIXTURE = 'DATA_AXIS = "data"\n'


def test_xf012_unsharded_device_put_in_hot_module_fires(tmp_path):
    findings, _ = scan(tmp_path, {
        "parallel/mesh.py": _MESH_FIXTURE,
        "parallel/put.py": (
            "import jax\n"
            "def stage(x):\n"
            "    return jax.device_put(x)\n"
        ),
    }, select=["XF012"])
    assert len(findings) == 1
    assert "without a sharding" in findings[0].message


def test_xf012_sharded_put_and_cold_module_silent(tmp_path):
    findings, _ = scan(tmp_path, {
        "parallel/mesh.py": _MESH_FIXTURE,
        "parallel/put.py": (
            "import jax\n"
            "from parallel.mesh import table_sharding\n"
            "def stage(x, mesh):\n"
            "    return jax.device_put(x, table_sharding(mesh))\n"
        ),
        "utils/ck.py": (  # cold module: restore-path puts are exempt
            "import jax\n"
            "def restore(x):\n"
            "    return jax.device_put(x)\n"
        ),
    }, select=["XF012"])
    assert findings == []


def test_xf012_adhoc_namedsharding_fires_mesh_module_silent(tmp_path):
    findings, _ = scan(tmp_path, {
        "parallel/mesh.py": (
            "from jax.sharding import Mesh, NamedSharding, "
            "PartitionSpec as P\n"
            'DATA_AXIS = "data"\n'
            "def table_sharding(mesh):\n"
            "    return NamedSharding(mesh, P(DATA_AXIS, None))\n"
        ),
        "serve/eng.py": (
            "from jax.sharding import NamedSharding, PartitionSpec\n"
            "def layout(mesh):\n"
            "    return NamedSharding(mesh, PartitionSpec('data'))\n"
        ),
    }, select=["XF012"])
    assert len(findings) == 1
    assert findings[0].path == "serve/eng.py"
    assert "outside parallel/mesh.py" in findings[0].message


def test_xf012_unknown_collective_axis_fires_declared_silent(tmp_path):
    findings, _ = scan(tmp_path, {
        "parallel/mesh.py": _MESH_FIXTURE,
        "parallel/coll.py": (
            "import jax\n"
            "def both(x):\n"
            "    good = jax.lax.psum(x, 'data')\n"
            "    bad = jax.lax.psum(x, 'model')\n"
            "    return good, bad\n"
        ),
    }, select=["XF012"])
    assert len(findings) == 1
    assert findings[0].line == 4
    assert "'model'" in findings[0].message


# -- XF013: donation safety ------------------------------------------------

_XF013_CLASS = (
    "import jax\n"
    "class S:\n"
    "    def __init__(self):\n"
    "        self.train = jax.jit(self._impl, donate_argnums=0)\n"
    "    def _impl(self, state, b):\n"
    "        return state\n"
)


def test_xf013_read_after_donation_fires(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        _XF013_CLASS
        + "    def run(self, state, b):\n"
        + "        out = self.train(state, b)\n"
        + "        return out, state['step']\n"
    )}, select=["XF013"])
    assert len(findings) == 1
    assert "donated" in findings[0].message
    assert findings[0].line == 9


def test_xf013_rebind_idiom_is_silent(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        _XF013_CLASS
        + "    def run(self, state, b):\n"
        + "        state = self.train(state, b)\n"
        + "        return state\n"
    )}, select=["XF013"])
    assert findings == []


def test_xf013_cross_file_receiver_call_fires(tmp_path):
    """The real call sites of a donate-bound jit live OUTSIDE the
    binding's file and go through arbitrary receivers
    (step.train(...)) — matched by attribute name package-wide."""
    findings, _ = scan(tmp_path, {
        "step.py": _XF013_CLASS,
        "trainer.py": (
            "def run(step, state, b):\n"
            "    out = step.train(state, b)\n"
            "    return out, state\n"
        ),
    }, select=["XF013"])
    assert len(findings) == 1
    assert findings[0].path == "trainer.py"
    assert "donated" in findings[0].message


def test_xf013_undonated_jit_is_silent(tmp_path):
    findings, _ = scan(tmp_path, {"mod.py": (
        "import jax\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self.predict = jax.jit(self._impl)\n"
        "    def _impl(self, state, b):\n"
        "        return state\n"
        "    def run(self, state, b):\n"
        "        out = self.predict(state, b)\n"
        "        return out, state\n"
    )}, select=["XF013"])
    assert findings == []


# -- XF014: transient budget -----------------------------------------------

_XF014_MOD = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "@jax.jit\n"
    "def step(state, batch):\n"
    "    # fixture design buffer (xf: ignore[XF010])\n"
    "    return {n: jnp.zeros_like(t['param'])\n"
    "            for n, t in state['tables'].items()}\n"
)

_GEOMETRY = {
    "T": 1 << 20, "B": 64, "K": 8, "Kh": 4, "H": 256, "S": 4,
    "families": {"lr": 1, "fm": 10},
}


def _budget_tree(budgets: dict) -> dict[str, str]:
    return {
        "mod.py": _XF014_MOD,
        "memory-budget.json": json.dumps(
            {"geometry": _GEOMETRY, "budgets": budgets}
        ),
    }


def test_xf014_within_budget_is_silent(tmp_path):
    # [T=2^20, D] f32: lr 4 MiB, fm 40 MiB
    findings, _ = scan(tmp_path, _budget_tree(
        {"mod.py::step": {"lr": 5 << 20, "fm": 41 << 20}}
    ), select=["XF014"])
    assert findings == []


def test_xf014_over_budget_fires_with_largest_site(tmp_path):
    findings, _ = scan(tmp_path, _budget_tree(
        {"mod.py::step": {"lr": 1 << 20, "fm": 41 << 20}}
    ), select=["XF014"])
    assert len(findings) == 1
    f = findings[0]
    assert "exceeds the committed budget" in f.message
    assert "'lr'" in f.message and "[T, D]" in f.message


def test_xf014_missing_entry_and_family_fire(tmp_path):
    findings, _ = scan(tmp_path, _budget_tree({}), select=["XF014"])
    assert len(findings) == 1
    assert "no memory-budget.json entry" in findings[0].message

    findings, _ = scan(tmp_path, _budget_tree(
        {"mod.py::step": {"lr": 5 << 20}}  # fm missing
    ), select=["XF014"])
    assert len(findings) == 1
    assert "no budget for model family 'fm'" in findings[0].message


def test_xf014_stale_entry_fails(tmp_path):
    """A budget entry matching no live jit must fail the run — it
    would silently grandfather a future regression under its key."""
    findings, _ = scan(tmp_path, _budget_tree({
        "mod.py::step": {"lr": 5 << 20, "fm": 41 << 20},
        "gone.py::old_step": {"lr": 1},
    }), select=["XF014"])
    assert len(findings) == 1
    assert "stale budget entry" in findings[0].message
    assert "gone.py::old_step" in findings[0].message


def test_xf014_stale_family_fires_comment_exempt(tmp_path):
    """A numeric budget value for a family the geometry no longer
    declares must fail (it would silently re-arm if the name ever
    returned); non-numeric fields (comments) are carried, not stale."""
    findings, _ = scan(tmp_path, _budget_tree({
        "mod.py::step": {
            "lr": 5 << 20, "fm": 41 << 20, "gone": 1,
            "comment": "per-entry note",
        },
    }), select=["XF014"])
    assert len(findings) == 1
    assert "stale budget family 'gone'" in findings[0].message


def test_xf014_no_budget_file_in_scope_is_silent(tmp_path):
    # fixture scans without a budget don't fire; the committed repo
    # file is enforced by scripts/check_memory.py instead
    findings, _ = scan(tmp_path, {"mod.py": _XF014_MOD},
                       select=["XF014"])
    assert findings == []


def test_budget_round_trip_validation(tmp_path):
    from xflow_tpu.analysis import load_budget

    path = tmp_path / "memory-budget.json"
    path.write_text(json.dumps({"geometry": _GEOMETRY, "budgets": {}}))
    doc = load_budget(str(path))
    assert doc["geometry"]["families"] == _GEOMETRY["families"]
    path.write_text(json.dumps({"budgets": {}}))
    with pytest.raises(ValueError, match="geometry"):
        load_budget(str(path))
    path.write_text(json.dumps({"geometry": {}, "budgets": {}}))
    with pytest.raises(ValueError, match="families"):
        load_budget(str(path))


# -- narrow_keys_i32 (the XF011 choke point) -------------------------------


def test_narrow_keys_i32_contract():
    from xflow_tpu.io.batch import narrow_keys_i32

    a = np.arange(8, dtype=np.int32)
    assert narrow_keys_i32(a) is a  # int32 passes through untouched
    wide = np.array([0, 2**20], dtype=np.int64)
    out = narrow_keys_i32(wide)
    assert out.dtype == np.int32 and out.tolist() == [0, 2**20]
    u64 = np.array([1, 5], dtype=np.uint64)
    assert narrow_keys_i32(u64).dtype == np.int32
    with pytest.raises(ValueError, match="never wrap"):
        narrow_keys_i32(np.array([2**40], dtype=np.uint64))
    with pytest.raises(ValueError, match="never wrap"):
        narrow_keys_i32(np.array([-(2**33)], dtype=np.int64))


def test_compact_wire_sentinel_ignores_masked_garbage():
    """Masked lanes may carry unreduced 64-bit garbage (external
    batches pad however they like) — only LIVE keys owe the int32
    range contract.  The sentinel coding zeroes masked lanes in the
    wide dtype BEFORE narrowing, then applies -1 in int32 space."""
    from xflow_tpu.io.batch import Batch
    from xflow_tpu.parallel.step import compact_wire_np

    def mk(mask):
        return Batch(
            keys=np.array([[1, 2**40]], dtype=np.int64),
            slots=np.zeros((1, 2), np.int32),
            vals=mask.copy(),
            mask=mask,
            labels=np.ones(1, np.float32),
            weights=np.ones(1, np.float32),
        )

    wire = compact_wire_np(mk(np.array([[1.0, 0.0]], np.float32)))
    assert wire["ckeys"].dtype == np.int32
    assert wire["ckeys"].tolist() == [[1, -1]]
    # the same garbage in a LIVE lane still rejects (never wraps)
    with pytest.raises(ValueError, match="never wrap"):
        compact_wire_np(mk(np.ones((1, 2), np.float32)))


# -- acceptance: repo tree, estimates, CLI wiring, tier-1 gate -------------


def _run_cli(args, cwd):
    return subprocess.run(
        [sys.executable, "-m", "xflow_tpu.analysis", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env={**os.environ, "PYTHONPATH": REPO},
    )


def test_repo_tree_is_clean_under_memory_rules():
    """The ISSUE 7 acceptance gate: the shipped tree passes XF010–XF014
    (justified pragmas + committed budget only)."""
    proc = _run_cli(
        ["xflow_tpu", "--select", ",".join(MEM_RULES)], cwd=REPO
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_repo_estimates_cover_every_family_within_budget():
    """XF014 reports a per-jit transient estimate at T=2^28 for every
    model family, and the justified step.py window-end path is within
    the committed budget."""
    from xflow_tpu.analysis import estimate_transients, load_budget

    doc = load_budget(os.path.join(REPO, "memory-budget.json"))
    assert doc["geometry"]["T"] == 1 << 28
    est = estimate_transients(
        PackageIndex([os.path.join(REPO, "xflow_tpu")]), doc
    )
    train_key = "parallel/step.py::TrainStep._train_impl"
    assert train_key in est
    # the budget geometry must cover exactly the REGISTERED families
    # (models/__init__.py): a new family registers once and the memory
    # gate covers it, or this asserts
    from xflow_tpu.models import model_names

    families = set(doc["geometry"]["families"])
    assert families == set(model_names())
    # jits that are in-place scatters of donated state have NO sized
    # transients by design — a zero estimate is the correct answer
    # there, not a shapeflow bail-out (store/hot.py::_fill_impl writes
    # PROMOTE_CAP rows with .at[].set into the donated tier); the
    # serving engine's retrieval legs' dominant transient ([B, N]
    # scores over the runtime-sized item index) is unsized by the
    # static flow, so zero is legitimate there too; its predict leg
    # unpacks a request buffer of a few KB and hands the planes to
    # TrainStep._predict_impl, whose transients are sized and budgeted
    # under that entry's own key
    zero_ok = {
        "store/hot.py::HotTier._fill_impl",
        "serve/engine.py::PredictEngine._topk_impl",
        "serve/engine.py::PredictEngine._item_embed_impl",
        "serve/engine.py::PredictEngine._predict_impl",
    }
    for key, fams in est.items():
        assert set(fams) == families
        for family, e in fams.items():
            budget = doc["budgets"][key][family]
            floor = 0 if key in zero_ok else 1
            assert floor <= e["bytes"] <= budget, (
                key, family, e["bytes"],
            )
    # the window-end [T, D] path is among the sized sites
    sites = est[train_key]["fm"]["sites"]
    assert any(
        s["shape"] == "[T, D]" and s["path"].endswith("parallel/step.py")
        for s in sites
    )
    # and the flagship-D scaling is visible: fm >> lr
    assert (
        est[train_key]["fm"]["bytes"] > 5 * est[train_key]["lr"]["bytes"]
    )


def test_new_rules_in_list_rules_and_select():
    proc = _run_cli(["--list-rules"], cwd=REPO)
    assert proc.returncode == 0
    for rule in MEM_RULES:
        assert rule in proc.stdout
    proc = _run_cli(["xflow_tpu", "--select", "XF010"], cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_memory_rules_ride_changed_only(tmp_path):
    """The pre-commit path (PR 6's --changed-only) scopes XF010 findings
    to changed files like every other rule."""
    def git(*args):
        proc = subprocess.run(
            ["git", *args], cwd=str(tmp_path),
            capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        return proc

    git("init", "-q", ".")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "clean.py").write_text("x = 1\n")
    git("add", "clean.py")
    git("commit", "-qm", "seed")
    (tmp_path / "fresh.py").write_text(_XF010_POSITIVE)
    proc = _run_cli(
        [str(tmp_path), "--select", "XF010", "--changed-only",
         "--format", "json"],
        cwd=str(tmp_path),
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["counts"]["new"] == 1
    assert doc["findings"][0]["path"] == "fresh.py"
    assert doc["findings"][0]["rule"] == "XF010"


def test_check_memory_script():
    """The tier-1 gate passes on the shipped tree — run exactly as CI
    does (same pattern as check_analysis/check_concurrency)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_memory.py")],
        capture_output=True,
        text=True,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # the report prints an estimate line per jit per family
    assert "TrainStep._train_impl [lr]" in proc.stdout
    assert "TrainStep._train_impl [wide_deep]" in proc.stdout
    assert "budget" in proc.stdout

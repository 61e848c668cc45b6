"""The plain references, and the check that holds the system to them."""

import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import control
from benchmarks.harness import refcheck
from benchmarks.reference import dcn, ffm, fm, ftrl, lr, mvm, wide_deep

HYPER = {"alpha": 5e-2, "beta": 1.0, "lambda1": 5e-5, "lambda2": 10.0}


def test_ftrl_update_is_the_recurrence_of_ftrl_h():
    """ftrl.h:58-74, one key at a time, in Python floats."""
    rng = np.random.default_rng(0)
    w, n, z, g = (rng.normal(0, 1, 50) for _ in range(4))
    n = n * n
    z[:5] = 1e-5  # inside the L1 ball after a tiny push
    g[:5] = 1e-6
    got = ftrl.ftrl_update(
        {"param": jnp.asarray(w, jnp.float32), "n": jnp.asarray(n, jnp.float32),
         "z": jnp.asarray(z, jnp.float32)},
        jnp.asarray(g, jnp.float32), HYPER,
    )
    for i in range(50):
        n1 = n[i] + g[i] ** 2
        z1 = z[i] + g[i] - (np.sqrt(n1) - np.sqrt(n[i])) / HYPER["alpha"] * w[i]
        w1 = 0.0 if abs(z1) <= HYPER["lambda1"] else (
            (np.sign(z1) * HYPER["lambda1"] - z1)
            / ((HYPER["beta"] + np.sqrt(n1)) / HYPER["alpha"] + HYPER["lambda2"])
        )
        assert float(got["n"][i]) == pytest.approx(n1, rel=1e-5)
        assert float(got["z"][i]) == pytest.approx(z1, rel=1e-5, abs=1e-7)
        assert float(got["param"][i]) == pytest.approx(w1, rel=1e-4, abs=1e-7)
    assert float(got["param"][0]) == 0.0


def test_an_entry_no_gradient_has_reached_keeps_its_drawn_value():
    row = {"param": jnp.asarray([0.3]), "n": jnp.zeros(1), "z": jnp.zeros(1)}
    assert float(ftrl.ftrl_update(row, jnp.zeros(1), HYPER)["param"][0]) == pytest.approx(0.3)


def test_fm_forward_has_no_half_and_its_backward_is_of_the_halved_form():
    rng = np.random.default_rng(1)
    rows = {
        "w": jnp.asarray(rng.normal(0, 1, (4, 6, 1)), jnp.float32),
        "v": jnp.asarray(rng.normal(0, 1, (4, 6, fm.V_DIM)), jnp.float32),
    }
    x = jnp.asarray(rng.integers(0, 2, (4, 6)), jnp.float32)
    w, v, xs = (np.asarray(a, np.float64) for a in (rows["w"], rows["v"], x))
    pairs = np.zeros(4)
    for b in range(4):
        for i in range(6):
            for j in range(6):
                if i != j:
                    pairs[b] += (v[b, i] * v[b, j]).sum() * xs[b, i] * xs[b, j]
    # sum over ORDERED pairs = (sum)^2 - sum of squares: twice the usual FM term
    want = (w[..., 0] * xs).sum(1) + pairs
    assert np.allclose(fm.logit(rows, x), want, rtol=1e-4, atol=1e-4)

    def halved(rows_):
        vx = rows_["v"] * x[..., None]
        pair = jnp.sum(vx, 1) ** 2 - jnp.sum(vx * vx, 1)
        return jnp.sum(jnp.sum(rows_["w"][..., 0] * x, -1) + 0.5 * jnp.sum(pair, -1))

    auto = jax.grad(halved)(rows)
    explicit = fm.grad_logit(rows, x)
    assert np.allclose(explicit["w"], auto["w"], atol=1e-5)
    assert np.allclose(explicit["v"], auto["v"], rtol=1e-4, atol=1e-5)


def test_mvm_logit_is_the_product_over_fields_of_one_plus_the_field_sums():
    """Nested loops in float64: a field's sum, one plus it, the product over
    the fields, minus one, summed over the factors.  Entries whose field is
    outside [0, F) count for nothing, an empty field for a factor 1."""
    rng = np.random.default_rng(2)
    b, k, f = 5, 9, 4
    v = rng.normal(0, 0.3, (b, k, mvm.V_DIM))
    x = rng.integers(0, 2, (b, k)).astype(np.float64)
    slots = rng.integers(-1, f + 2, (b, k))
    slots[0] = np.where(slots[0] == 2, 0, slots[0])  # a row with an empty field
    want = np.zeros(b)
    for r in range(b):
        for d in range(mvm.V_DIM):
            prod = 1.0
            for field in range(f):
                prod *= 1.0 + sum(
                    v[r, i, d] * x[r, i] for i in range(k) if slots[r, i] == field
                )
            want[r] += prod - 1.0
    got = mvm.logit(
        {"v": jnp.asarray(v, jnp.float32)}, jnp.asarray(x, jnp.float32),
        jnp.asarray(slots, jnp.int32), f,
    )
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_mvm_backward_is_the_gradient_of_the_uncentred_forward():
    """The explicit gradient against autodiff of sum_d prod_f (1 + s_fd); the
    guard: where an entry's own factor is 0 the reference (mvm_worker.cc:156)
    gives 0, not the product of the other factors."""
    rng = np.random.default_rng(3)
    b, k, f = 6, 8, 3
    rows = {"v": jnp.asarray(rng.normal(0, 0.3, (b, k, mvm.V_DIM)), jnp.float32)}
    x = jnp.asarray(rng.integers(0, 2, (b, k)), jnp.float32)
    slots = jnp.asarray(rng.integers(-1, f + 1, (b, k)), jnp.int32)

    def uncentred(rows_):
        return jnp.sum(mvm.logit(rows_, x, slots, f) + mvm.V_DIM)

    auto = jax.grad(uncentred)(rows)["v"]
    explicit = mvm.grad_logit(rows, x, slots, f)["v"]
    assert np.allclose(explicit, auto, rtol=1e-4, atol=1e-6)
    outside = np.asarray((slots < 0) | (slots >= f))
    assert outside.any() and not np.asarray(explicit)[outside].any()

    # one entry alone in its field with v = -1: its factor is exactly 0
    lone = {"v": jnp.zeros((1, 2, mvm.V_DIM)).at[0, 0].set(-1.0).at[0, 1].set(0.5)}
    g = mvm.grad_logit(lone, jnp.ones((1, 2)), jnp.asarray([[0, 1]], jnp.int32), 2)["v"]
    assert not np.asarray(g[0, 0]).any()  # guarded: 0, where autodiff says 1.5
    assert np.allclose(g[0, 1], 0.0)  # the product holds the zero factor


def test_a_family_that_reads_no_fields_is_compiled_without_them():
    """``train_step`` hands the field ids only to a family that declares
    ``USES_FIELDS``: LR's program is the same text with and without them."""
    rng = np.random.default_rng(4)
    rows = {"w": {a: jnp.zeros((16, 1)) for a in ("param", "n", "z")}}
    idx = jnp.asarray(rng.integers(0, 16, (8, 3)), jnp.int32)
    x, ones = jnp.ones((8, 3)), jnp.ones(8)
    hyper = tuple(HYPER.items())
    plain = ftrl.train_step.lower(lr, rows, idx, x, ones, ones, hyper)
    handed = ftrl.train_step.lower(lr, rows, idx, x, ones, ones, hyper, idx, 7)
    assert handed.as_text() == plain.as_text()
    assert not hasattr(lr, "USES_FIELDS") and not hasattr(fm, "USES_FIELDS")
    # nor dense parameters and their rate, where it declares none (PR 38)
    dense = {"w1": jnp.ones((3, 2))}
    handed = ftrl.train_step.lower(lr, rows, idx, x, ones, ones, hyper, idx, 7, dense, 0.5)
    assert handed.as_text() == plain.as_text()
    assert not any(hasattr(f, "DENSE") for f in (lr, fm, mvm, ffm))


# ``reference/ftrl.py::train_step`` on the inputs below, as PR 37's tree (the
# last before the protocol learned of dense parameters) computes it on the CPU:
# the logloss, and sha256 over the bytes of every row array it returns
AS_BEFORE_DENSE_PARAMETERS = {
    "lr": ("0x1.626b9c0000000p-1", "a5f8acdd947a90ed"),
    "fm": ("0x1.6bc79a0000000p-1", "bb3bbebaf31816cb"),
    "mvm": ("0x1.118fd40000000p-1", "0915c9c6ff3033af"),
    "ffm": ("0x1.5917880000000p-1", "0c24f6597a4ef116"),
}


@pytest.mark.parametrize("family", [lr, fm, mvm, ffm], ids=lambda f: f.__name__.split(".")[-1])
def test_a_family_without_dense_parameters_gets_the_step_it_always_had(family):
    """The four families whose parameters are all table rows: on fixed inputs
    the reference step returns, bit for bit, what it returned before the
    protocol learned of dense parameters, and ``{}`` for them.  (A change of
    the reference that is meant to move these numbers moves the pins with
    it.)"""
    rng = np.random.default_rng(7)
    rows = {
        t: {a: jnp.asarray(rng.normal(0, 0.1, (16, d)), jnp.float32) for a in ("param", "n", "z")}
        for t, d in family.TABLES.items()
    }
    rows = {t: {**r, "n": jnp.abs(r["n"])} for t, r in rows.items()}
    idx = jnp.asarray(rng.integers(0, 16, (8, 3)), jnp.int32)
    slots = jnp.asarray(rng.integers(0, 40, (8, 3)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 2, 8), jnp.float32)
    ll, new, dense = ftrl.train_step(
        family, rows, idx, jnp.ones((8, 3)), labels, jnp.ones(8),
        tuple(HYPER.items()), slots, 40,
    )
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(new):
        digest.update(np.asarray(leaf).tobytes())
    name = family.__name__.split(".")[-1]
    assert (float(ll).hex(), digest.hexdigest()[:16]) == AS_BEFORE_DENSE_PARAMETERS[name]
    assert dense == {}


MAX_FIELDS = 4  # a dozen entries a row over four fields: every field sum has terms
# How far the first step's logloss may lie from ln 2.  Tables that start at
# zero give a logit of 0 (lr), or of products of two drawn v ~ 1e-2 (fm).
# MVM's logit is of first order in its drawn rows: ~12 entries x 10 factors
# of N(0, 1e-2) give it a deviation of 0.11 and each row's loss one of half
# that, so the mean over 59 real rows has a deviation of 7e-3: three of them
# (read: 0.6993 at hot_log2 0, 0.7034 at 5).
# The deep families: a tower of field sums of N(0, 1e-2) through He-drawn
# layers, a logit of ~1e-2 (read: 0.6937 wide_deep, 0.6925 dcn).
FIRST_LOGLOSS_BAND = {"lr": 2e-3, "fm": 2e-3, "mvm": 2.2e-2, "wide_deep": 2e-3, "dcn": 2e-3}
FAMILIES = [("lr", lr), ("fm", fm), ("mvm", mvm), ("wide_deep", wide_deep), ("dcn", dcn)]
DENSE_FAMILIES = [("wide_deep", wide_deep), ("dcn", dcn)]


def _system(model: str, hot_log2: int, **fields):
    from xflow_tpu.config import Config
    from xflow_tpu.io.batch import make_batch
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    cfg = Config(
        model=model, optimizer="ftrl", table_size_log2=12, batch_size=64,
        max_nnz=6, hot_size_log2=hot_log2, hot_nnz=6, num_devices=1, seed=3,
        max_fields=MAX_FIELDS, **fields,
    )
    mesh = make_mesh(1)
    mdl, opt = make_model(cfg), make_optimizer(cfg)
    system = types.SimpleNamespace(  # what the check uses of a Trainer
        step=TrainStep(mdl, opt, cfg, mesh), state=init_state(mdl, opt, cfg, mesh)
    )
    rng = np.random.default_rng(5)
    k = cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
    batches = []
    for _ in range(3):
        keys = rng.integers(0, cfg.table_size, (64, k))
        keys = np.where(rng.random(keys.shape) < 0.5, rng.integers(0, 40, keys.shape), keys)
        mask = (rng.random(keys.shape) < 0.7).astype(np.float32)
        # field ids in [0, MAX_FIELDS), one in ten outside it, on both sides
        slots = rng.integers(0, MAX_FIELDS, keys.shape)
        outside = rng.choice([-1, MAX_FIELDS, MAX_FIELDS + 3], keys.shape)
        slots = np.where(rng.random(keys.shape) < 0.1, outside, slots)
        weights = np.ones(64, np.float32)
        weights[-5:] = 0.0  # padding examples
        batches.append(make_batch(
            keys.astype(np.int32), slots.astype(np.int32), mask.copy(),
            mask, rng.integers(0, 2, 64).astype(np.float32), weights,
            cfg.hot_size, cfg.hot_nnz,
        ))
    return system, batches, cfg


@pytest.mark.parametrize("model, family", FAMILIES)
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_system_step_agrees_with_the_reference(model, family, hot_log2):
    """xflow_tpu's train step — wire, hot/cold split, dense FTRL pass, and
    for the deep families the autodiff backward and the plain SGD of their
    dense parameters — against the plain reference, three steps running (the
    second and third from a state that is no longer the drawn one)."""
    system, batches, cfg = _system(model, hot_log2)
    got = refcheck.check_train_steps(system, family, batches, cfg)
    assert got["ok"], got
    assert all(s["touched_rows"] > 100 for s in got["steps"])
    assert got["steps"][0]["logloss"] == pytest.approx(np.log(2), abs=FIRST_LOGLOSS_BAND[model])
    # dense numbers in the record and in ``compared`` for a family that owns
    # dense parameters, and for no other
    owns = hasattr(family, "DENSE")
    assert all(("dense" in s) == owns for s in got["steps"])
    compared = refcheck.dense_compared(got["steps"])
    if not owns:
        assert compared == {}
        return
    arrays = set(system.state["dense"])
    assert all(set(s["dense"]) == arrays for s in got["steps"])
    assert {k for k in compared if k.startswith("dense_rel_err.")} == {
        f"dense_rel_err.{a}" for a in arrays
    }
    assert all(v["value"] <= v["limit"] == refcheck.DENSE_RTOL
               for k, v in compared.items() if k.startswith("dense_rel_err."))
    # every array says how many float32 steps of itself its best entry
    # moved, a reading beside no limit: the biases start at 0 and move by
    # 1e6 or more, so their number holds their precision
    assert {k for k in compared if k.startswith("dense_update_ulps.")} == {
        f"dense_update_ulps.{a}" for a in arrays
    }
    assert all(compared[f"dense_update_ulps.{a}"]["value"] > 1 / refcheck.DENSE_RTOL
               for a in arrays if a.startswith(("b", "cross_b")))
    assert compared["dense_update_ulps.w1"] == {
        "value": pytest.approx(compared["dense_update_ulps.w1"]["value"]), "op": ">=", "limit": 0.0,
    }
    assert compared["dense_update_max"]["value"] > 0.0


def _with_updates_scaled(monkeypatch, array: str, factor: float):
    """The reference step with ONE dense array's update scaled."""
    real = ftrl.train_step

    def scaled(*args):
        ll, rows, dense = real(*args)
        before = args[-2][array]
        return ll, rows, {**dense, array: before + factor * (dense[array] - before)}

    monkeypatch.setattr(refcheck.ftrl, "train_step", scaled)


@pytest.mark.parametrize("model, family, array", [
    ("wide_deep", wide_deep, "b1"), ("wide_deep", wide_deep, "b2"),
    ("dcn", dcn, "b1"), ("dcn", dcn, "b_out"), ("dcn", dcn, "cross_b"),
])
def test_a_dense_update_one_percent_off_fails_by_that_array_alone(
    model, family, array, monkeypatch
):
    """A reference whose update of ONE dense array is 1.01 times what it
    should be: every step fails, by that array's number and by no other."""
    system, batches, cfg = _system(model, 5)
    _with_updates_scaled(monkeypatch, array, 1.01)
    got = refcheck.check_train_steps(system, family, batches, cfg)
    assert not got["ok"] and not any(s["ok"] for s in got["steps"])
    for step in got["steps"]:
        assert step["logloss_err"] <= refcheck.LOGLOSS_ATOL
        assert max(step["rows_rel_err"].values()) <= refcheck.ROWS_RTOL
        off = {a for a, d in step["dense"].items() if d["rel_err"] > refcheck.DENSE_RTOL}
        assert off == {array}
        assert step["dense"][array]["rel_err"] == pytest.approx(0.01 / 1.01, rel=1e-2)


def _with_program_updates_scaled(system, array: str, factor: float):
    """The program's step with ONE dense array's update scaled: 0 leaves the
    array as it was, 2 moves it twice."""
    real = system.step.train

    def train(state, arrays):
        before = jnp.array(state["dense"][array])  # the step donates its state
        new, metrics = real(state, arrays)
        moved = before + factor * (new["dense"][array] - before)
        return {**new, "dense": {**new["dense"], array: moved}}, metrics

    system.step.train = train


# At a cell's size (39 fields, B = 16384, ``sgd_lr`` 1e-3) the first-layer
# matrix moves by 0.25-3 float32 steps of its LARGEST entry, DCN's ``cross_w``
# by under half of one (PERF.md section 2).  The toy step's mean is over 59
# rows, so the same regime is a rate of 5e-5 here.
MEASURED_SIZE = {"sgd_lr": 5e-5}


def _largest_steps(system, array: str, step: dict) -> float:
    """An array's largest update in float32 steps of its largest entry."""
    largest = np.float32(np.max(np.abs(np.asarray(system.state["dense"][array]))))
    return step["dense"][array]["update"] / float(np.spacing(largest))


@pytest.mark.parametrize("model, family, array, factor, reads", [
    ("wide_deep", wide_deep, "w1", 0.0, 1.0), ("wide_deep", wide_deep, "w1", 2.0, 0.1),
    ("dcn", dcn, "w1", 0.0, 1.0), ("dcn", dcn, "w1", 2.0, 0.1),
    ("dcn", dcn, "cross_w", 0.0, 1.0),
])
def test_an_array_left_as_it_was_or_moved_twice_fails_at_a_cells_update_size(
    model, family, array, factor, reads
):
    """The program leaves ONE dense array where it was (it then reads exactly
    1, with no rounding allowance) or moves it twice, where the array's
    largest entries move by about one float32 step of themselves or less, as
    at a cell's size: the step fails by that array's number alone, because
    entries near 0 move by many steps of THEIR own.  (``cross_w`` moved twice
    is not among the cases: where no entry moves by two steps of itself the
    doubled update rounds to what a sound step leaves.)"""
    system, batches, cfg = _system(model, 5, **MEASURED_SIZE)
    _with_program_updates_scaled(system, array, factor)
    got = refcheck.check_train_steps(system, family, batches[:1], cfg)
    step = got["steps"][0]
    assert _largest_steps(system, array, step) < 1.5
    assert not got["ok"]
    off = {a for a, d in step["dense"].items() if d["rel_err"] > refcheck.DENSE_RTOL}
    assert off == {array}
    assert reads <= step["dense"][array]["rel_err"] <= 1.0
    assert step["logloss_err"] <= refcheck.LOGLOSS_ATOL
    assert max(step["rows_rel_err"].values()) <= refcheck.ROWS_RTOL


def test_a_weight_array_is_held_to_its_update_not_always_to_its_precision(monkeypatch):
    """At a cell's update size no entry of ``w1`` moves by 1e4 steps of
    itself, so a reference update a thousandth off is inside every entry's
    rounding: the step passes, and ``dense_update_ulps`` says why.  At the
    toy's own rate the same array moves by over 1e3 steps and the same
    thousandth is seen."""
    for rate, seen in ((MEASURED_SIZE["sgd_lr"], False), (1e-3, True)):
        system, batches, cfg = _system("wide_deep", 5, sgd_lr=rate)
        _with_updates_scaled(monkeypatch, "w1", 1.001)
        got = refcheck.check_train_steps(system, wide_deep, batches[:1], cfg)
        monkeypatch.undo()
        compared = refcheck.dense_compared(got["steps"])
        assert got["ok"] is not seen
        assert (compared["dense_update_ulps.w1"]["value"] > 1e3) is seen
        off = {k for k, v in compared.items()
               if k.startswith("dense_rel_err") and v["value"] > v["limit"]}
        assert off == ({"dense_rel_err.w1"} if seen else set())


def test_a_step_that_moves_no_dense_array_proves_nothing_of_them():
    """Reference updates of exactly 0 in EVERY dense array (here: a rate of 0
    on both sides, so the program agrees) fail the step, as rows that did not
    move do."""
    system, batches, cfg = _system("wide_deep", 5, sgd_lr=0.0)
    got = refcheck.check_train_steps(system, wide_deep, batches, cfg)
    assert not got["ok"]
    assert refcheck.dense_compared(got["steps"])["dense_update_max"]["value"] == 0.0
    assert all(max(s["rows_rel_err"].values()) <= refcheck.ROWS_RTOL for s in got["steps"])


def test_one_dense_array_may_stand_still_in_a_sound_step():
    """DCN's ``cross_w`` moves by under half a float32 step of its entries at
    a cell's size, so in float32 it can stand bit for bit where it was, in
    the program and in the reference alike (on the chip: one seed in twelve).
    That step is sound; one in which the program then moves the array by two
    steps is not."""
    b = {"cross_w": np.full(4, 0.25, np.float32), "b1": np.zeros(4, np.float32)}
    w = {"cross_w": b["cross_w"].copy(), "b1": np.full(4, -1e-5, np.float32)}
    sound = refcheck.dense_errors(b, w, w)
    assert sound["cross_w"] == {"rel_err": 0.0, "update": 0.0, "update_ulps": 0.0}
    steps = [{"dense": sound}]
    compared = refcheck.dense_compared(steps)
    assert compared["dense_rel_err.cross_w"]["value"] == 0.0
    assert compared["dense_update_ulps.cross_w"]["value"] == 0.0
    assert compared["dense_update_max"]["value"] == pytest.approx(1e-5)
    one = np.spacing(np.float32(0.25))
    for steps_off, err in ((1, 0.0), (2, 1.0)):  # one step is rounding's
        g = {**w, "cross_w": w["cross_w"] + np.float32(steps_off * one)}
        assert refcheck.dense_errors(b, g, w)["cross_w"]["rel_err"] == err


def test_the_rounding_allowance_is_each_entrys_own_step():
    """A matrix whose large entry moves by half a float32 step of itself and
    whose entry near 0 moves by thousands of its own: an error of one step of
    the LARGE entry is rounding there and a fault in the small one; an array
    left bit for bit as it was reads exactly 1, allowance or none."""
    big = np.float32(0.25)
    one = np.spacing(big)
    b = {"w1": np.array([big, 1e-6], np.float32)}
    w = {"w1": b["w1"] - np.array([one, 0.5 * one], np.float32)}
    assert refcheck.dense_errors(b, w, w)["w1"]["rel_err"] == 0.0
    assert refcheck.dense_errors(b, w, w)["w1"]["update_ulps"] > 1e3
    g = {"w1": w["w1"] + np.array([one, 0.0], np.float32)}
    assert refcheck.dense_errors(b, g, w)["w1"]["rel_err"] == 0.0
    g = {"w1": w["w1"] + np.array([0.0, one], np.float32)}
    assert refcheck.dense_errors(b, g, w)["w1"]["rel_err"] == pytest.approx(1.0, rel=1e-3)
    assert refcheck.dense_errors(b, b, w)["w1"]["rel_err"] == 1.0
    only_big = {"w1": b["w1"] - np.array([one, 0.0], np.float32)}
    assert refcheck.dense_errors(b, b, only_big)["w1"]["rel_err"] == 1.0  # no allowance


class _Again:
    """A family under a second identity: ``train_step`` compiles it anew."""

    def __init__(self, family):
        self.family = family

    def __getattr__(self, name):
        return getattr(self.family, name)


def test_the_dense_step_block_by_block_is_the_step_in_one_block(monkeypatch):
    """The reference's dense step over four blocks of 16 examples (what it
    does at a cell's size, ``DENSE_BLOCK`` examples at a time) against the
    same step in one block of 64: the logloss, every row and every dense
    array agree to float32's rounding of a sum taken in another order."""
    rng = np.random.default_rng(8)
    fields, p = 4, 4 * dcn.EMB_DIM
    rows = {
        t: {"param": jnp.asarray(rng.normal(0, 0.1, (32, d)), jnp.float32),
            "n": jnp.asarray(rng.uniform(0, 1, (32, d)), jnp.float32),
            "z": jnp.asarray(rng.normal(0, 1, (32, d)), jnp.float32)}
        for t, d in dcn.TABLES.items()
    }
    shapes = {"cross_w": (2, p), "cross_b": (2, p), "w1": (p, 8), "b1": (8,),
              "w_out": (p + 8, 1), "b_out": (1,)}
    dense = {k: jnp.asarray(rng.normal(0, 0.3, v), jnp.float32) for k, v in shapes.items()}
    idx = jnp.asarray(rng.integers(0, 32, (64, 6)), jnp.int32)
    slots = jnp.asarray(rng.integers(-1, fields + 1, (64, 6)), jnp.int32)
    x = jnp.asarray(rng.integers(0, 2, (64, 6)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, 64), jnp.float32)
    weights = jnp.ones(64).at[-5:].set(0.0)
    args = (rows, idx, x, labels, weights, tuple(HYPER.items()), slots, fields, dense, 0.1)
    whole = ftrl.train_step(dcn, *args)
    monkeypatch.setattr(ftrl, "DENSE_BLOCK", 16)
    blocked = ftrl.train_step(_Again(dcn), *args)
    for one, four in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        np.testing.assert_allclose(four, one, rtol=2e-5, atol=1e-7)
    assert all(
        float(jnp.max(jnp.abs(whole[2][k] - dense[k]))) > 1e-4 for k in dense
    )  # every dense array moved


def test_a_family_and_a_program_must_agree_on_what_the_parameters_are():
    system, batches, cfg = _system("wide_deep", 5)
    with pytest.raises(ValueError, match="dense parameters"):
        refcheck.check_train_steps(system, lr, batches, cfg)
    system, batches, cfg = _system("wide_deep", 5, emb_dim=16)
    with pytest.raises(ValueError, match="'emb': 16"):
        refcheck.check_train_steps(system, wide_deep, batches, cfg)


def test_the_check_can_fail():
    """A reference at another learning rate (0.1 % off) is outside the
    tolerance: the check is tight enough to see it."""
    system, batches, cfg = _system("lr", 5)
    off = cfg.replace(alpha=cfg.alpha * 1.001)
    got = refcheck.check_train_steps(system, lr, batches, off)
    assert not got["ok"]
    assert max(got["steps"][-1]["rows_rel_err"].values()) > refcheck.ROWS_RTOL


def test_the_check_can_fail_on_field_ids(monkeypatch):
    """The reference handed the batch's field ids shifted by one place within
    each row, and all else as the loader steered it, is outside the
    tolerance: which field an entry belongs to is part of what is checked."""
    system, batches, cfg = _system("mvm", 5)
    entries = refcheck.entries

    def shifted(batch):
        keys, x, slots = entries(batch)
        return keys, x, np.roll(slots, 1, axis=1)

    monkeypatch.setattr(refcheck, "entries", shifted)
    got = refcheck.check_train_steps(system, mvm, batches, cfg)
    assert not got["ok"]
    assert max(got["steps"][0]["rows_rel_err"].values()) > 100 * refcheck.ROWS_RTOL


def test_entries_carry_the_field_ids_hot_section_first():
    _, batches, cfg = _system("mvm", 5)
    keys, x, slots = refcheck.entries(batches[0])
    assert keys.shape == x.shape == slots.shape == (64, cfg.hot_nnz + cfg.max_nnz)
    assert slots.dtype == np.int32
    assert (slots[:, : cfg.hot_nnz] == batches[0].hot_slots).all()
    assert (slots[:, cfg.hot_nnz :] == batches[0].slots).all()
    live = slots[x != 0]
    assert ((live < 0) | (live >= cfg.max_fields)).any()  # some outside, kept as drawn


@pytest.mark.parametrize("model, family", FAMILIES)
@pytest.mark.parametrize("hot_log2", [0, 5])
def test_the_control_is_outside_the_tolerance(model, family, hot_log2):
    """The control (``benchmarks/control.py``: the reference with its gathered
    rows rounded to bfloat16, which is what a default-precision float32
    contraction on the TPU makes of its operands) in the reference's place,
    from a state three steps old: not ``ok``, and the limit on the rows lies
    between the sound steps' worst reading and the control's."""
    system, batches, cfg = _system(model, hot_log2)
    sound = refcheck.check_train_steps(system, family, batches, cfg)
    got = refcheck.check_train_steps(system, control.InBfloat16(family), batches, cfg)

    def worst(check):
        return max(max(s["rows_rel_err"].values()) for s in check["steps"])

    assert sound["ok"] and not got["ok"]
    # the control's forward hands out its ReLU arguments as the family's does
    assert all(("relu" in s) == hasattr(family, "DENSE") for s in got["steps"])
    # toy size: sound <= 4.0e-7 (mvm), the control >= 2.8e-6 (lr, hot 2^5)
    assert 2 * worst(sound) < refcheck.ROWS_RTOL < worst(got) / 2
    if hasattr(family, "DENSE"):
        # the control rounds the dense operands too: each bias, which float32
        # resolves, is outside the dense limit on its own (toy size: sound
        # <= 1e-7, the control >= 4e-4)
        def dense_worst(check, pick):
            return pick(
                max(s["dense"][a]["rel_err"] for s in check["steps"])
                for a in check["steps"][0]["dense"] if a.startswith(("b", "cross_b"))
            )

        assert 2 * dense_worst(sound, max) < refcheck.DENSE_RTOL < dense_worst(got, min) / 2


class _Nudged:
    """``wide_deep`` with the biases of the units ``nudged`` lowered by ``by``
    inside the forward: a reference that stands on the other side of a kink
    than the program does, as two sound orders of one sum leave them on the
    chip (a contraction of a thousand inputs or more) and never on the CPU."""

    TABLES, USES_FIELDS, DENSE = wide_deep.TABLES, True, True
    matmuls = staticmethod(wide_deep.matmuls)

    def __init__(self, nudged=(), by=0.0):
        self.nudged, self.by = tuple(nudged), by

    def arguments(self, rows, x, slots, num_fields, dense):
        t = wide_deep.tower(rows["emb"], x, slots, num_fields)
        down = jnp.zeros_like(dense["b1"]).at[np.asarray(self.nudged, int)].set(self.by)
        return t @ dense["w1"] + dense["b1"] - down

    def logit(self, rows, x, slots, num_fields, dense):
        wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
        h = wide_deep.relu(self.arguments(rows, x, slots, num_fields, dense))
        return wide + (h @ dense["w2"] + dense["b2"])[:, 0]


def _planted(count: int):
    """A toy ``wide_deep`` system whose first batch has ``count`` examples
    with one first-layer argument each (each in a unit of its own) planted,
    through ``b1``, one float32 step of the layer's largest argument above 0,
    and the family that sees those units' arguments two such steps lower: the
    program stands above the kink, the reference below it, by less than the
    threshold.  -> (system, batch, cfg, family, the step, the examples)."""
    system, batches, cfg = _system("wide_deep", 5)
    batch = batches[0]
    keys, x, slots = refcheck.entries(batch)
    gathered = {
        t: np.asarray(a["param"])[keys] for t, a in system.state["tables"].items()
    }
    dense = {k: np.asarray(v) for k, v in system.state["dense"].items()}
    plain = _Nudged()
    before = np.asarray(plain.arguments(gathered, x, slots, cfg.max_fields, dense))
    ulp = float(np.spacing(np.abs(before).max()))
    near = np.where(batch.weights[:, None] > 0, np.abs(before), np.inf)
    examples, units = [], []
    b1 = dense["b1"].copy()
    for _ in range(count):  # the smallest argument, each in an example and a unit of its own
        i, j = np.unravel_index(np.argmin(near), near.shape)
        b1[j] += np.float32(ulp) - before[i, j]
        near[i, :], near[:, j] = np.inf, np.inf
        examples.append(int(i))
        units.append(int(j))
    system.state["dense"]["b1"] = jnp.asarray(b1)
    after = np.asarray(plain.arguments(gathered, x, slots, cfg.max_fields, {**dense, "b1": b1}))
    assert float(np.spacing(np.abs(after).max())) == ulp
    assert all(0.5 * ulp < after[i, j] < 1.5 * ulp for i, j in zip(examples, units))
    return system, batch, cfg, _Nudged(units, np.float32(2 * ulp)), ulp, examples


def _over(step: dict) -> set:
    """The numbers of a step's record that are over their limits."""
    out = {f"dense_rel_err.{a}" for a, d in step["dense"].items()
           if d["rel_err"] > refcheck.DENSE_RTOL}
    out |= {f"rows_rel_err.{a}" for a, e in step["rows_rel_err"].items()
            if e > refcheck.ROWS_RTOL}
    return out | ({"logloss_err"} if step["logloss_err"] > refcheck.LOGLOSS_ATOL else set())


def test_an_example_on_a_relus_kink_is_left_out_of_the_step_on_both_sides(monkeypatch):
    """One unit of one example within rounding of 0, the program above and the
    reference below: the forward barely moves (the logloss agrees), the
    backward's gate for that example is 1 on one side and 0 on the other, and
    the comparison without the tie rule (the parent's: a factor of 0 ties
    nothing) reads a dense array and a row over their limits.  Under the rule
    the reference finds that example, and that one alone, from its own
    forward before the step; weight 0 on both sides, and every reading is
    under its limit."""
    system, batch, cfg, family, ulp, examples = _planted(1)
    monkeypatch.setattr(refcheck, "TIE_FACTOR", 0.0)
    got = refcheck.check_train_steps(system, family, [batch], cfg)
    step = got["steps"][0]
    assert not got["ok"] and step["relu"]["under"] == [0]
    assert {n.split(".")[0] for n in _over(step)} == {"dense_rel_err", "rows_rel_err"}
    assert max(d["rel_err"] for d in step["dense"].values()) > 100 * refcheck.DENSE_RTOL
    monkeypatch.undo()

    system, batch, cfg, family, ulp, examples = _planted(1)
    got = refcheck.check_train_steps(system, family, [batch], cfg)
    step = got["steps"][0]
    assert got["ok"] and _over(step) == set()
    real = int(batch.weights.sum())
    assert step["relu"] == {
        "share": 1 / real, "threshold": [refcheck.TIE_FACTOR * ulp], "under": [1],
    }
    assert refcheck.dense_compared(got["steps"])["relu_tie_share"] == {
        "value": 1 / real, "op": "<=", "limit": refcheck.TIE_SHARE_MAX,
    }
    # the batch with a weight set to 0 is the same planes to the program: the
    # step it takes is the compiled one (on the chip: the window's)
    import dataclasses

    fewer = dataclasses.replace(batch, weights=batch.weights * (np.arange(64) != examples[0]))
    shapes = [
        jax.tree.map(lambda a: (a.shape, a.dtype), system.step.put_batch(b))
        for b in (batch, fewer)
    ]
    assert shapes[0] == shapes[1]
    # which example: the reference's own margins say
    keys, x, slots = refcheck.entries(batch)
    rows = {t: {"param": jnp.asarray(np.asarray(a["param"]))}
            for t, a in _planted(1)[0].state["tables"].items()}
    margin, read = ftrl.relu_margins(
        family, rows, jnp.asarray(keys), jnp.asarray(x), jnp.asarray(slots),
        cfg.max_fields, _planted(1)[0].state["dense"],
    )
    tie, _ = refcheck.relu_ties(np.asarray(margin), np.asarray(read), batch.weights)
    assert np.flatnonzero(tie).tolist() == examples


def test_a_batch_cannot_hide_behind_its_ties():
    """Seven tie examples of 59 real ones are more than ``TIE_SHARE_MAX``
    allows: every other number is under its limit and the step still fails."""
    system, batch, cfg, family, _, examples = _planted(7)
    got = refcheck.check_train_steps(system, family, [batch], cfg)
    step = got["steps"][0]
    assert step["relu"]["under"] == [7] and _over(step) == set()
    assert step["relu"]["share"] == 7 / int(batch.weights.sum()) > refcheck.TIE_SHARE_MAX
    assert not got["ok"]
    share = refcheck.dense_compared(got["steps"])["relu_tie_share"]
    assert share["value"] > share["limit"]
    # a forward whose ReLUs are dead, every argument exactly 0, ties whole
    tie, read = refcheck.relu_ties(np.zeros((2, 8)), np.zeros(2), np.ones(8))
    assert tie.all() and read["share"] == 1.0


class _OwnRelu:
    """``wide_deep`` with a ReLU that is not ``wide_deep.relu``: the check
    sees no call (``reference/`` may hold no such family: the scan below)."""

    TABLES, USES_FIELDS, DENSE = wide_deep.TABLES, True, True

    @staticmethod
    def logit(rows, x, slots, num_fields, dense):
        wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
        t = wide_deep.tower(rows["emb"], x, slots, num_fields)
        h = jax.nn.relu(t @ dense["w1"] + dense["b1"])
        return wide + (h @ dense["w2"] + dense["b2"])[:, 0]


def test_a_forward_that_never_calls_relu_has_no_ties_and_no_share():
    """A dense family whose forward calls ``relu`` nowhere is stepped as it
    always was and its ``compared`` has the parent's keys; so has a family
    without dense parameters, which is never asked."""
    system, batches, cfg = _system("wide_deep", 5)
    got = refcheck.check_train_steps(system, _OwnRelu, batches, cfg)
    assert got["ok"] and not any("relu" in s for s in got["steps"])
    compared = refcheck.dense_compared(got["steps"])
    assert "relu_tie_share" not in compared
    assert {k.split(".")[0] for k in compared} == {
        "dense_rel_err", "dense_update_ulps", "dense_update_max",
    }
    system, batches, cfg = _system("mvm", 5)
    got = refcheck.check_train_steps(system, mvm, batches, cfg)
    assert got["ok"] and not any("relu" in s or "dense" in s for s in got["steps"])
    assert refcheck.dense_compared(got["steps"]) == {}


def test_relu_hands_out_its_arguments_only_while_looked_at():
    a = jnp.asarray([[1e-9, -2.0], [3.0, 0.0]])
    live = jnp.asarray([[False, True], [True, True]])
    with wide_deep.relu_arguments() as seen:
        out = wide_deep.relu(a, live)
        wide_deep.relu(a)
    wide_deep.relu(a)  # nobody looks: nothing is kept
    assert len(seen) == 2
    np.testing.assert_array_equal(out, [[0.0, 0.0], [3.0, 0.0]])
    # an entry that does not reach the logit is no tie, however near 0
    np.testing.assert_array_equal(seen[0], [[np.inf, -2.0], [3.0, 0.0]])
    np.testing.assert_array_equal(seen[1], a)
    np.testing.assert_array_equal(jax.grad(lambda v: wide_deep.relu(v, live).sum())(a),
                                  [[0.0, 0.0], [1.0, 0.0]])


class _ReluUnderCheckpoint(_Nudged):
    """``wide_deep`` with its ReLU INSIDE a ``jax.checkpoint``: the argument
    cannot leave the trace it was made in."""

    def logit(self, rows, x, slots, num_fields, dense):
        hidden = jax.checkpoint(
            lambda r, v, f, d: wide_deep.relu(self.arguments(r, v, f, num_fields, d))
        )(rows, x, slots, dense)
        return (hidden @ dense["w2"] + dense["b2"])[:, 0]


def test_a_relu_inside_a_checkpoint_is_an_error_not_a_pass():
    system, batches, cfg = _system("wide_deep", 5)
    with pytest.raises(jax.errors.UnexpectedTracerError):
        refcheck.check_train_steps(system, _ReluUnderCheckpoint(), batches[:1], cfg)


def test_a_step_that_took_out_ties_may_compile_nothing():
    """With the run's meter the check counts the programs compiled by the
    steps whose batches lost their tie examples: a trainer that had compiled
    its step reads 0, one that meets the batch fresh fails by that number
    alone."""
    from benchmarks.harness import compiles

    meter = compiles.CompileMeter()
    system, batch, cfg, family, _, _ = _planted(1)
    got = refcheck.check_train_steps(system, family, [batch], cfg, meter)
    step = got["steps"][0]
    assert step["relu"]["compiles"] > 0 and _over(step) == set() and not got["ok"]
    compared = refcheck.dense_compared(got["steps"])
    assert compared["relu_step_compiles"]["value"] > compared["relu_step_compiles"]["limit"] == 0
    # (the second step meets the state as a step leaves it, the last new thing)
    refcheck.check_train_steps(system, family, [batch], cfg, meter)
    again = refcheck.check_train_steps(system, family, [batch], cfg, meter)
    assert again["steps"][0]["relu"]["compiles"] == 0
    assert refcheck.dense_compared(again["steps"])["relu_step_compiles"]["value"] == 0
    # without a meter nothing is counted and the line has no such key
    bare = refcheck.check_train_steps(system, family, [batch], cfg)
    assert "relu_step_compiles" not in refcheck.dense_compared(bare["steps"])


def test_every_relu_of_a_reference_family_is_the_one_the_check_sees():
    """``reference/*.py`` spells a ReLU nowhere but in ``wide_deep.relu``
    (no ``x.relu(...)`` of another module, no ``maximum`` or ``clip``
    against a literal 0, no ``where`` on a comparison with a literal 0 that
    picks a literal 0), and a bare ``relu`` is the one imported from there:
    a ReLU the check cannot see is an error, not a pass."""
    import ast
    import glob
    import os

    from benchmarks.harness import manifest

    def zero(node) -> bool:
        return isinstance(node, ast.Constant) and node.value == 0

    def spelt(call, ours: bool) -> bool:
        func = call.func
        what = func.attr if isinstance(func, ast.Attribute) else getattr(func, "id", "")
        return bool(
            (what in ("relu", "relu6", "leaky_relu")
             and (isinstance(func, ast.Attribute) or not ours))
            or (what in ("maximum", "clip") and any(map(zero, call.args)))
            or (what == "where" and len(call.args) == 3
                and isinstance(call.args[0], ast.Compare)
                and any(map(zero, call.args[0].comparators + [call.args[0].left]))
                and any(map(zero, call.args[1:])))
        )

    def calls(tree):
        return [n for n in ast.walk(tree) if isinstance(n, ast.Call)]

    found = []
    for path in sorted(glob.glob(os.path.join(manifest.BENCH_DIR, "reference", "*.py"))):
        name = os.path.basename(path)
        with open(path) as f:
            tree = ast.parse(f.read())
        ours = name == "wide_deep.py" or any(
            isinstance(n, ast.ImportFrom) and n.module == "benchmarks.reference.wide_deep"
            and any(a.name == "relu" and a.asname is None for a in n.names)
            for n in tree.body
        )
        inside = {
            id(c) for n in tree.body
            if name == "wide_deep.py" and isinstance(n, ast.FunctionDef) and n.name == "relu"
            for c in ast.walk(n)
        }
        found += [
            (name, c.lineno, ast.unparse(c)) for c in calls(tree)
            if spelt(c, ours) and id(c) not in inside
        ]
    assert found == []
    # the scan sees what it is for, and lets the shared one through
    for line in ("jnp.where(a > 0.0, a, 0.0)", "jax.nn.relu(a)", "jnp.maximum(a, 0)",
                 "jnp.clip(a, 0.0, None)", "relu(a)"):
        assert spelt(calls(ast.parse(line))[0], ours=False), line
    for line in ("relu(a)", "relu(a, live)", "jnp.where(live, a, 0.0)",
                 "jnp.maximum(jnp.sum(w), 1.0)", "jnp.where(x > 30.0, 1.0, p)"):
        assert not spelt(calls(ast.parse(line))[0], ours=True), line


@pytest.mark.parametrize("cell, check", [
    ("lr_tb.train_packed", "steps_match_reference"),
    ("lr_tb.serve_rows", "answers_match_reference"),
])
def test_the_control_tool_fails_the_reference_check_and_no_other(cell, check, capsys):
    """``benchmarks/control.py`` on a rehearsal of a cell of each kind: exit
    0, the one failed check is the reference's, and what it patched is back."""
    from benchmarks.drivers import serve_open_loop
    from benchmarks.harness import manifest

    before = manifest.reference, serve_open_loop.Served.__init__
    argv = ["--workload", cell, "--rehearsal", "--seed", "6", "--seconds", "0.5"]
    assert control.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["checks_failed"] == [check] and "correct" not in line
    assert (manifest.reference, serve_open_loop.Served.__init__) == before


def test_a_run_with_the_step_broken_underneath_is_not_correct(monkeypatch, capsys):
    """The whole of a run but its look for a chip (a rehearsal of the LR
    train cell, in this process), with the program's ``put_batch`` leaving
    every second example out of the batches the check hands it: ``correct``
    comes out false on ``steps_match_reference``, and the numbers compared
    are printed beside their limits."""
    import dataclasses

    from benchmarks import run
    from xflow_tpu.io.batch import Batch
    from xflow_tpu.parallel.step import TrainStep

    real = TrainStep.put_batch

    def half(self, batch, *args, **kwargs):
        if isinstance(batch, Batch):
            keep = (np.arange(len(batch.weights)) % 2).astype(batch.weights.dtype)
            batch = dataclasses.replace(batch, weights=batch.weights * keep)
        return real(self, batch, *args, **kwargs)

    argv = ["--workload", "lr_tb.train_packed", "--rehearsal", "--seed", "6",
            "--seconds", "0.3", "--trace", "0"]
    assert run.main(argv) == 0
    sound = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert all(sound["checks"].values())
    monkeypatch.setattr(TrainStep, "put_batch", half)
    assert run.main(argv) == 1
    done = capsys.readouterr()
    broken = json.loads(done.out.strip().splitlines()[-1])
    assert [k for k, ok in broken["checks"].items() if not ok] == ["steps_match_reference"]
    assert list(broken)[-1] == "compared"
    rows = broken["compared"]["rows_rel_err"]
    assert rows["value"] > 100 * rows["limit"] > sound["compared"]["rows_rel_err"]["value"]
    assert f"compared rows_rel_err {rows['value']!r} <= limit {rows['limit']!r}" in done.err
    assert rows["limit"] == refcheck.ROWS_RTOL

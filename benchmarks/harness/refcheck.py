"""Hold the system's train step to the plain reference, on the chip, at the
cell's full width, outside the measured window.

For each of a few batches of the cell's corpus: gather the rows the batch
touches from the state as it is, let the system take its step (its own
wire, hot/cold split, sharding and donation), gather the same rows again,
and give the reference (``benchmarks/reference/``) the first gather, the
batch's entries as the loader steered them (table rows, values and field
ids, so truncation is identical) and nothing else.  The step's logloss and
every touched row of every table (``param``, ``n``, ``z``) must agree.

Tolerances.  Rows: 1e-6 of the largest magnitude in the array.  Logloss:
1e-6 absolute on a value of ~0.7, a few float32 ulps of a mean over 131072
rows.  Each lies between the largest error that sound runs of the cells read
on the chip at full width and the smallest that the control reads there
(``benchmarks/control.py``: the reference from operands rounded to bfloat16,
what a hot path or a contraction at default precision would compute).  The
readings are in PERF.md section 2; in short, rows: sound <= 2.0e-7, control
>= 5.3e-6 (LR; FM 1.1e-5; the limit was 1e-5 until PR 31, ABOVE what LR's
control reads); logloss: sound <= 1.2e-7, control >= 2.0e-6.  Both are constants, the same
for every family and configuration: a file cannot loosen what ``correct``
rests on.

Dense replicated parameters (PR 38).  A family that sets ``DENSE``
(``reference/ftrl.py``) is handed a copy of ``state["dense"]`` as it was
before the step, and each dense array the program leaves is compared with
the reference's.  The row measure would be blind here: a weight of 0.1 moves
by ``sgd_lr`` x a mean-over-batch gradient, about one float32 step of itself,
so an error of a hundredth of the UPDATE is a billionth of the array.  So the
error is read against the array's largest update in that step (``max |want -
before|``), every difference taken in float64 on the host.  What float32
leaves of that: both sides round ``p - sgd_lr * g`` to the float32 nearest,
so two sound steps can differ in any entry by one float32 step OF THAT ENTRY.
That much is rounding's and is taken off entry by entry: ``dense_rel_err`` is
the largest of what is left, held to ``DENSE_RTOL`` for every array.  The
allowance is the entry's own step, not the array's largest entry's: a matrix
whose large entries move by half a step has thousands of entries near 0 that
move by hundreds of their own, and they hold it.  An array the program leaves
bit for bit where it was while the reference moves it gets no allowance and
reads exactly 1: a state left unchanged.  ``dense_update_ulps.<array>`` is
the most float32 steps of itself that any entry of the array moves, a
reading beside no limit: an error of ``e`` of an update shows from about ``1
/ e`` such steps on, so under ``1 / DENSE_RTOL`` the limit is not what holds
the array, and under about 2 (DCN's ``cross_w`` on some steps) an array moved
twice rounds to what a sound step leaves and only its standing still is
seen.  A step in which the reference moves NO dense array proves nothing of
them and fails, as rows that did not move do; a single array may stand still
in a sound step (on one seed in twelve the chip's sound step and the
reference both left ``cross_w`` bit for bit where it was, twice).  The
readings ``DENSE_RTOL`` lies between are in PERF.md section 2; in short
(wide&deep and DCN at 39 fields, hidden 64 and 1024, on the chip, 13 seeds):
the program at ``highest`` <= 2.9e-8 in every array of 50 runs; the control,
whose gradients are 1e-3 to 1e-2 off, >= 2.5e-3 in ``b1`` and >= 3.7e-4 in
the first-layer matrix ``w1`` (entries that move by 400 to 1.7e5 of their own
steps) in every run of 50, >= 4.6e-4 in the other biases, and 0 in an output
weight of 64 entries or ``cross_w`` on most; an array left as it was 1.

Examples on a ReLU's kink (PR 51).  The allowance above is for two sound
roundings of ``p - sgd_lr * g``.  Two sound roundings of a PRE-ACTIVATION
differ too: one ``[B, K] x [K, H]`` product at ``highest`` summed in the order
the compiler gives the batch whole and in the order it gives a block of
``DENSE_BLOCK`` differs in most entries from K of about a thousand on (PR 50's
probe: by up to 4.8e-7 at K = 1 040 and 7.2e-7 at K = 15 600 on arguments of
rms 0.3; bit for bit alike at K = 400), and of some 10^7 to 10^8 arguments a
step a few dozen lie nearer 0 than that.  Such a unit can stand on the OTHER
side of 0 in the program than in the reference.  The forward barely moves (a
ReLU of 3e-7 against 0: the logloss agrees to 1e-7), but the backward's gate
for that example is 1 on one side and 0 on the other, and everything
upstream of the unit takes or loses that example's whole contribution: 1e-4
to 2.7e-2 of an array's largest update, 2.7e-6 to 6.3e-5 of a row (PR 50's
FiBiNET cell, refused; its builder's chip runs).  No limit holds that: a flip
reads up to 540 x ``DENSE_RTOL`` and the bfloat16 control's smallest readings
start at 3.7e-4.  So the examples that sit on a kink ("tie examples") take
part in a check step on NEITHER side.  They are found by the reference alone,
from ONE forward of its own, before the program's step
(``reference/ftrl.py::relu_margins``; every ReLU of every family is
``reference/wide_deep.py::relu``, which hands out its arguments): an example
ties where an argument of some ``relu`` call is nearer 0 than ``TIE_FACTOR``
float32 steps of THAT CALL'S LARGEST argument in the batch.  That is the size
two sound orders of one sum differ by: PR 50's probe reads at most 3 such
steps at K = 15 600 (7.2e-7 on a largest argument of 2.27) and under one at
the 99.9th percentile.  The threshold is not measured from a second forward
of the reference's (PR 51's first form did, and read exactly 0 wherever the
compiler gave both shapes one order of sums, which says nothing of the
program's order).  A tie example's weight is 0 in the batch the program's
``put_batch`` takes and in the weights the reference takes: the wire's own
mark of a padding example, so the step is the window's compiled program at
the window's shapes (``relu_step_compiles``, counted around those steps by
the run's meter, is held to 0) and the mean is over the examples that stay.
``relu_tie_share``, the largest share of a step's real examples left out, is
held to ``TIE_SHARE_MAX``: a cell cannot hide a batch behind its ties.  A
family without dense parameters, or whose forward never calls ``relu``, is
stepped as it always was.  What the chip reads (PR 51, 10 s windows;
PERF.md sections 2 and 6).  A sound VARIANT of the program in a scratch copy
(``blocks.dense_dot`` summing K in two halves, each at ``highest``: what a
re-tiling of the dense half does to the order of a sum) in DCN's cell, 13
seeds: held to the comparison WITHOUT this rule it is not ``correct`` on 11
(``dense_rel_err`` up to 9.7e-5 in ``b1`` and 1.9e-4 in ``b2``, 2.3e-5 or more
in every run, where the program as it ships reads 1.5e-7 at most); with the
rule ``correct`` on 13, every ``dense_rel_err`` <= 1.9e-7, 76 to 220 examples a
call under thresholds of 9.3e-10 to 3.7e-9 (DCN's arguments stay under
0.016).  The cells as committed, six seeds each: DCN's share 0.0036 to 0.0056;
xDeepFM's 0.0007 to 0.0025 (2 to 23 examples a call under 2.4e-7 to 4.8e-7);
AutoInt's 0.017 to 0.027 (three calls of 2 560 arguments an example).  The
control ties as the sound runs do and fails by the numbers above.  ``margin <
threshold`` is no proof that a unit cannot flip, only that the ones the
reference can see to be at risk are out of the step.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from benchmarks.harness.context import beside
from benchmarks.reference import ftrl

ROWS_RTOL = 1e-6
LOGLOSS_ATOL = 1e-6
DENSE_RTOL = 5e-5
# An example ties where a ReLU's argument is nearer 0 than this many float32
# steps of the largest argument of that ``relu`` call in the batch.  Two sound
# orders of a sum of 15 600 terms differ by at most 3 such steps (PR 50's
# probe: 7.2e-7 on a largest of 2.27; 6.3e-7 at PR 51) and by under one at the
# 99.9th percentile; an argument that flips is one whose size is under that
# difference.  The same for every family and configuration.
TIE_FACTOR = 4.0
# The largest share of a step's real examples that may be left out as ties.
# Four times the largest share the committed cells read on the chip: AutoInt's
# 0.027 (18 steps of six seeds: 0.017 to 0.027; DCN <= 0.0056, xDeepFM <=
# 0.0025: PR 51), rounded down.  ISSUE 51 asked for no more than 0.05, which
# stands 1.9 times over AutoInt's largest, and a call's threshold DOUBLES where
# its largest argument crosses a power of two (253 examples against 121 in one
# run's steps): a step checks 90 % of its examples or fails.  What must fail:
# a forward whose ReLUs are dead (every argument exactly 0) reads 1.
TIE_SHARE_MAX = 0.1


def entries(batch) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A loader batch as the model sees it: table rows int32 [B, K], values
    float32 [B, K] (0 marks padding) and field ids int32 [B, K], hot section
    first."""
    keys = np.concatenate([batch.hot_keys, batch.keys], axis=1)
    x = np.concatenate(
        [batch.hot_vals * batch.hot_mask, batch.vals * batch.mask], axis=1
    )
    slots = np.concatenate([batch.hot_slots, batch.slots], axis=1, dtype=np.int32)
    return keys, x, slots


def dense_errors(before: dict, got: dict, want: dict) -> dict:
    """Per dense array (float32, on the host): ``update`` the largest ``|want
    - before|``; ``update_ulps`` the most float32 steps of itself that any
    entry moves; ``rel_err`` the largest ``|got - want|`` beyond the entry's
    own float32 step, which two sound roundings of ``p - sgd_lr * g`` can
    differ by, over ``update`` (over the array's largest step where the
    reference does not move it).  An array that ``got`` leaves bit for bit as
    ``before`` while ``want`` moves it gets no allowance and reads 1.
    Differences in float64: the numbers are float32 and an update is a few of
    their last bits."""
    out = {}
    for name in before:
        b32, g32, w32 = (np.asarray(a[name], np.float32) for a in (before, got, want))
        b, g, w = (a.astype(np.float64) for a in (b32, g32, w32))
        moved = np.abs(w - b)
        update = float(moved.max())
        left_as_it_was = update > 0.0 and np.array_equal(g32, b32)
        own = np.spacing(np.maximum(np.abs(g32), np.abs(w32))).astype(np.float64)
        beyond = np.abs(g - w) - (0.0 if left_as_it_was else own)
        out[name] = {
            "rel_err": max(float(beyond.max()), 0.0) / (update or float(own.max())),
            "update": update,
            "update_ulps": float(
                (moved / np.spacing(np.maximum(np.abs(b32), np.abs(w32)))).max()
            ),
        }
    return out


def relu_ties(
    margin: np.ndarray, largest: np.ndarray, weights: np.ndarray
) -> tuple[np.ndarray, dict]:
    """From ``reference/ftrl.py::relu_margins`` and a batch's weights: bool
    [B], the real examples in which some ReLU's argument is nearer 0 than
    ``TIE_FACTOR`` float32 steps of that call's largest argument, and the
    readings for the step's record: their ``share`` of the real examples, the
    ``threshold`` and the count ``under`` it by call."""
    real = np.asarray(weights) > 0
    threshold = TIE_FACTOR * np.spacing(np.asarray(largest, np.float32))
    near = (margin < threshold[:, None]) & real
    tie = near.any(axis=0)
    return tie, {
        "share": float(tie.sum() / max(real.sum(), 1)),
        "threshold": [float(v) for v in threshold],
        "under": [int(v) for v in near.sum(axis=1)],
    }


def dense_compared(steps: list[dict]) -> dict:
    """The dense numbers of a run's steps as ``compared`` has them: each
    array's worst error beside ``DENSE_RTOL``, and the most float32 steps of
    itself that an entry of it moved, at its smallest over the steps: a
    reading, beside no limit of its own (an error of ``e`` of an update shows
    from about ``1 / e`` on); the largest update of a step's
    arrays, at its smallest over the steps, which may not be 0; for a family
    whose forward calls ``relu``, the largest share of a step's real examples
    left out as ties, beside ``TIE_SHARE_MAX``, and the programs compiled by
    the steps that took those batches, beside 0.  ``{}`` for a family without
    dense parameters."""
    out: dict = {}
    for name in steps[0].get("dense", {}):
        mine = [s["dense"][name] for s in steps]
        out[f"dense_rel_err.{name}"] = beside(
            max(a["rel_err"] for a in mine), DENSE_RTOL
        )
        out[f"dense_update_ulps.{name}"] = beside(
            min(a["update_ulps"] for a in mine), 0.0, ">="
        )
    if out:
        out["dense_update_max"] = beside(
            min(max(a["update"] for a in s["dense"].values()) for s in steps),
            0.0, ">",
        )
    if "relu" in steps[0]:
        out["relu_tie_share"] = beside(
            max(s["relu"]["share"] for s in steps), TIE_SHARE_MAX
        )
        if "compiles" in steps[0]["relu"]:
            out["relu_step_compiles"] = beside(
                sum(s["relu"]["compiles"] for s in steps), 0
            )
    return out


def check_train_steps(trainer, family, batches: list, cfg, meter=None) -> dict:
    """Run ``len(batches)`` system steps from the trainer's present state,
    each against the reference.  Leaves the trainer's state advanced.  With
    the run's ``meter`` (``harness/compiles.py``), a step whose batch had tie
    examples taken out has to be a program the trainer had compiled before:
    the window's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    hyper = ftrl.hyper_of(cfg)
    owns_dense = bool(getattr(family, "DENSE", False))
    if owns_dense != bool(trainer.state["dense"]):
        raise ValueError(
            f"reference {getattr(family, '__name__', family)} "
            f"{'has' if owns_dense else 'has no'} dense parameters and the "
            f"program's state has {sorted(trainer.state['dense'])}"
        )

    widths = {t: a["param"].shape[1] for t, a in trainer.state["tables"].items()}
    if widths != dict(family.TABLES):
        raise ValueError(
            f"the program's tables are {widths} wide and the reference's "
            f"TABLES say {dict(family.TABLES)}"
        )

    def dense_copy() -> dict:
        """The dense arrays on the host, by name: the first device's copy
        (they are replicated), taken before the step donates them."""
        return {
            name: np.array(a.addressable_shards[0].data)
            for name, a in trainer.state["dense"].items()
        }

    # room for the rows a batch touches, rounded up to a power of two so
    # that every batch, and nearly every seed, has the one compiled shape
    held = [entries(b) for b in batches]
    touched = [np.unique(keys[x != 0]) for keys, x, _ in held]
    cap = 1 << max(10, int(max(len(t) for t in touched) - 1).bit_length())

    # the touched rows leave the (possibly sharded) state as whole copies,
    # and the reference works on the first device's copy: no sharding
    whole = NamedSharding(trainer.step.mesh, PartitionSpec())

    @functools.partial(jax.jit, out_shardings=whole)
    def gather_whole(tables, rows):
        return jax.tree.map(lambda a: a[rows], tables)

    def gather(tables, rows):
        return jax.tree.map(
            lambda a: a.addressable_shards[0].data, gather_whole(tables, rows)
        )

    @jax.jit
    def worst(got, want, valid):
        """Per array, (largest error, largest reference magnitude) over the
        valid rows."""
        return jax.tree.map(
            lambda a, b: (
                jnp.max(jnp.abs(a - b) * valid[:, None]),
                jnp.max(jnp.abs(b) * valid[:, None]),
            ),
            got, want,
        )

    out = {"steps": [], "ok": True}
    if owns_dense:  # what ``family.matmuls`` counts the step's products from
        out["dense_shapes"] = {
            name: list(a.shape) for name, a in trainer.state["dense"].items()
        }
    for batch, (keys, x, slots), mine in zip(batches, held, touched):
        n = len(mine)
        rows = np.full(cap, mine[-1], np.int32)
        rows[:n] = mine
        idx = np.where(x != 0, np.searchsorted(mine, keys), 0).astype(np.int32)
        rows_dev = jnp.asarray(rows)
        before = gather(trainer.state["tables"], rows_dev)
        dense_before = dense_copy()
        relu = None
        if owns_dense:
            margin, largest = jax.device_get(ftrl.relu_margins(
                family, before, idx, x, slots, cfg.max_fields, dense_before
            ))
            if len(largest):  # the forward calls ``relu``
                tie, relu = relu_ties(margin, largest, batch.weights)
                # weight 0 is the wire's own mark of a padding example: the
                # program's compiled step and the reference's both leave it out
                batch = dataclasses.replace(
                    batch, weights=np.where(tie, np.float32(0), batch.weights)
                )
        counted = meter if relu else None  # only a step that lost its ties
        compiled = counted.snapshot()["compiles"] if counted else 0
        trainer.state, metrics = trainer.step.train(
            trainer.state, trainer.step.put_batch(batch)
        )
        if counted:
            relu["compiles"] = counted.snapshot()["compiles"] - compiled
        after = gather(trainer.state["tables"], rows_dev)
        # a family without dense parameters is called as it always was
        handed = (dense_before, float(cfg.sgd_lr)) if owns_dense else ()
        ll_ref, want, want_dense = ftrl.train_step(
            family, before, idx, x, batch.labels, batch.weights, hyper,
            slots, cfg.max_fields, *handed,
        )
        errs = jax.device_get(worst(after, want, jnp.arange(cap) < n))
        ll_sys, ll_ref = float(metrics["logloss"]), float(ll_ref)
        step = {
            "touched_rows": n,
            "logloss": ll_sys,
            "logloss_err": abs(ll_sys - ll_ref),
            "rows_rel_err": {
                f"{t}.{a}": float(err / max(float(mag), 1e-30))
                for t, arrays in errs.items()
                for a, (err, mag) in arrays.items()
            },
        }
        moved = float(
            max(float(mag) for arrays in errs.values() for _, mag in arrays.values())
        )
        step["ok"] = bool(
            np.isfinite(ll_sys)
            and step["logloss_err"] <= LOGLOSS_ATOL
            and all(e <= ROWS_RTOL for e in step["rows_rel_err"].values())
            and moved > 0.0  # a step that touched nothing proves nothing
        )
        if owns_dense:
            step["dense"] = dense_errors(
                dense_before, dense_copy(), jax.device_get(want_dense)
            )
            arrays = step["dense"].values()
            step["ok"] = bool(
                step["ok"]
                and all(a["rel_err"] <= DENSE_RTOL for a in arrays)
                # a step that moved no dense array proves nothing of them
                and max(a["update"] for a in arrays) > 0.0
            )
        if relu:
            step["relu"] = relu
            # a cell cannot hide a batch behind its ties, and the step that
            # took the batch without them is the window's compiled program
            step["ok"] = (
                step["ok"] and relu["share"] <= TIE_SHARE_MAX
                and relu.get("compiles", 0) == 0
            )
        out["steps"].append(step)
        out["ok"] = out["ok"] and step["ok"]
    return out

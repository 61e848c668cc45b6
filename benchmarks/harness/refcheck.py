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
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.harness.context import beside
from benchmarks.reference import ftrl

ROWS_RTOL = 1e-6
LOGLOSS_ATOL = 1e-6
DENSE_RTOL = 5e-5


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


def dense_compared(steps: list[dict]) -> dict:
    """The dense numbers of a run's steps as ``compared`` has them: each
    array's worst error beside ``DENSE_RTOL``, and the most float32 steps of
    itself that an entry of it moved, at its smallest over the steps: a
    reading, beside no limit of its own (an error of ``e`` of an update shows
    from about ``1 / e`` on); the largest update of a step's
    arrays, at its smallest over the steps, which may not be 0.  ``{}`` for a
    family without dense parameters."""
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
    return out


def check_train_steps(trainer, family, batches: list, cfg) -> dict:
    """Run ``len(batches)`` system steps from the trainer's present state,
    each against the reference.  Leaves the trainer's state advanced."""
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
        trainer.state, metrics = trainer.step.train(
            trainer.state, trainer.step.put_batch(batch)
        )
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
        out["steps"].append(step)
        out["ok"] = out["ok"] and step["ok"]
    return out

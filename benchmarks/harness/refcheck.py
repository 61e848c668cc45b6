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
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.reference import ftrl

ROWS_RTOL = 1e-6
LOGLOSS_ATOL = 1e-6


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


def check_train_steps(trainer, family, batches: list, cfg) -> dict:
    """Run ``len(batches)`` system steps from the trainer's present state,
    each against the reference.  Leaves the trainer's state advanced."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    hyper = ftrl.hyper_of(cfg)
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
    for batch, (keys, x, slots), mine in zip(batches, held, touched):
        n = len(mine)
        rows = np.full(cap, mine[-1], np.int32)
        rows[:n] = mine
        idx = np.where(x != 0, np.searchsorted(mine, keys), 0).astype(np.int32)
        rows_dev = jnp.asarray(rows)
        before = gather(trainer.state["tables"], rows_dev)
        trainer.state, metrics = trainer.step.train(
            trainer.state, trainer.step.put_batch(batch)
        )
        after = gather(trainer.state["tables"], rows_dev)
        ll_ref, want = ftrl.train_step(
            family, before, idx, x, batch.labels, batch.weights, hyper,
            slots, cfg.max_fields,
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
        out["steps"].append(step)
        out["ok"] = out["ok"] and step["ok"]
    return out

"""Hold the system's train step to the plain reference, on the chip, at the
cell's full width, outside the measured window.

For each of a few batches of the cell's corpus: gather the rows the batch
touches from the state as it is, let the system take its step (its own
wire, hot/cold split, sharding and donation), gather the same rows again,
and give the reference (``benchmarks/reference/``) the first gather, the
batch's entries as the loader steered them (so truncation is identical) and
nothing else.  The step's logloss and every touched row of every table
(``param``, ``n``, ``z``) must agree.

Tolerances.  Rows: 1e-5 of the largest magnitude in the array; float32
sums of ~10^5 terms in another order differ by ~1e-6 of it, and a hot path
that rounded its operands to bfloat16 (2^-9 = 2e-3) would miss by two
orders.  Logloss: 1e-6 absolute on a value of ~0.7, i.e. a few float32
ulps of a mean over 131072 rows; the chip against the CPU backend was 3e-7
(PERF.md, PR 21).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.reference import ftrl

ROWS_RTOL = 1e-5
LOGLOSS_ATOL = 1e-6


def entries(batch) -> tuple[np.ndarray, np.ndarray]:
    """A loader batch as the model sees it: table rows int32 [B, K] and
    values float32 [B, K] (0 marks padding), hot section first."""
    keys = np.concatenate([batch.hot_keys, batch.keys], axis=1)
    x = np.concatenate(
        [batch.hot_vals * batch.hot_mask, batch.vals * batch.mask], axis=1
    )
    return keys, x


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
    touched = [np.unique(keys[x != 0]) for keys, x in held]
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
    for batch, (keys, x), mine in zip(batches, held, touched):
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
            family, before, idx, x, batch.labels, batch.weights, hyper
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

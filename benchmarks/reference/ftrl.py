"""What every family's reference shares: the reference's clamped sigmoid and
mean-over-batch gradient, logloss, and the FTRL-proximal recurrence
(``ftrl.h:54-79``), in plain float32 ``jax.numpy``.

One train step is written for the rows a batch touches and nothing else: no
table, no hot/cold split, no wire, no sharding.  It is handed

    rows    {table: {"param", "n", "z": [U, D]}}  the U touched rows, gathered
            from the state as it was before the step
    idx     int32 [B, K]    which of the U rows each feature entry is
    x       float32 [B, K]  the entry's value: 1 for a feature, 0 for padding
    labels, weights  float32 [B]   (weight 0 marks a padding example)
    slots   int32 [B, K]    the entry's field id, as the loader steered it
    num_fields              how many fields the configuration counts (static)

and returns the step's logloss and the U rows as the step leaves them.

A family is a module with ``TABLES`` (table name -> row width),
``logit(rows, x)`` and ``grad_logit(rows, x)``.  One that sets
``USES_FIELDS = True`` reads the field ids and is called as
``logit(rows, x, slots, num_fields)``, ``grad_logit(rows, x, slots,
num_fields)``; for every other family the two arguments stay out of the
compiled program.  Dense (replicated) parameters are not in the protocol:
every parameter a family has is a row of a hashed table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


HYPER_KEYS = ("alpha", "beta", "lambda1", "lambda2")  # ftrl.h:17-20


def hyper_of(cfg) -> tuple[tuple[str, float], ...]:
    """The FTRL settings of a configuration, hashable (``train_step`` takes
    them as a static argument); ``dict()`` of it is what the update takes."""
    return tuple((k, float(getattr(cfg, k))) for k in HYPER_KEYS)


def sigmoid_clamped(x):
    """base.h:54-63: below -30 the answer is 1e-6, above 30 it is 1."""
    p = 1.0 / (1.0 + jnp.exp(-x))
    return jnp.where(x > 30.0, 1.0, jnp.where(x < -30.0, 1e-6, p))


def logloss(labels, p, weights):
    """Weighted mean negative log-likelihood, p clamped to [1e-6, 1 - 1e-6]
    (the system's stated departure from base.h's log2 form)."""
    p = jnp.clip(p, 1e-6, 1.0 - 1e-6)
    nll = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log(1.0 - p))
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def weight_of(z, n, hyper: dict):
    """The weight FTRL-proximal keeps for accumulators ``z`` and ``n``: zero
    inside the L1 ball, else the closed form of ftrl.h:66-74."""
    return jnp.where(
        jnp.abs(z) <= hyper["lambda1"],
        0.0,
        (jnp.sign(z) * hyper["lambda1"] - z)
        / ((hyper["beta"] + jnp.sqrt(n)) / hyper["alpha"] + hyper["lambda2"]),
    )


def ftrl_update(row: dict, g, hyper: dict) -> dict:
    """One push of gradient ``g`` to rows ``{"param", "n", "z"}``."""
    w, n, z = row["param"], row["n"], row["z"]
    n_new = n + g * g
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / hyper["alpha"]
    z_new = z + g - sigma * w
    w_new = weight_of(z_new, n_new, hyper)
    # ftrl.h:113-120 gives a latent factor its random value on the first
    # push; the system draws the whole table at the start instead and keeps
    # the drawn value of an entry no gradient has reached yet (n' == 0)
    w_new = jnp.where(n_new == 0.0, w, w_new)
    return {"param": w_new, "n": n_new, "z": z_new}


@functools.partial(jax.jit, static_argnames=("family", "hyper", "num_fields"))
def train_step(
    family, rows, idx, x, labels, weights, hyper, slots=None, num_fields=0
):
    """``family`` is a reference module (``logit``, ``grad_logit``);
    ``hyper`` a hashable tuple of (name, value) FTRL settings; ``slots`` and
    ``num_fields`` go to a family that declares ``USES_FIELDS`` and to no
    other."""
    h = dict(hyper)
    fields = (slots, num_fields) if getattr(family, "USES_FIELDS", False) else ()
    with jax.default_matmul_precision("highest"):
        gathered = {t: r["param"][idx] for t, r in rows.items()}  # [B, K, D]
        p = sigmoid_clamped(family.logit(gathered, x, *fields))
        ll = logloss(labels, p, weights)
        # lr_worker.cc:116-118: the gradient is the mean over the real rows
        residual = (p - labels) * weights / jnp.maximum(jnp.sum(weights), 1.0)
        new_rows = {}
        for t, g in family.grad_logit(gathered, x, *fields).items():
            occ = (g * residual[:, None, None]).reshape(-1, g.shape[-1])
            pushed = jax.ops.segment_sum(
                occ, idx.reshape(-1), num_segments=rows[t]["param"].shape[0]
            )
            new_rows[t] = ftrl_update(rows[t], pushed, h)
    return ll, new_rows

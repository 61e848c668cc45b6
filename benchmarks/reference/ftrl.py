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
    dense   {name: array}   the family's dense replicated parameters, by the
                            program's names, as they were before the step
    sgd_lr                  the rate of their plain SGD (static)

and returns the step's logloss, the U rows and the dense parameters as the
step leaves them (``{}`` for a family that has none).

A family is a module with ``TABLES`` (table name -> row width),
``logit(rows, x)`` and ``grad_logit(rows, x)``.  One that sets
``USES_FIELDS = True`` reads the field ids and is called as
``logit(rows, x, slots, num_fields)``, ``grad_logit(rows, x, slots,
num_fields)``; for every other family the two arguments stay out of the
compiled program.

A family that owns dense parameters (an MLP's weights, a cross stack) sets
``DENSE = True`` and writes ``logit`` alone, called with the pytree last:
``logit(rows, x[, slots, num_fields], dense)``.  Its gradients, of the
gathered rows AND of ``dense``, are one ``jax.vjp`` of that definition at
``highest`` matmul precision, handed the residual every family's rows get
(``lr_worker.cc:116-118``: the mean over the real rows); the rows go through
FTRL as every table's, the dense arrays take ``p - sgd_lr * g``, the
program's plain SGD whatever the tables' optimizer
(``parallel/step.py::apply_dense_sgd``).  The step runs over blocks of
``DENSE_BLOCK`` examples, each block gathering its own rows and adding to the
pushed gradients and to the dense gradient (a row's logit reads that row's
entries alone), so that nothing of ``[B, K, D]`` is held for the whole batch
and the step fits beside a live trainer.  Such a family also says which
matmuls a step has to do, for the roofline's FLOPs
(``harness/costs.py``): ``matmuls(shapes) -> [(k, n), ...]``, one ``[B, k] x
[k, n]`` product each, from the shapes of the program's dense arrays by name
(the widths are stated once, by the program's state).  For a family without
``DENSE`` the two arguments stay out of the compiled program, which is the
one it always was.

A dense family's ReLUs are ``reference/wide_deep.py::relu`` and nothing else,
and ``relu_margins`` is how the check (``harness/refcheck.py``) asks, before
a step, how near 0 each example's ReLU arguments stand and how large the
call's arguments run: an example nearer a kink than a few float32 steps of
the largest takes part in that step on neither side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.wide_deep import relu_arguments


HYPER_KEYS = ("alpha", "beta", "lambda1", "lambda2")  # ftrl.h:17-20
DENSE_BLOCK = 4096  # examples whose gathered rows a dense family's step holds at once


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


def _dense_step(family, rows, idx, x, labels, weights, fields, dense, sgd_lr):
    """The step of a family that owns dense parameters: (p [B], pushed
    {table: [U, D]}, the dense arrays after ``p - sgd_lr * g``)."""
    # the field ids go block by block with the entries; their count is static
    planes, num_fields = (idx, x, labels, weights) + fields[:1], fields[1:]
    num_real = jnp.maximum(jnp.sum(weights), 1.0)
    block = math.gcd(x.shape[0], DENSE_BLOCK)

    def blocks(a):
        return a.reshape(-1, block, *a.shape[1:])

    def one(carry, blk):
        pushed, grad_dense = carry
        idx_b, x_b, labels_b, weights_b, *slots_b = blk
        gathered = {t: r["param"][idx_b] for t, r in rows.items()}  # [block, K, D]
        logit, pullback = jax.vjp(
            lambda g, d: family.logit(g, x_b, *slots_b, *num_fields, d),
            gathered, dense,
        )
        p = sigmoid_clamped(logit)
        grad_rows, grad_d = pullback((p - labels_b) * weights_b / num_real)
        pushed = {
            t: acc + jax.ops.segment_sum(
                grad_rows[t].reshape(-1, acc.shape[-1]), idx_b.reshape(-1),
                num_segments=acc.shape[0],
            )
            for t, acc in pushed.items()
        }
        return (pushed, jax.tree.map(jnp.add, grad_dense, grad_d)), p

    zeros = (
        {t: jnp.zeros_like(r["param"]) for t, r in rows.items()},
        jax.tree.map(jnp.zeros_like, dense),
    )
    (pushed, grad_dense), p = jax.lax.scan(one, zeros, tuple(map(blocks, planes)))
    new_dense = jax.tree.map(lambda a, g: a - sgd_lr * g, dense, grad_dense)
    return p.reshape(-1), pushed, new_dense


@functools.partial(jax.jit, static_argnames=("family", "num_fields"))
def relu_margins(family, rows, idx, x, slots=None, num_fields=0, dense=None):
    """How near 0 the ReLUs of a dense family's forward stand in each example,
    and how large their arguments run: ``(margin [L, B], largest [L])`` for
    the L calls of ``relu`` in one ``family.logit``, in call order (L = 0
    where it calls none).  ``margin[l, i]`` is the smallest ``|argument|`` of
    call l in example i and ``largest[l]`` the largest of call l in the batch,
    both over the entries that count.  One forward at ``highest``, block by
    block as ``_dense_step`` takes it: nothing of ``[B, K, D]`` is held
    whole."""
    fields = (slots, num_fields) if getattr(family, "USES_FIELDS", False) else ()
    planes, num_fields = (idx, x) + fields[:1], fields[1:]
    block = math.gcd(x.shape[0], DENSE_BLOCK)

    def one(blk):
        idx_b, x_b, *slots_b = blk
        gathered = {t: r["param"][idx_b] for t, r in rows.items()}
        with relu_arguments() as seen:
            family.logit(gathered, x_b, *slots_b, *num_fields, dense)
        seen = [jnp.abs(a).reshape(block, -1) for a in seen]
        margin = jnp.asarray([a.min(axis=1) for a in seen]).reshape(len(seen), block)
        # an entry that does not count is ``inf``
        largest = [jnp.max(jnp.where(jnp.isfinite(a), a, 0.0)) for a in seen]
        return margin, jnp.asarray(largest).reshape(len(seen))

    blocks = tuple(a.reshape(-1, block, *a.shape[1:]) for a in planes)
    with jax.default_matmul_precision("highest"):
        margin, largest = jax.lax.map(one, blocks)  # [blocks, L, block], [blocks, L]
    calls = largest.shape[1]
    return margin.transpose(1, 0, 2).reshape(calls, x.shape[0]), largest.max(axis=0)


@functools.partial(
    jax.jit, static_argnames=("family", "hyper", "num_fields", "sgd_lr")
)
def train_step(
    family, rows, idx, x, labels, weights, hyper, slots=None, num_fields=0,
    dense=None, sgd_lr=0.0,
):
    """``family`` is a reference module (``logit`` and, unless it sets
    ``DENSE``, ``grad_logit``); ``hyper`` a hashable tuple of (name, value)
    FTRL settings; ``slots`` and ``num_fields`` go to a family that declares
    ``USES_FIELDS`` and to no other, ``dense`` and ``sgd_lr`` to one that
    declares ``DENSE`` and to no other.  Returns (logloss, rows, dense)."""
    h = dict(hyper)
    fields = (slots, num_fields) if getattr(family, "USES_FIELDS", False) else ()
    with jax.default_matmul_precision("highest"):
        if getattr(family, "DENSE", False):
            p, pushed, new_dense = _dense_step(
                family, rows, idx, x, labels, weights, fields, dense, sgd_lr
            )
            new_rows = {t: ftrl_update(rows[t], g, h) for t, g in pushed.items()}
            return logloss(labels, p, weights), new_rows, new_dense
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
    return ll, new_rows, {}

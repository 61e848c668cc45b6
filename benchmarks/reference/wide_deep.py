"""Wide & Deep (Cheng et al., "Wide & Deep Learning for Recommender Systems",
DLRS 2016) as the program defines its family (``models/wide_deep.py``): a
sparse linear term beside an embedding tower with one hidden layer.

    tower[f, :] = sum over the entries i of the row with field f:  emb_i * x_i
    h           = ReLU(flatten(tower) W1 + b1)          [F * E] -> [H]
    logit       = sum_i w_i x_i  +  h W2 + b2           [H] -> 1

An entry whose field is outside ``[0, num_fields)`` adds nothing to the tower
and has gradient 0 there; its linear term stays.  ``w`` and ``emb`` are rows
of hashed tables under FTRL; ``w1, b1, w2, b2`` are dense replicated
parameters under plain SGD (``reference/ftrl.py``: the ``DENSE`` protocol,
gradients by ``jax.vjp`` of this definition).  The dense widths are read off
the arrays, so one file serves every ``hidden_dim``; ``EMB_DIM`` is the
program's default, what ``TABLES`` states for the byte counts and what the
check holds the program's table to.  This is the
program's family as it stands, not a published configuration: no cell runs
it yet.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax.numpy as jnp

EMB_DIM = 8  # Config.emb_dim's default
TABLES = {"w": 1, "emb": EMB_DIM}
USES_FIELDS = True  # logit takes (slots, num_fields)
DENSE = True  # ... and the dense pytree last; no grad_logit


def tower(emb, x, slots, num_fields: int):
    """emb [B, K, E] gathered rows, x [B, K], slots [B, K] -> [B, F * E]: each
    inside entry's ``emb_i * x_i`` added to its row's and field's sum."""
    inside = (slots >= 0) & (slots < num_fields)
    field = jnp.where(inside, slots, 0)
    ex = jnp.where(inside[..., None], emb * x[..., None], 0.0)
    row = jnp.arange(x.shape[0])[:, None]
    sums = jnp.zeros((x.shape[0], num_fields, emb.shape[-1]), emb.dtype)
    return sums.at[row, field].add(ex).reshape(x.shape[0], -1)


_SEEN: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "relu_arguments", default=None
)


@contextlib.contextmanager
def relu_arguments():
    """Within, every call of ``relu`` leaves its argument in the list this
    yields, in call order, an entry its ``live`` says does not count as
    ``inf``: how ``reference/ftrl.py::relu_margins`` sees how near 0 each
    example's ReLUs stand.  Filled while a function is traced, so open it
    inside the traced function and return what it holds from there; for the
    same reason a family keeps ``relu`` OUTSIDE any ``jax.checkpoint`` it wraps
    a layer in (``reference/autoint_criteo.py``: the wrapper ends where the
    argument is made), or the argument could not leave the inner trace."""
    seen: list = []
    token = _SEEN.set(seen)
    try:
        yield seen
    finally:
        _SEEN.reset(token)


def relu(a, live=None):
    """Gradient 0 at 0 and below, as ``jax.nn.relu``'s.  EVERY ReLU of every
    reference family is this function: the check leaves out of its steps the
    examples in which an argument lies within rounding of 0
    (``harness/refcheck.py``), and a ReLU it cannot see is one whose flips it
    would hold against the program.  ``live`` (broadcast against ``a``) marks
    the entries that reach the logit; the others come out 0, have gradient 0
    on either side of the kink and are no tie."""
    seen = _SEEN.get()
    if seen is not None:
        seen.append(a if live is None else jnp.where(live, a, jnp.inf))
    return jnp.where(a > 0.0 if live is None else live & (a > 0.0), a, 0.0)


def logit(rows: dict, x, slots, num_fields: int, dense: dict):
    """rows["w"] [B, K, 1], rows["emb"] [B, K, E] gathered rows; dense
    ``w1 [F * E, H], b1 [H], w2 [H, 1], b2 [1]`` -> [B]."""
    wide = jnp.sum(rows["w"][..., 0] * x, axis=-1)
    h = relu(tower(rows["emb"], x, slots, num_fields) @ dense["w1"] + dense["b1"])
    return wide + (h @ dense["w2"] + dense["b2"])[:, 0]


def matmuls(shapes: dict) -> list[tuple[int, int]]:
    """The ``[B, k] x [k, n]`` products of one forward pass, from the dense
    arrays' shapes."""
    return [tuple(shapes["w1"]), tuple(shapes["w2"])]

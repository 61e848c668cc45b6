"""Sparse logistic regression (reference ``src/model/lr/lr_worker.cc``):
the logit is the sum of the weights of a row's features."""

from __future__ import annotations

import jax.numpy as jnp

TABLES = {"w": 1}  # table name -> row width


def logit(rows: dict, x):
    """rows["w"] [B, K, 1] gathered weights, x [B, K] values -> [B]."""
    return jnp.sum(rows["w"][..., 0] * x, axis=-1)


def grad_logit(rows: dict, x) -> dict:
    """d logit / d each gathered entry, [B, K, 1]."""
    return {"w": x[..., None]}

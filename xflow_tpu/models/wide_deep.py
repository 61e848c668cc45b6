"""Wide & Deep: sparse linear ("wide") + embedding MLP ("deep").

Capability extension beyond the reference's model zoo (BASELINE.json
configs list "Wide-and-deep (LR + 2-layer MLP) on Criteo-Kaggle").

* Wide: the LR weight table, FTRL-updated like every table.
* Deep: an embedding table [T, emb_dim]; each sample's embeddings are
  field-summed into max_fields buckets (same one-hot trick as MVM, so
  variable features-per-field work under static shapes), concatenated
  to [max_fields * emb_dim], and fed through a 2-layer ReLU MLP whose
  weights are replicated dense parameters.

Autodiff model: table gradients and MLP gradients both come from
jax.grad of the batch loss.  The dense MLP parameters are updated with
plain SGD (config.sgd_lr) regardless of the table optimizer — FTRL's
per-coordinate L1 shrinkage is for sparse one-hot features, not dense
hidden layers.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    field_sum_tower,
    flatten_tower,
    linear_term,
    masked_x,
    mlp_head,
    mlp_head_init,
)


@dataclasses.dataclass(frozen=True)
class WideDeepModel(AutodiffModel):
    emb_dim: int = 8
    hidden: int = 64
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "wide_deep"

    def tables(self) -> list[TableSpec]:
        return [
            TableSpec("w", 1, lambda rng, shape: jnp.zeros(shape, jnp.float32)),
            TableSpec(
                "emb",
                self.emb_dim,
                lambda rng, shape: (
                    jax.random.normal(rng, shape, jnp.float32) * self.v_init_scale
                ),
                init_kind="normal",
                init_scale=self.v_init_scale,
            ),
        ]

    def dense_init(self, rng: jax.Array) -> dict:
        # He init for the ReLU layer, small linear head
        # (blocks.mlp_head_init — the lifted pre-refactor geometry).
        return mlp_head_init(
            rng, self.max_fields * self.emb_dim, self.hidden
        )

    def dense_matmuls(self) -> list[tuple[int, int]]:
        return [(self.max_fields * self.emb_dim, self.hidden), (self.hidden, 1)]

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "wide_deep requires dense MLP params"
        x = masked_x(batch)  # [B, K]
        wide = linear_term(rows["w"], x)
        # embedding tower + scalar MLP head, both straight off the
        # blocks shelf (field_sum_tower IS the lifted deep half)
        field_emb = field_sum_tower(
            rows["emb"], x, batch["slots"], self.max_fields
        )  # [B, F, E]
        deep = mlp_head(dense, flatten_tower(field_emb))
        return wide + deep

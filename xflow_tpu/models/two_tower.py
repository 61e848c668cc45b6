"""Two-tower retrieval: user/item towers over disjoint field groups.

The other half of a real recommender stack (arXiv:2501.10546): before
a ranker can point-score candidates, something has to GENERATE them
from a catalog of millions.  The two-tower factorization makes that
tractable: the logit is a dot product

    logit = < u(user features), i(item features) >

where each tower only reads its own field group — fields
``[0, split_field)`` are user-side, ``[split_field, max_fields)`` are
item-side.  Because the item tower is independent of the user, every
item's embedding can be computed ONCE offline and frozen into a
serve-time index (serve/artifact.py::export_item_index); retrieval is
then one [B, Dt] user-tower pass plus a dot-product scan + top-k over
the index (PredictEngine.topk) — no per-candidate model evaluation.

Training is standard BCE over the dot product on (user, item, click)
rows — an AutodiffModel riding the existing gather→tower→reduce step:
one shared ``emb`` table (both towers draw from the same hashed key
space; the field split keeps their rows disjoint in practice), a
2-layer MLP tower per side (replicated dense params, plain-SGD updated
like wide&deep's head).  Built entirely from models/blocks.py:
field_sum_tower → slice the field range → mlp_tower → dot_interaction.

**Bias lanes.**  Each tower's MLP emits ``tower_dim + 1`` lanes; the
last is a per-side BIAS folded into the dot by augmentation —
``u' = [u, b_u, 1]``, ``i' = [i, 1, b_i]`` so ``<u', i'> = <u, i> +
b_u + b_i``.  A bare dot cannot represent ADDITIVE structure (a
user-only propensity plus an item-only popularity — the dominant
terms of real CTR and exactly the planted signal of the convergence
proxy: measured AUC 0.510 after 2 epochs without the lanes vs 0.640
with, docs/CONVERGENCE.md); the bias lanes add it while keeping the
score a PURE dot product, so the serve-time index scan
(PredictEngine.topk over [N, tower_dim + 2] rows) is unchanged —
item popularity simply lives inside each index row.

Out-of-range fields drop out of the one-hot like every other family;
features on the WRONG side of the split simply pool into that side's
tower (the slot says which tower owns the feature).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    dot_interaction,
    field_sum_tower,
    masked_x,
    mlp_tower,
    mlp_tower_init,
)


@dataclasses.dataclass(frozen=True)
class TwoTowerModel(AutodiffModel):
    emb_dim: int = 8
    tower_dim: int = 16
    hidden: int = 64
    max_fields: int = 32
    split_field: int = 16  # fields < split are user-side, >= are item-side
    v_init_scale: float = 1e-2
    name: str = "two_tower"

    def __post_init__(self) -> None:
        if not 0 < self.split_field < self.max_fields:
            raise ValueError(
                f"two_tower split_field {self.split_field} must be in "
                f"(0, max_fields={self.max_fields}): both towers need "
                "at least one field"
            )

    @property
    def index_dim(self) -> int:
        """Serve-time index row width: tower_dim core lanes + the two
        bias-augmentation lanes (module docstring).  PredictEngine.
        attach_item_index validates index shapes against this."""
        return self.tower_dim + 2

    def tables(self) -> list[TableSpec]:
        return [
            TableSpec(
                "emb",
                self.emb_dim,
                lambda rng, shape: (
                    jax.random.normal(rng, shape, jnp.float32)
                    * self.v_init_scale
                ),
                init_kind="normal",
                init_scale=self.v_init_scale,
            )
        ]

    def dense_init(self, rng: jax.Array) -> dict:
        ku, ki = jax.random.split(rng)
        user_in = self.split_field * self.emb_dim
        item_in = (self.max_fields - self.split_field) * self.emb_dim
        # + 1 output lane per tower: the per-side bias the dot
        # augmentation folds in (module docstring)
        dense = mlp_tower_init(
            ku, user_in, self.hidden, self.tower_dim + 1, prefix="u_"
        )
        dense.update(mlp_tower_init(
            ki, item_in, self.hidden, self.tower_dim + 1, prefix="i_"
        ))
        return dense

    def dense_matmuls(self) -> list[tuple[int, int]]:
        user_in = self.split_field * self.emb_dim
        item_in = (self.max_fields - self.split_field) * self.emb_dim
        out = (self.hidden, self.tower_dim + 1)
        return [(user_in, self.hidden), out, (item_in, self.hidden), out]

    def _towers_input(
        self, rows: dict[str, jax.Array], batch: BatchArrays
    ) -> jax.Array:
        """One shared field-pool over ALL fields [B, F, E]; each tower
        slices its own field range (one one-hot matmul serves both)."""
        return field_sum_tower(
            rows["emb"], masked_x(batch), batch["slots"], self.max_fields
        )

    def user_embed(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        """[B, tower_dim + 2] augmented user-tower output
        ``[u, b_u, 1]`` — the serve-time query embedding
        (PredictEngine.topk runs exactly this, then a dot scan over
        the frozen item index)."""
        assert dense is not None, "two_tower requires dense tower params"
        part = self._towers_input(rows, batch)[:, : self.split_field]
        m = mlp_tower(dense, part.reshape(part.shape[0], -1), "u_")
        ones = jnp.ones((m.shape[0], 1), m.dtype)
        return jnp.concatenate([m, ones], axis=-1)  # [u, b_u, 1]

    def item_embed(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        """[B, tower_dim + 2] augmented item-tower output
        ``[i, 1, b_i]`` — what export_item_index freezes, one row per
        catalog item (the bias lane IS the item's popularity prior,
        frozen into its index row)."""
        assert dense is not None, "two_tower requires dense tower params"
        part = self._towers_input(rows, batch)[:, self.split_field:]
        m = mlp_tower(dense, part.reshape(part.shape[0], -1), "i_")
        ones = jnp.ones((m.shape[0], 1), m.dtype)
        return jnp.concatenate(
            [m[:, : self.tower_dim], ones, m[:, self.tower_dim:]],
            axis=-1,
        )  # [i, 1, b_i]

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        # training logit == the retrieval score: same dot, so index
        # scores are calibrated against the trained objective
        return dot_interaction(
            self.user_embed(rows, batch, dense),
            self.item_embed(rows, batch, dense),
        )

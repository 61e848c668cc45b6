"""Field-aware Factorization Machine.

Capability extension beyond the reference's model zoo (BASELINE.json
configs list "Field-aware FM (FFM) on Avazu CTR" as a target workload;
the reference itself ships only LR/FM/MVM).  Standard FFM:

    logit = sum_i w_i x_i
          + sum_{i<j} < v[k_i, f_j, :], v[k_j, f_i, :] > x_i x_j

Each feature key holds one latent vector PER FIELD: the v table is
[T, max_fields * v_dim], viewed as [T, F, D].  Fields beyond
max_fields contribute nothing (their one-hot row is zero), matching
MVM's field handling.

Pure autodiff model — no reference forward/backward quirks to
reproduce: the train step pulls the residual back through ``logit``
(parallel/step.py::grads_from_rows).  What holds it to float32 on the
TPU, where benchmarks/reference/ffm.py (the plain sum over pairs)
judges it at libffm's widths (PERF.md section 6, PR 34): the one
contraction of the forward, the field sums, asks for
Precision.HIGHEST (blocks.field_contract), and so does its transpose,
which autodiff writes with the forward's precision; the cross term
and the diagonal are elementwise; and the residual is the step's own
``sigmoid_ref(logit) - y``, not the derivative of a softplus loss,
which the TPU computes to 7e-5 of the sigmoid.

The pair interaction uses the field-aggregated identity (round-2
restructure; the naive form materializes [B, K, K, D] pair tensors —
tens of GB at bench shapes):

    S[b, f1, f2, :] = sum_{i: field(i)=f1} x_i * v[k_i, f2, :]
    sum_{i<j} <v[k_i,f_j], v[k_j,f_i]> x_i x_j
        = 1/2 ( sum_{f1,f2} <S[f1,f2], S[f2,f1]>
                - sum_i x_i^2 ||v[k_i, f_i]||^2 )

which is O(B*K*F^2*D) compute via one MXU batch-matmul over K and
O(B*F^2*D) memory — same-field pairs included, diagonal (i=i)
subtracted, both orderings halved, exactly the i<j sum.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    ffm_field_interaction,
    linear_term,
    masked_x,
    valid_fields,
)


@dataclasses.dataclass(frozen=True)
class FFMModel(AutodiffModel):
    v_dim: int = 4
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "ffm"

    def tables(self) -> list[TableSpec]:
        return [
            TableSpec("w", 1, lambda rng, shape: jnp.zeros(shape, jnp.float32)),
            TableSpec(
                "v",
                self.max_fields * self.v_dim,
                lambda rng, shape: (
                    jax.random.normal(rng, shape, jnp.float32) * self.v_init_scale
                ),
                # v rows are max_fields*v_dim ≈ 156 lanes wide: the
                # one-hot h2*dim traffic exceeds the DMA cost it
                # replaces, so only w rides the MXU hot path
                # (TableSpec.hot rationale)
                hot=False,
                init_kind="normal",
                init_scale=self.v_init_scale,
            ),
        ]

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        f = self.max_fields
        x = masked_x(batch)  # [B, K]
        linear = linear_term(rows["w"], x)

        # negative field ids dropped, matching MVM/Wide&Deep
        valid = valid_fields(batch["slots"], batch["mask"], f)
        x_eff = jnp.where(valid, x, 0.0)
        slot = jnp.clip(batch["slots"], 0, f - 1)  # [B, K]
        # the field-aggregated pairwise identity + its TPU layout
        # discipline live in blocks.ffm_field_interaction (E = F*D
        # stays the minor dim; no [B, K, K, *] pair tensors)
        return linear + ffm_field_interaction(
            rows["v"], x_eff, slot, valid, f, self.v_dim
        )

    def logit_pairwise(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        """Naive O(B*K^2*D) pairwise form — the definition the aggregated
        ``logit`` must match (kept as the equivalence oracle for
        tests/test_extended_models.py; do not use at scale)."""
        b, k = batch["keys"].shape
        f, d = self.max_fields, self.v_dim
        x = batch["vals"] * batch["mask"]  # [B, K]
        linear = jnp.sum(rows["w"][..., 0] * x, axis=-1)

        v = rows["v"].reshape(b, k, f, d)
        slot = jnp.clip(batch["slots"], 0, f - 1)
        valid = (
            (batch["slots"] >= 0) & (batch["slots"] < f) & (batch["mask"] > 0)
        )
        # v_for[b, i, j, :] = v[key_i, field_of_j, :]
        v_for = v[
            jnp.arange(b)[:, None, None],
            jnp.arange(k)[None, :, None],
            slot[:, None, :],
            :,
        ]  # [B, K(i), K(j), D]
        inter = jnp.einsum("bijd,bjid->bij", v_for, v_for)
        xx = x[:, :, None] * x[:, None, :]
        pair_valid = (
            valid[:, :, None]
            & valid[:, None, :]
            & (jnp.arange(k)[:, None] < jnp.arange(k)[None, :])
        )
        return linear + jnp.sum(
            jnp.where(pair_valid, inter * xx, 0.0), axis=(1, 2)
        )

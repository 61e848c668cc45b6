"""Deep & Cross ranker: explicit bounded-degree feature crosses + MLP.

The cascade's RANKING stage (docs/SERVING.md): where the two-tower
retriever is architecturally forbidden from crossing user and item
features (the dot factorization is what makes the index precomputable),
the ranker exists to model exactly those crosses over the few hundred
retrieved candidates.  DCN (Deep & Cross Network) makes the crossing
explicit and cheap:

    x_0     = flattened field-pooled embedding tower  [B, P]
    x_{l+1} = x_0 * (x_l . w_l) + b_l + x_l           (cross stack, L layers)
    h_k     = ReLU(h_{k-1} W_k + b_k),  h_0 = x_0     (deep half, n layers)
    logit   = wide + [x_L ; h_n] W_out + b_out

Each cross layer adds one learned degree of polynomial interaction at
O(P) parameters — the standard alternative to FM/FFM's fixed
second-order forms when the interactions worth modeling are sparse
and data-determined.

Composed from models/blocks.py (field_sum_tower / cross_network /
mlp_stack / linear_term); the wide half and the dense-parameter path
(replicated pytree, plain-SGD via parallel/step.py::apply_dense_sgd) are
exactly wide&deep's — no new train-step machinery.

Depth.  ``cross_layers`` (L) and ``deep_layers`` (n, Config fields of
the same names) choose shapes, not code paths: the deep half is n ReLU
layers of ``hidden`` (blocks.mlp_stack, keys ``w1, b1 ... wn, bn``); at
n = 1 the dense pytree and the logit are bit for bit what they were
before there was a stack.  The paper's Criteo optimum is L = 6 beside
n = 2 layers of 1024 (benchmarks/configs/dcn_ftrl_criteo_tb.json).

Precision.  Every product with a dense matrix (the stack's, ``@ W_out``)
goes through blocks.dense_dot at Precision.HIGHEST, forward and, through
autodiff's transposes, backward: float32 on the TPU, which at default
precision rounds both operands to bfloat16 and misses the benchmark's
reference (PERF.md section 7, PR 38).  The cross layer's ``x_l . w_l`` is
a multiply and a sum, no dot.  The dense half runs under the device
scope ``xf.dense`` (docs/OBSERVABILITY.md).

Departures from the paper (Wang et al., ADKDD 2017, arXiv:1708.05123),
shared with benchmarks/reference/dcn_criteo.py: a sparse linear term
``wide`` over the table ``w`` (the paper has none); no batch
normalisation in the deep half and no gradient clipping (the dense
pytree holds SGD parameters only, no running statistics); FTRL for the
tables and plain SGD for the dense parameters (the paper: Adam at batch
512); every field embedded, the integer ones bucketed first (the wires
ship binary values, so there are no real-valued inputs beside the
embeddings); one embedding width for every field (the paper:
6 * cardinality^(1/4) a field).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    DENSE_SCOPE,
    cross_network,
    dense_dot,
    field_sum_tower,
    flatten_tower,
    linear_term,
    masked_x,
    mlp_stack,
    mlp_stack_init,
)


@dataclasses.dataclass(frozen=True)
class DCNModel(AutodiffModel):
    emb_dim: int = 8
    hidden: int = 64
    cross_layers: int = 2
    deep_layers: int = 1
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "dcn"

    def __post_init__(self) -> None:
        if self.cross_layers < 1:
            raise ValueError(
                f"dcn cross_layers {self.cross_layers} must be >= 1 "
                "(0 layers is wide&deep — use that family)"
            )
        if self.deep_layers < 1:
            raise ValueError(
                f"dcn deep_layers {self.deep_layers} must be >= 1"
            )

    def tables(self) -> list[TableSpec]:
        return [
            TableSpec("w", 1, lambda rng, shape: jnp.zeros(shape, jnp.float32)),
            TableSpec(
                "emb",
                self.emb_dim,
                lambda rng, shape: (
                    jax.random.normal(rng, shape, jnp.float32)
                    * self.v_init_scale
                ),
                init_kind="normal",
                init_scale=self.v_init_scale,
            ),
        ]

    def dense_init(self, rng: jax.Array) -> dict:
        kc, k1, ko = jax.random.split(rng, 3)
        p = self.max_fields * self.emb_dim
        # cross weights start small (each layer perturbs the identity
        # path x_l + ...); biases zero; He for the ReLU deep half.
        return {
            "cross_w": jax.random.normal(
                kc, (self.cross_layers, p), jnp.float32
            ) * jnp.sqrt(1.0 / p),
            "cross_b": jnp.zeros((self.cross_layers, p), jnp.float32),
            **mlp_stack_init(k1, p, self.hidden, self.deep_layers),
            "w_out": jax.random.normal(
                ko, (p + self.hidden, 1), jnp.float32
            ) * jnp.sqrt(1.0 / (p + self.hidden)),
            "b_out": jnp.zeros((1,), jnp.float32),
        }

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "dcn requires dense cross/MLP params"
        x = masked_x(batch)  # [B, K]
        wide = linear_term(rows["w"], x)
        x0 = flatten_tower(field_sum_tower(
            rows["emb"], x, batch["slots"], self.max_fields
        ))  # [B, P]
        with jax.named_scope(DENSE_SCOPE):
            xc = cross_network(x0, dense["cross_w"], dense["cross_b"])
            h = mlp_stack(dense, x0, self.deep_layers)
            out = (
                dense_dot(jnp.concatenate([xc, h], axis=-1), dense["w_out"])
                + dense["b_out"]
            )[:, 0]
        return wide + out

    def dense_matmuls(self) -> list[tuple[int, int]]:
        p, h = self.max_fields * self.emb_dim, self.hidden
        return (
            [(p, h)] + [(h, h)] * (self.deep_layers - 1) + [(p + h, 1)]
            + [(p, 1)] * self.cross_layers  # a cross layer's x_l . w_l
        )

"""FiBiNET: SENET gates over the fields and a bilinear product a field pair.

Huang, Zhang, Zhang, "FiBiNET: Combining Feature Importance and Bilinear
feature Interaction for Click-Through Rate Prediction", RecSys 2019
(arXiv:1905.09433), sections 3.2-3.5.  Where xDeepFM's CIN and AutoInt's
attention weigh interactions, FiBiNET first weighs the FIELDS, by a gate a
field computed from all of the example's fields (a Squeeze-and-Excitation
block), and then interacts every pair of fields through a learned matrix of
its own, once on the embeddings and once on the gated embeddings.  With
``e_i [D]`` the sum of field i's embeddings, m fields, r the reduction ratio:

    z_i  = mean_d e_i[d]                                   (squeeze)
    a    = ReLU(ReLU(z S1) S2)     S1 [m, m // r], S2 [m // r, m], no bias
    v_i  = a_i e_i                                         (re-weight)
    p_ij = (e_i P_ij) * e_j ,  q_ij = (v_i Q_ij) * v_j     i < j, [D, D] each
    c    = [p_ij ; q_ij] over all pairs                    [2 m (m - 1) / 2 D]
    h_n  = ReLU(h_{n-1} W_n + b_n),  h_0 = c
    logit = sum_k w_k x_k + h_n w_out + b_out

(the paper's equations 5-9 with mean pooling in the squeeze and the
Field-Interaction type on both towers).  The paper's Criteo setting (section
4.1.4) is D = 10, r = 3 and 3 layers of 400
(benchmarks/configs/fibinet_ftrl_criteo_tb.json): 780 pairs at 40 field
buckets, c 15 600 wide, 6 718 641 dense parameters.

Composed from models/blocks.py: ``field_sum_tower`` for the fields' vectors,
``senet_bilinear`` (``senet_gates``, ``bilinear_pairs``; scope
``xf.bilinear``) for c, ``mlp_stack`` and ``dense_dot`` for the hidden layers
and the output (scope ``xf.dense``), ``linear_term`` for the wide half.  The
dense pytree (``senet_w1``, ``senet_w2``, ``bil_p``, ``bil_q`` of ``[P, D,
D]``, ``w1, b1 .. wn, bn``, ``w_out``, ``b_out``) is replicated and takes
plain SGD (parallel/step.py::apply_dense_sgd), as DCN's, xDeepFM's and
AutoInt's.  ``senet_reduction``, ``deep_layers`` and ``hidden`` choose shapes,
no code path: Field-Interaction on both towers is the one bilinear kind built.

Memory.  c is ``2 P D`` floats an example, 0.95 GiB at B = 16384, and the
first hidden layer reads it whole; the block makes it from two-dimensional
arrays, whole where they fit and a slice of the batch at a time where they do
not (``blocks.bilinear_slice_rows``, from shapes).

Precision.  Every product with a dense array (the excitation's two, a field's
matrices, the hidden layers, the output) is float32 on the TPU
(Precision.HIGHEST); a pair's second factor is a float32 multiply.

Departures from the paper, shared with
benchmarks/reference/fibinet_criteo.py: the sparse linear term is the table
``w`` (the paper's linear part, but hashed); FTRL for the tables and plain SGD
for the dense arrays where the paper runs Adam at 1e-4; no dropout (the paper:
0.5); the 13 integer fields are bucketed and embedded like the 26 categorical
ones (the wires ship binary values); ``max_fields`` may count a bucket more
than the rows have fields (40 for 39); a field a row does not have (that
bucket always, and a field whose entry the capacity rule dropped) is a zero
vector: its squeeze is 0, its pairs are 0 on both towers and no gradient
reaches their matrices or their rows of ``w1`` (on a row with every field this
is the paper's layer exactly), so no presence mask enters.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    DENSE_SCOPE,
    bilinear_slice_rows,
    dense_dot,
    field_pairs,
    field_sum_tower,
    linear_term,
    masked_x,
    mlp_stack,
    mlp_stack_init,
    senet_bilinear,
)


@dataclasses.dataclass(frozen=True)
class FiBiNETModel(AutodiffModel):
    emb_dim: int = 8
    senet_reduction: int = 3
    hidden: int = 64
    deep_layers: int = 1
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "fibinet"

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

    @property
    def pairs(self) -> int:
        """``P``: the field pairs ``i < j``."""
        return field_pairs(self.max_fields)

    @property
    def squeezed(self) -> int:
        """``m // r``: the excitation's inner width, at least 1."""
        return max(self.max_fields // self.senet_reduction, 1)

    def dense_init(self, rng: jax.Array) -> dict:
        ks1, ks2, kp, kq, k1, ko = jax.random.split(rng, 6)
        m, d, s = self.max_fields, self.emb_dim, self.squeezed
        return {
            # He: the excitation is two ReLU layers without bias
            "senet_w1": jax.random.normal(ks1, (m, s), jnp.float32)
            * jnp.sqrt(2.0 / m),
            "senet_w2": jax.random.normal(ks2, (s, m), jnp.float32)
            * jnp.sqrt(2.0 / s),
            # Glorot for a D x D matrix: a pair's product keeps a vector's scale
            "bil_p": jax.random.normal(kp, (self.pairs, d, d), jnp.float32)
            * jnp.sqrt(1.0 / d),
            "bil_q": jax.random.normal(kq, (self.pairs, d, d), jnp.float32)
            * jnp.sqrt(1.0 / d),
            **mlp_stack_init(k1, 2 * self.pairs * d, self.hidden, self.deep_layers),
            "w_out": jax.random.normal(ko, (self.hidden, 1), jnp.float32)
            * jnp.sqrt(1.0 / self.hidden),
            "b_out": jnp.zeros((1,), jnp.float32),
        }

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "fibinet requires dense SENET/bilinear/DNN params"
        x = masked_x(batch)  # [B, K]
        wide = linear_term(rows["w"], x)
        tower = field_sum_tower(
            rows["emb"], x, batch["slots"], self.max_fields
        )  # [B, m, D]
        c = senet_bilinear(
            dense["senet_w1"], dense["senet_w2"], dense["bil_p"], dense["bil_q"],
            tower, bilinear_slice_rows(x.shape[0], self.emb_dim, self.max_fields),
        )  # [B, 2 P D]
        with jax.named_scope(DENSE_SCOPE):
            h = mlp_stack(dense, c, self.deep_layers)
            out = (dense_dot(h, dense["w_out"]) + dense["b_out"])[:, 0]
        return wide + out

    def dense_matmuls(self) -> list[tuple[int, int]]:
        # the hidden stack and the output product; the block's own products (a
        # pair's [D] x [D, D] on two towers, the excitation's two) are counted
        # where they are read, by the benchmark's bilinear_mxu_roofline
        h = self.hidden
        return (
            [(2 * self.pairs * self.emb_dim, h)] + [(h, h)] * (self.deep_layers - 1)
            + [(h, 1)]
        )

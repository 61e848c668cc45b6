"""xDeepFM: a Compressed Interaction Network beside a plain DNN.

Lian, Zhou, Zhang, Chen, Xie, Sun, "xDeepFM: Combining Explicit and
Implicit Feature Interactions for Recommender Systems", KDD 2018
(arXiv:1803.05170).  Where DCN's cross layer crosses the flattened tower
bit by bit, the CIN crosses it VECTOR-WISE: every feature map of layer k
is a weighted sum of the Hadamard products of a map of layer k - 1 with
a field's embedding, so layer k holds interactions of degree k + 1 and
the embedding dimension D is never mixed.  With ``X^0 [m, D]`` the
field tower (m fields) and ``H_0 = m``:

    X^k[h, :] = sum_{i < H_{k-1}} sum_{j < m} W^k[h, i, j] (X^{k-1}[i, :] * X^0[j, :])
    p^k[h]    = sum_d X^k[h, d]                 p+ = [p^1 ; ... ; p^L]
    h_n       = ReLU(h_{n-1} W_n + b_n),  h_0 = flatten(X^0)
    logit     = sum_i w_i x_i  +  [p+ ; h_n] w_out + b_out

(the paper's equations 6-8 with the identity on the maps, which its
section 4.4 reports best, and every map of every layer pooled into the
output).  The paper's Criteo setting (section 4.1) is D = 10, L = 3
layers of 200 maps beside 2 ReLU layers of 400
(benchmarks/configs/xdeepfm_ftrl_criteo_tb.json).

Composed from models/blocks.py: ``field_sum_tower`` for X^0, the CIN
block ``cin_stack`` (scope ``xf.cin``), ``mlp_stack`` and ``dense_dot``
for the DNN and the output (scope ``xf.dense``), ``linear_term`` for the
wide half.  The dense pytree (``cin_w1 .. cin_wL`` of ``[cin_maps,
H_{k-1}, max_fields]``, ``w1, b1 .. wn, bn``, ``w_out``, ``b_out``) is
replicated and takes plain SGD (parallel/step.py::apply_dense_sgd), as
wide&deep's and DCN's.  ``cross_layers`` (L, the explicit stack's depth
as for DCN), ``cin_maps``, ``deep_layers`` and ``hidden`` choose shapes,
no code path.

Memory.  A layer's pair tensor ``X^{k-1} (x) X^0`` is ``H_{k-1} m D``
floats an example: 80 000 at the paper's sizes, 5.24 GB a layer at
B = 16384.  ``cin_stack`` holds it for a slice of the batch at a time,
forward and backward (``blocks.cin_slice_rows``, from shapes).

Precision.  Every contraction with a ``cin_w`` and every product with a
dense matrix is float32 on the TPU (Precision.HIGHEST); the pair product
is a float32 multiply.

Departures from the paper, shared with
benchmarks/reference/xdeepfm_criteo.py: the sparse linear term is the
table ``w`` (the paper's linear part, but hashed); FTRL for the tables
and plain SGD for the dense arrays where the paper runs Adam at 1e-3; no
L2 penalty (the paper: 1e-4) and no dropout; the 13 integer fields are
bucketed and embedded like the 26 categorical ones (the wires ship
binary values); ``max_fields`` may count one bucket more than the rows
have fields (40 for 39): that row of X^0 is zero, and no gradient
reaches its weights on either side (``cin_wk[:, :, 39]`` and
``cin_w1[:, 39, :]``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    DENSE_SCOPE,
    cin_slice_rows,
    cin_stack,
    dense_dot,
    field_sum_tower,
    flatten_tower,
    linear_term,
    masked_x,
    mlp_stack,
    mlp_stack_init,
)


@dataclasses.dataclass(frozen=True)
class XDeepFMModel(AutodiffModel):
    emb_dim: int = 8
    cin_maps: int = 16
    cross_layers: int = 2
    hidden: int = 64
    deep_layers: int = 1
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "xdeepfm"

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

    def cin_widths(self) -> list[tuple[int, int]]:
        """``(H_{k-1}, H_k)`` of every CIN layer, ``H_0 = max_fields``."""
        widths = [self.max_fields] + [self.cin_maps] * self.cross_layers
        return list(zip(widths[:-1], widths[1:]))

    def dense_init(self, rng: jax.Array) -> dict:
        kc, k1, ko = jax.random.split(rng, 3)
        m = self.max_fields
        out_in = self.cross_layers * self.cin_maps + self.hidden
        # a map sums H_{k-1} * m pair products: 1 / fan-in keeps its scale
        cin = {
            f"cin_w{k}": jax.random.normal(
                jax.random.fold_in(kc, k), (h_out, h_in, m), jnp.float32
            ) * jnp.sqrt(1.0 / (h_in * m))
            for k, (h_in, h_out) in enumerate(self.cin_widths(), start=1)
        }
        return {
            **cin,
            **mlp_stack_init(k1, m * self.emb_dim, self.hidden, self.deep_layers),
            "w_out": jax.random.normal(ko, (out_in, 1), jnp.float32)
            * jnp.sqrt(1.0 / out_in),
            "b_out": jnp.zeros((1,), jnp.float32),
        }

    def cin_slice_rows(self, batch: int) -> int:
        """Examples a slice of the CIN holds at this batch size."""
        return cin_slice_rows(batch, self.emb_dim, self.max_fields, self.cin_maps)

    def dense_counters(self, batch: int) -> dict[str, int]:
        return {"dense.cin_slice_rows": self.cin_slice_rows(batch)}

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "xdeepfm requires dense CIN/DNN params"
        x = masked_x(batch)  # [B, K]
        wide = linear_term(rows["w"], x)
        tower = field_sum_tower(
            rows["emb"], x, batch["slots"], self.max_fields
        )  # [B, m, D]
        pooled = cin_stack(
            [dense[f"cin_w{k}"] for k in range(1, self.cross_layers + 1)],
            tower, self.cin_slice_rows(x.shape[0]),
        )  # [B, L * maps]
        with jax.named_scope(DENSE_SCOPE):
            h = mlp_stack(dense, flatten_tower(tower), self.deep_layers)
            out = (
                dense_dot(jnp.concatenate([pooled, h], axis=-1), dense["w_out"])
                + dense["b_out"]
            )[:, 0]
        return wide + out

    def dense_matmuls(self) -> list[tuple[int, int]]:
        # a CIN layer is [B * D, H_{k-1} * m] x [H_{k-1} * m, H_k]: B * D
        # rows, declared with D folded into k so that 6 B k n stays exact
        p, h = self.max_fields * self.emb_dim, self.hidden
        return (
            [(p * h_in, h_out) for h_in, h_out in self.cin_widths()]
            + [(p, h)] + [(h, h)] * (self.deep_layers - 1)
            + [(self.cross_layers * self.cin_maps + h, 1)]
        )

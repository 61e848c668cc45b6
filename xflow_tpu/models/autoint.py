"""AutoInt: field interactions learned by multi-head self-attention.

Song, Shi, Xiao, Duan, Xu, Zhang, Tang, "AutoInt: Automatic Feature
Interaction Learning via Self-Attentive Neural Networks", CIKM 2019
(arXiv:1810.11921), sections 4.3-4.5.  Where DCN crosses the flattened
tower bit by bit and xDeepFM's CIN crosses it vector by vector with FIXED
weights, an interacting layer lets every field choose, per example, which
other fields it combines with: the fields of one row attend to each
other.  With ``e_m`` the m-th field's vector entering layer l (``d_1 =
emb_dim``, ``d_l = H d'`` after), heads h = 1..H of width d':

    psi^h(m, k)  = <W_Q^h e_m, W_K^h e_k>
    alpha^h(m, k) = exp psi^h(m, k) / sum_{l present} exp psi^h(m, l)
    e~_m^h       = sum_{k present} alpha^h(m, k) W_V^h e_k
    e_m^Res      = ReLU([e~_m^1 ; ... ; e~_m^H] + W_Res e_m)
    logit        = w_out^T [e_1^Res ; ... ; e_M^Res] + b_out     after L layers

(the paper's equations 5-9; its eq. 5 has no 1 / sqrt(d')).  The paper's
Criteo setting (section 5.1.4) is d = 16, L = 3 layers of H = 2 heads of
d' = 32 (benchmarks/configs/autoint_ftrl_criteo_tb.json).  This is the
plain AutoInt, not AutoInt+ (whose feed-forward half would be
``mlp_stack`` beside it).

Composed from models/blocks.py: ``field_sum_tower`` for the fields'
vectors, ``field_presence`` for which fields a row has,
``field_attention_stack`` (scope ``xf.attn``) and ``dense_dot`` for the
output (scope ``xf.dense``).  ONE table, ``emb``: no first-order term.
The dense pytree (``attn_q1 .. attn_qL``, ``attn_k*``, ``attn_v*``,
``attn_r*`` of ``[d_l, H d']``, ``w_out [max_fields H d', 1]``, ``b_out``)
is replicated and takes plain SGD (parallel/step.py::apply_dense_sgd), as
DCN's and xDeepFM's.  ``cross_layers`` (L, the explicit-interaction
stack's depth as for DCN and xDeepFM), ``attn_heads`` and ``attn_dim``
choose shapes, no code path.

Memory.  A layer's projections, scores and weights are 16 000 floats an
example at the paper's sizes: 3.1 GB over three layers at B = 16384 as
numbers.  The stack holds them for a slice of the batch at a time,
forward and backward (``blocks.attn_slice_rows``, from shapes), the
slice's examples minor-most: on the TPU's 128 lanes, where the fields
(40), a head (32) or a layer's width (64) would leave most of a vector
register empty.

Precision.  The projections (one product a layer, on the MXU) and the
output are float32 on the TPU (Precision.HIGHEST); the per-example scores
and weighted sums are float32 multiplies and sums (``blocks._lane_contract``:
on the TPU a Mosaic kernel), and so is the softmax.

Departures from the paper, shared with
benchmarks/reference/autoint_criteo.py: FTRL for the table and plain SGD
for the dense arrays where the paper runs Adam; no dropout (the paper
uses none on Criteo); the 13 integer fields are bucketed and embedded
like the 26 categorical ones (the paper multiplies a field's vector by
the logged value; the wires ship binary values); ``max_fields`` may count
a bucket more than the rows have fields (40 for 39); a field a row does
not have (that bucket always, and a field whose entry the capacity rule
dropped) neither attends nor is attended to: its key takes weight 0 and
its own ``e^Res`` is 0, so its slice of ``w_out`` sees no gradient (on a
row with every field this is the paper's layer exactly); the paper's
equations, not its released code (which, from memory, puts a ReLU on the
projections).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    DENSE_SCOPE,
    attn_slice_rows,
    dense_dot,
    field_attention_stack,
    field_presence,
    field_sum_tower,
    flatten_tower,
    masked_x,
)

_PROJECTIONS = ("q", "k", "v", "r")  # W_Q, W_K, W_V, W_Res of a layer


@dataclasses.dataclass(frozen=True)
class AutoIntModel(AutodiffModel):
    emb_dim: int = 8
    attn_heads: int = 2
    attn_dim: int = 8
    cross_layers: int = 2
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "autoint"

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
            ),
        ]

    @property
    def width(self) -> int:
        """``H d'``: a field's vector after an interacting layer."""
        return self.attn_heads * self.attn_dim

    def layer_inputs(self) -> list[int]:
        """``d_l`` of every interacting layer."""
        return [self.emb_dim] + [self.width] * (self.cross_layers - 1)

    def dense_init(self, rng: jax.Array) -> dict:
        ka, ko = jax.random.split(rng)
        out_in = self.max_fields * self.width
        dense = {}
        for layer, d_in in enumerate(self.layer_inputs(), start=1):
            for i, name in enumerate(_PROJECTIONS):
                key = jax.random.fold_in(ka, 4 * layer + i)
                # Glorot: the projections keep a vector's scale both ways
                dense[f"attn_{name}{layer}"] = jax.random.normal(
                    key, (d_in, self.width), jnp.float32
                ) * jnp.sqrt(2.0 / (d_in + self.width))
        dense["w_out"] = jax.random.normal(
            ko, (out_in, 1), jnp.float32
        ) * jnp.sqrt(1.0 / out_in)
        dense["b_out"] = jnp.zeros((1,), jnp.float32)
        return dense

    def attn_slice_rows(self, batch: int) -> int:
        """Examples a slice of the interacting layers holds at this batch."""
        return attn_slice_rows(
            batch, self.max_fields, self.attn_heads, self.attn_dim,
            self.cross_layers,
        )

    def dense_counters(self, batch: int) -> dict[str, int]:
        m, heads, layers = self.max_fields, self.attn_heads, self.cross_layers
        # the block's products: the declared ones less the output's (xf.dense's)
        per_example = sum(k * n for k, n in self.dense_matmuls()[:-1])
        return {
            # 6 B sum_l [4 m d_l H d' + 2 H m m d'], forward and backward
            "dense.attn_flops": 6 * batch * per_example,
            # what a step's scores and weights WOULD take whole
            "dense.attn_score_bytes": 4 * batch * layers * 2 * heads * m * m,
            "dense.attn_slice_rows": self.attn_slice_rows(batch),
        }

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "autoint requires dense attention params"
        x = masked_x(batch)  # [B, K]
        tower = field_sum_tower(
            rows["emb"], x, batch["slots"], self.max_fields
        )  # [B, m, d]
        present = field_presence(x, batch["slots"], self.max_fields)  # [B, m]
        e = field_attention_stack(
            [
                tuple(dense[f"attn_{name}{layer}"] for name in _PROJECTIONS)
                for layer in range(1, self.cross_layers + 1)
            ],
            tower, present, self.attn_heads, self.attn_slice_rows(x.shape[0]),
        )  # [B, m, H d']
        with jax.named_scope(DENSE_SCOPE):
            out = dense_dot(flatten_tower(e), dense["w_out"]) + dense["b_out"]
        return out[:, 0]

    def dense_matmuls(self) -> list[tuple[int, int]]:
        # a projection is [B m, d_l] x [d_l, H d'], B m rows; the scores
        # and the weighted sum are per-example products of two
        # ACTIVATIONS ([m, d'] x [d', m] and [m, m] x [m, d'] a head):
        # declared with the rows folded into k so that 6 B k n stays exact.
        # Unlike the one-hot field contraction they multiply, so they count
        m, heads, head_dim = self.max_fields, self.attn_heads, self.attn_dim
        per_layer = [
            (heads * m * head_dim, m),  # scores
            (heads * m * m, head_dim),  # weighted sum
        ]
        return [
            pair
            for d_in in self.layer_inputs()
            for pair in [(m * d_in, self.width)] * 4 + per_layer
        ] + [(m * self.width, 1)]

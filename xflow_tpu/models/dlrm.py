"""DLRM: a stack over the real-valued inputs, embeddings, their pairwise
dots, a stack over those.

Naumov et al., "Deep Learning Recommendation Model for Personalization and
Recommendation Systems" (arXiv:1906.00091); the Criteo-Terabyte setting of
``facebookresearch/dlrm``'s ``bench/dlrm_s_criteo_terabyte.sh``
(``--arch-sparse-feature-size=128 --arch-mlp-bot=13-512-256-128
--arch-mlp-top=1024-1024-512-256-1``, interaction ``dot`` without
self-interaction), which is also MLPerf Training's recommendation network.
Where every other family here embeds ALL of a row's fields, DLRM reads the
numeric fields' VALUES: with ``x_f`` the value of numeric field f (the
script feeds ``log(1 + count)``; here whatever the row's token holds) and
``E`` the table:

    h_0 = x ;  h_l = ReLU(h_{l-1} A_l + a_l)        bottom stack ;  z = h_last
    e_j = sum_{k in field j} E[key_k] x_k            one a categorical field
    T   = [z ; e_1 ; ... ; e_m] ;   Z = T T^T
    r   = [z ; Z_ij for i > j]
    g_0 = r ;  g_l = ReLU(g_{l-1} C_l + c_l)        top stack
    logit = g_last c_out + b_out

(ReLU on the bottom stack's last layer too, as the script's).

Which fields.  ``numeric_fields`` (Config) fields are real-valued: ids ``0
.. numeric_fields - 1``, libffm's ``field:index:value`` with one index a
field.  Such an entry stays a table entry on every path (its row is
gathered, takes gradient 0 and the optimizer's no-gradient step); the model
reads its value, picked out of the batch's values by field id as
``field_contract`` picks rows (a row without the field reads 0).  ``max_fields
- numeric_fields`` vectors interact: z, which stands in the tower as the
LAST field ``max_fields - 1``, and the embeddings of the categorical fields
``numeric_fields .. max_fields - 2`` (39 fields under ``max_fields`` 40 and
13 numeric: 26 embeddings and z, the 351 pairs of 27 vectors).  A
categorical field a row has no entry of is a zero vector, and its dots are
0; an entry of field ``max_fields - 1`` or beyond is ignored, as every
family ignores a field id outside its buckets.

Composed from models/blocks.py: ``field_contract`` for the values and the
embeddings' field sums, ``mlp_stack`` (scope ``xf.dense``) for both stacks,
``pairwise_dots`` (scope ``xf.interact``), ``dense_dot`` for the output.  ONE
table, ``emb``: no first-order term.  The dense pytree (``bot_w1, bot_b1 ..``,
``top_w1, top_b1 ..``, ``w_out [top_last, 1]``, ``b_out``) is replicated and
takes plain SGD (parallel/step.py::apply_dense_sgd), which IS the published
optimizer of the dense half.  ``mlp_bottom`` / ``mlp_top`` choose shapes, no
code path.

Departures from the script, shared with
benchmarks/reference/dlrm_criteo.py: FTRL for the table where the script
runs SGD; ONE hashed table for its 26; a field's embedding is the SUM of its
entries' rows times their values (the script's bag sum; one id a field in
its data); He-normal weights and zero biases where the script draws both
from normals of its own; the values arrive transformed (the script applies
``log(1 + x)`` itself).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import AutodiffModel, BatchArrays, TableSpec
from xflow_tpu.models.blocks import (
    DENSE_SCOPE,
    dense_dot,
    field_contract,
    masked_x,
    mlp_stack,
    mlp_stack_init,
    pairwise_dots,
    vector_pairs,
)


@dataclasses.dataclass(frozen=True)
class DLRMModel(AutodiffModel):
    emb_dim: int = 8
    numeric_fields: int = 1
    mlp_bottom: tuple[int, ...] = (16, 8)
    mlp_top: tuple[int, ...] = (32, 16)
    max_fields: int = 32
    v_init_scale: float = 1e-2
    name: str = "dlrm"

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
    def vectors(self) -> int:
        """How many vectors of ``emb_dim`` interact: z and one a
        categorical field."""
        return self.max_fields - self.numeric_fields

    @property
    def top_in(self) -> int:
        """The top stack's input: z beside the pairs' dots."""
        return self.emb_dim + vector_pairs(self.vectors)

    def dense_init(self, rng: jax.Array) -> dict:
        kb, kt, ko = jax.random.split(rng, 3)
        last = self.mlp_top[-1]
        return {
            **mlp_stack_init(kb, self.numeric_fields, self.mlp_bottom, prefix="bot_"),
            **mlp_stack_init(kt, self.top_in, self.mlp_top, prefix="top_"),
            "w_out": jax.random.normal(ko, (last, 1), jnp.float32)
            * jnp.sqrt(1.0 / last),
            "b_out": jnp.zeros((1,), jnp.float32),
        }

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        assert dense is not None, "dlrm requires dense MLP params"
        x = masked_x(batch)  # [B, K]
        nf = self.numeric_fields
        slots = batch["slots"]
        # the numeric fields' values, by field id: [B, nf]
        numeric = field_contract(
            jax.nn.one_hot(slots, nf, dtype=x.dtype), x[..., None]
        )[..., 0]
        # the categorical fields' embeddings, by field id: [B, n - 1, d]
        emb = field_contract(
            jax.nn.one_hot(slots - nf, self.vectors - 1, dtype=x.dtype),
            rows["emb"] * x[..., None],
        )
        with jax.named_scope(DENSE_SCOPE):
            z = mlp_stack(dense, numeric, len(self.mlp_bottom), prefix="bot_")
        pairs = pairwise_dots(jnp.concatenate([z[:, None, :], emb], axis=1))
        with jax.named_scope(DENSE_SCOPE):
            g = mlp_stack(
                dense, jnp.concatenate([z, pairs], axis=-1),
                len(self.mlp_top), prefix="top_",
            )
            out = dense_dot(g, dense["w_out"]) + dense["b_out"]
        return out[:, 0]

    def dense_matmuls(self) -> list[tuple[int, int]]:
        # both stacks and the output, then the pairs' dots: per example a
        # product of two ACTIVATIONS ([n, d] x [d, n]), declared with the
        # rows folded into k so that 6 B k n stays exact; it multiplies, so
        # it counts (the one-hot field contractions do not)
        bottom = (self.numeric_fields,) + self.mlp_bottom
        top = (self.top_in,) + self.mlp_top + (1,)
        return (
            list(zip(bottom, bottom[1:])) + list(zip(top, top[1:]))
            + [(self.vectors * self.emb_dim, self.vectors)]
        )

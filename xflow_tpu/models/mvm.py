"""Multi-View Machine (reference: src/model/mvm/mvm_worker.{h,cc}).

Per factor d, the reference sums v within each field (fgid/slot), then
multiplies across fields, then sums over factors (mvm_worker.cc:67-95):

    reference forward:  logit = sum_d  prod_s ( sum_{i in s} v_id )
    reference backward: grad_v_id = prod_s(...) / (1 + slotsum_{s(i),d})
                        (0 when the slot sum is 0, mvm_worker.cc:155-156)

The reference's forward multiplies the bare slot sum but its backward
divides by (1 + slot sum) — a forward/backward mismatch flagged in the
SURVEY quirks ledger with the recommendation to fix both sides to the
``1 + sum`` form (which also matches the MVM paper's view-augmentation
with a constant-1 feature, and makes empty fields contribute a neutral
factor 1).  We implement the fixed, consistent form, CENTERED:

    logit = sum_d [ prod_s (1 + slotsum_sd)  -  1 ]
    grad_v_id = x_i * prod_s(1 + slotsum_sd) / (1 + slotsum_{s(i),d})

The ``- 1`` per factor removes the structural baseline: at init every
slotsum is ~0, so the uncentered product is ~1 per factor and the logit
starts at +v_dim (sigmoid ~0.9999) — measured on the convergence
dataset, the uncentered form spends its first epochs burning that bias
down (test logloss 0.70 after an epoch vs 0.58 base rate) instead of
learning.  The shift is a constant, so gradients are identical; it is
exactly a fixed -v_dim bias.  (The reference's bare-product forward has
the opposite degeneracy: products of ~N(0, 1e-2) slot sums vanish to
~0 and freeze MVM at sigma(0)=0.5 with ~0 gradients.)

This is the one intentional numeric divergence from the reference for
MVM; documented here and exercised in tests/test_models.py.

Field handling: the reference sizes per-sample slot arrays from the max
fgid seen (mvm_worker.cc:225-243); under static shapes fields are fixed
to ``max_fields`` and features with fgid >= max_fields are ignored
(config.max_fields).  MVM uses only the v table (store 1,
mvm_worker.h:38); v rows init N(0,1)*1e-2 like FM.

The backward's ``1 + slotsum_{s(i),d}`` of each entry is selected by
contracting the forward's one-hot [B, K, S] with ``1 + slotsum``
[B, S, D] on the MXU (blocks.field_pick), not gathered by index: the
TPU prices a gather per index, and B*K = 5.2 M of them were 112 of
the benchmark cell's 390 ms step (PERF.md section 6, PR 33), where the
contraction pays per field what the forward already pays.  It is the
same float32 number, not a nearby one, because the contraction asks
for Precision.HIGHEST: one operand is 0/1, so the bfloat16 pieces of
the other add back to the value itself.  That precision is not
optional: at the TPU's default a float32 dot rounds ``1 + s`` to
bfloat16, 8 bits of a number that is 1.00x, and the gradient is
garbage.  benchmarks/reference/mvm.py keeps its gather by index and
judges this form on the chip.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from xflow_tpu.models.base import BatchArrays, TableSpec
from xflow_tpu.models.blocks import field_pick, masked_x, mvm_slot_terms

_GUARD_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class MVMModel:
    v_dim: int = 10
    v_init_scale: float = 1e-2
    max_fields: int = 32
    name: str = "mvm"

    def tables(self) -> list[TableSpec]:
        return [
            TableSpec(
                "v",
                self.v_dim,
                lambda rng, shape: (
                    jax.random.normal(rng, shape, jnp.float32) * self.v_init_scale
                ),
                init_kind="normal",
                init_scale=self.v_init_scale,
            )
        ]

    def _slot_terms(
        self, rows: dict[str, jax.Array], batch: BatchArrays
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Returns (one_plus_slotsum [B, S, D], prod over S [B, D],
        the one-hot [B, K, S]) — blocks.mvm_slot_terms, bitwise the
        pre-refactor expression."""
        return mvm_slot_terms(
            rows["v"], masked_x(batch), batch["slots"], self.max_fields
        )

    def logit(self, rows: dict[str, jax.Array], batch: BatchArrays) -> jax.Array:
        _, prod, _ = self._slot_terms(rows, batch)
        # centered: remove the structural +v_dim baseline (docstring)
        return jnp.sum(prod - 1.0, axis=-1)

    def grad_logit(
        self, rows: dict[str, jax.Array], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        x = masked_x(batch)  # [B, K]
        one_plus, prod, onehot = self._slot_terms(rows, batch)
        # each entry's own field factor; 0 for a slot outside
        # [0, max_fields), whose one-hot row is all zero
        own = field_pick(onehot, one_plus)  # [B, K, D]
        safe = jnp.where(jnp.abs(own) < _GUARD_EPS, 1.0, own)
        grad_v = jnp.where(
            jnp.abs(own) < _GUARD_EPS,
            0.0,  # guard mirrors the reference zeroing at mvm_worker.cc:156
            prod[:, None, :] / safe,
        ) * x[..., None]
        # match the forward's one-hot semantics exactly: slots outside
        # [0, max_fields) contribute nothing there (zero one-hot row),
        # so they must get zero gradient here too (the guard above
        # already zeroes their all-zero pick; the mask says why)
        valid = (
            (batch["slots"] >= 0) & (batch["slots"] < self.max_fields)
        )[..., None]
        return {"v": jnp.where(valid, grad_v, 0.0)}

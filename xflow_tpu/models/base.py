"""Model protocol: pluggable losses over gathered sparse rows.

A model declares its parameter tables (the reference's "stores": LR
uses store 0 (w) only, FM stores 0+1 (w, v), MVM store 1 (v) only —
server.h:23-28, lr_worker.h:38, fm_worker.h:37-38, mvm_worker.h:38) and
provides, for a batch whose rows are already gathered to [B, K, D]
blocks:

* ``logit(rows, batch) -> [B]`` — the pre-sigmoid score;
* ``grad_logit(rows, batch) -> {table: [B, K, D]}`` — d logit / d row
  entry, per occurrence.

Gradients are explicit, not autodiff, because the reference's FM
backward is *not* the true gradient of its forward (fm_worker.cc:82 vs
:140-142 — the ½ factor is dropped in forward only) and parity requires
reproducing that; see models/fm.py.

The train step turns these into parameter updates:
``g_occurrence = (sigma(logit) - y) * weight / num_real * grad_logit``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Protocol

import jax

# Batch as a jit-friendly pytree: keys/slots/vals/mask [B,K], labels/weights [B].
BatchArrays = dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class TableSpec:
    name: str
    dim: int  # row width (1 for w; v_dim for latent factors)
    init: Callable[[jax.Array, tuple[int, int]], jax.Array]  # (rng, shape) -> array
    # Whether this table's hot-plane occurrences ride the two-level
    # one-hot MXU path (ops/hot.py).  The MXU route moves
    # M*(h1 + h2*dim) one-hot elements per M occurrences — a win for
    # narrow rows, but for very wide rows (FFM's v: max_fields*v_dim
    # ≈ 156 lanes) the h2*dim term makes it slower than the ~100 ns
    # DMA descriptor it replaces.  hot=False keeps THIS table's hot
    # occurrences on plain gather/scatter while other tables (and the
    # batch steering/remap) still use the hot machinery — e.g. FFM
    # takes the MXU win on its scalar w and leaves v on DMA, halving
    # its per-occurrence descriptor count.
    hot: bool = True
    # Declarative row-init distribution, the LAZY counterpart of
    # ``init``: the tiered parameter store (store/cold.py) materializes
    # a row only when it is first touched, so the initial value of row
    # r must be computable per-row, deterministically, and independent
    # of the table size — a [T, D] init draw is exactly the full-table
    # materialization the store exists to avoid at T=2^28.
    # "zeros" covers w tables; "normal" is N(0,1)*init_scale per entry
    # (the reference's lazy server-side v init, ftrl.h:113-120 — which
    # was itself per-row-on-first-touch, so the store reproduces the
    # REFERENCE semantics more literally than the eager ``init`` does).
    init_kind: str = "zeros"  # {"zeros", "normal"}
    init_scale: float = 0.0


class Model(Protocol):
    name: str

    def tables(self) -> list[TableSpec]:
        ...

    def logit(self, rows: dict[str, jax.Array], batch: BatchArrays) -> jax.Array:
        ...

    def grad_logit(
        self, rows: dict[str, jax.Array], batch: BatchArrays
    ) -> dict[str, jax.Array]:
        ...


class AutodiffModel:
    """Base for models without reference gradient quirks (FFM,
    wide&deep): define ``logit`` only — the train step derives
    per-occurrence table gradients and dense-parameter gradients with
    jax.grad.  May also own dense (non-table, replicated) parameters,
    e.g. MLP weights, via ``dense_init``."""

    #: marker the train step dispatches on
    autodiff = True

    def dense_init(self, rng: jax.Array) -> dict:
        """Replicated dense parameter pytree ({} if none)."""
        return {}

    def dense_matmuls(self) -> list[tuple[int, int]]:
        """``(k, n)`` of every ``[B, k] x [k, n]`` product with a dense
        parameter in one forward pass ([] if none): what the step books
        as ``dense.matmul_flops`` (parallel/step.py::_book_wire)."""
        return []

    def dense_counters(self, batch: int) -> dict[str, int]:
        """What else of its dense half a family wants on the ``_wire``
        row, from shapes at this batch size, by counter name
        (``dense.<what>``; {} if nothing): the step books each once a
        batch and the trainer writes it as ``dense_<what>``."""
        return {}

    def logit(
        self,
        rows: dict[str, jax.Array],
        batch: BatchArrays,
        dense: dict | None = None,
    ) -> jax.Array:
        raise NotImplementedError

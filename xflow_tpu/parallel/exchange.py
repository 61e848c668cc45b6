"""Pull and Push between the batch's shards and the table's row blocks,
written from one chip's point of view.

On a mesh of n > 1 devices every chip holds one contiguous block of
T/n table rows and one shard of B/n examples (parallel/mesh.py).  A
train step needs the rows of keys that live in other chips' blocks
(ps-lite's Pull) and must deliver their gradients there (Push).  The
functions here are the collectives of that exchange, to be called
inside a ``jax.shard_map`` over ``DATA_AXIS`` (parallel/step.py builds
it): each is ONE collective whose operand is of the order of the batch
(B x K x D) or of the hot head (H x D), never of the table, and none
sits inside a loop.

    pull:  all_batch(keys) -> every chip gathers the whole batch's rows
           from its own block, out-of-block keys reading zero rows ->
           to_batch_shards(rows): summed over chips (one chip holds each
           row, the others add exact zeros) and handed back as batch
           shards.
    push:  all_batch(keys), all_batch(gradient rows) -> every chip
           scatter-adds into its own block, out-of-block keys dropped.
    head:  read_head once a step (the hot head's rows, replicated);
           sum_head once (the head's gradient, summed over the batch
           shards), head_part picks this block's rows of it.

This is the simple form: every chip looks at all B x K slots.  A routed
all-to-all with a capacity per chip would divide that by n (PERF.md
section 7).

Everything here carries the scope ``xf.exchange`` on the profiler's
timeline (docs/OBSERVABILITY.md "Scopes and spans").
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from xflow_tpu.parallel.mesh import DATA_AXIS

SCOPE = "xf.exchange"


@jax.named_scope(SCOPE)
def all_batch(x: jax.Array) -> jax.Array:
    """The whole batch's copy of an array sharded on its leading (batch)
    axis: one all-gather."""
    return jax.lax.all_gather(x, DATA_AXIS, axis=0, tiled=True)


@jax.named_scope(SCOPE)
def to_batch_shards(x: jax.Array) -> jax.Array:
    """Sum a whole-batch array over the chips and keep this chip's batch
    shard of the sum: one reduce-scatter."""
    return jax.lax.psum_scatter(
        x, DATA_AXIS, scatter_dimension=0, tiled=True
    )


def block_index(keys: jax.Array, block_rows: int) -> jax.Array:
    """Table rows as indices into this chip's block; a row of another
    block (and the pad sentinel T) becomes ``block_rows``, one past the
    block's end, which a fill-mode gather reads as zeros and a drop-mode
    scatter drops.  Never negative: JAX would wrap a negative index."""
    local = keys - jax.lax.axis_index(DATA_AXIS) * block_rows
    return jnp.where(
        (local >= 0) & (local < block_rows), local, jnp.int32(block_rows)
    )


@jax.named_scope(SCOPE)
def read_head(block: jax.Array, hot_size: int) -> jax.Array:
    """Table rows [0, hot_size), whole on every chip: one all-gather of
    each block's first min(hot_size, T/n) rows (the head lies in the
    first block unless the table is tiny)."""
    m = min(hot_size, block.shape[0])
    return jax.lax.all_gather(block[:m], DATA_AXIS, axis=0, tiled=True)[
        :hot_size
    ]


@jax.named_scope(SCOPE)
def sum_head(ghot: jax.Array) -> jax.Array:
    """The head's gradient summed over the batch shards: one
    all-reduce."""
    return jax.lax.psum(ghot, DATA_AXIS)


def head_part(ghot: jax.Array, block_rows: int) -> jax.Array:
    """The rows of the (whole) head gradient that lie in this chip's
    block, as its first min(H, T/n) rows; zeros on a chip that holds
    none of the head."""
    m = min(ghot.shape[0], block_rows)
    start = jax.lax.axis_index(DATA_AXIS) * block_rows
    part = jax.lax.dynamic_slice_in_dim(ghot, start, m, axis=0)
    return jnp.where(start < ghot.shape[0], part, 0.0)


# -- reading a compiled program back -----------------------------------------

_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([^ ]+) \(.*\{$")
_CALLED_RE = re.compile(
    r"\b(?:body|condition|calls|to_apply|branch_computations)="
    r"(?:\{([^}]*)\}|(%?[\w.\-]+))"
)
COLLECTIVE_OPS = (
    "all-reduce|all-gather|all-to-all|reduce-scatter|"
    "collective-permute|collective-broadcast"
)
_COLLECTIVE_RE = re.compile(
    r" = (?P<type>\(?[a-z0-9]+\[[0-9,]*\][^=]*?) "
    rf"(?P<op>{COLLECTIVE_OPS})(?:-start)?\("
)
_SHAPE_RE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")
_CHAIN_RE = re.compile(r'chain_id="([0-9]+)"')


def collectives_in(hlo_text: str) -> list[dict]:
    """Every collective of a compiled module's text: ``op``, its result
    ``type`` as printed, ``rows`` (the largest leading dimension among
    its results), ``in_loop`` (whether it sits, at any depth of calls,
    in the body or condition of a ``while``) and ``pieces``.  What the
    structural tests and scripts/probe_mesh_step.py hold the step's
    promise to: no collective in a loop, none with the table's rows.

    The TPU's compiler may run ONE asynchronous collective as several
    instructions that share a ``chain_id`` (its start, its completion,
    and continuations it fuses into the compute around them, a
    neighbouring loop's body included, so that the transfer overlaps
    that loop).  Such a chain is one entry here with ``pieces`` > 1, and
    is ``in_loop`` only if every piece is: a collective issued anew by
    each iteration has no piece outside its loop."""
    computations: dict[str, list[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head:
            current = computations.setdefault(head.group(1), [])
        elif current is not None:
            current.append(line)

    def called(line: str) -> list[str]:
        return [
            name.strip().lstrip("%")
            for several, one in _CALLED_RE.findall(line)
            for name in (several or one).split(",")
        ]

    in_loop: set[str] = set()
    todo = [
        name
        for lines in computations.values()
        for line in lines if " while(" in line
        for name in called(line)
    ]
    while todo:
        name = todo.pop()
        if name in in_loop or name not in computations:
            continue
        in_loop.add(name)
        todo.extend(n for line in computations[name] for n in called(line))
    out: list[dict] = []
    chains: dict[str, dict] = {}
    for name, lines in computations.items():
        for line in lines:
            m = _COLLECTIVE_RE.search(line)
            if not m:
                continue
            dims = [
                int(d.split(",")[0]) for d in _SHAPE_RE.findall(m.group("type"))
                if d
            ]
            found = {
                "op": m.group("op"), "type": m.group("type").strip(),
                "rows": max(dims, default=1), "in_loop": name in in_loop,
                "pieces": 1,
            }
            chain = _CHAIN_RE.search(line)
            whole = chains.get(chain.group(1)) if chain else None
            if whole is None:
                out.append(found)
                if chain:
                    chains[chain.group(1)] = found
            else:
                whole["pieces"] += 1
                whole["rows"] = max(whole["rows"], found["rows"])
                whole["in_loop"] = whole["in_loop"] and found["in_loop"]
    return out

"""Composable model blocks — embedding towers + interaction blocks.

Five CTR families grew up in ``models/`` each re-implementing the same
sparse recipe: mask the values, gather rows, pool per field, interact,
reduce.  This module is the single home of those pieces, used three
ways:

* the five incumbent families (lr/fm/mvm/ffm/wide_deep) express their
  logits THROUGH these blocks — with **bitwise-unchanged** outputs
  (tests/test_models.py pins every family's logit against a frozen
  copy of the pre-refactor implementation, in dense, MXU-hot, and
  tiered store modes);
* the retrieval/ranking families this substrate enables
  (models/two_tower.py, models/dcn.py, models/xdeepfm.py) compose the
  same blocks into new architectures instead of re-implementing the
  recipe a sixth and seventh time;
* future families register in models/__init__.py and pick blocks off
  this shelf.

Bitwise discipline: each block body is the EXACT expression lifted
from the incumbent model it came from (same ops, same order, same
contractions; the three one-hot field contractions share
``field_contract``, which asks for float32 on a TPU).  A change here
is a numerics change for every family at once — the no-regression
tests exist to catch exactly that.

Blocks take gathered rows / already-masked values, never a model
instance: they are jit-safe pure functions over arrays, so any model's
``logit`` (and explicit ``grad_logit`` where the reference demands
quirk parity) can call them inside the fused step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.models.base import BatchArrays

# -- feature plumbing ---------------------------------------------------------


def masked_x(batch: BatchArrays) -> jax.Array:
    """Effective feature values: ``vals * mask`` [B, K] — zero for
    padding, the value (1.0 in hash mode) for real entries.  Every
    family's first line."""
    return batch["vals"] * batch["mask"]


def linear_term(w_rows: jax.Array, x: jax.Array) -> jax.Array:
    """Sparse linear reduction ``sum_i w_i x_i`` [B] over gathered
    [B, K, 1] w rows (lr_worker.cc:121-143's join as a masked gather
    reduction) — LR's whole forward, FM/FFM/wide&deep/DCN's wide
    half."""
    return jnp.sum(w_rows[..., 0] * x, axis=-1)


def valid_fields(
    slots: jax.Array, mask: jax.Array, num_fields: int
) -> jax.Array:
    """Bool [B, K]: the entry is real AND its field id is in
    [0, num_fields) — the shared out-of-range-field drop semantics
    (negative or oversized fgids contribute nothing; mvm.py / ffm.py /
    wide&deep's one-hot rows of zeros)."""
    return (slots >= 0) & (slots < num_fields) & (mask > 0)


def field_contract(onehot: jax.Array, rows: jax.Array) -> jax.Array:
    """``sum_k onehot[b, k, f] * rows[b, k, e]`` -> [B, F, E]: a row's
    entries summed by field, as one batch matmul contracting K.  In
    float32 on every backend: the TPU runs a float32 dot at default
    precision with both operands rounded to bfloat16 (as ops/hot.py
    found for the head), and the field sums of reference MVM then miss
    float32 by 2e-3 of the largest (PERF.md section 6, PR 31: the
    benchmark's reference check read 7e-6 - 2.8e-5 against its 1e-6).
    The one-hot is exact in bfloat16, so HIGHEST buys ``rows`` alone
    its precision.  On the CPU precision changes nothing: the bitwise
    pins of tests/test_models.py hold as they were."""
    return jnp.einsum(
        "bkf,bke->bfe", onehot, rows, precision=jax.lax.Precision.HIGHEST
    )


def field_pick(onehot: jax.Array, per_field: jax.Array) -> jax.Array:
    """``sum_f onehot[b, k, f] * per_field[b, f, e]`` -> [B, K, E]: each
    entry's own field's row of ``per_field``, as ``field_contract``'s
    transpose: one batch matmul contracting F where a gather would take
    one index per entry (the TPU prices a gather per INDEX: PERF.md
    section 6, PR 33).  An entry whose one-hot row is all zero (a field
    id outside [0, F)) gets 0.  HIGHEST makes this a pick and not an
    approximation of one: the one-hot is exact in bfloat16, and the
    bfloat16 pieces HIGHEST splits ``per_field`` into add back, in
    float32, to the number itself: ``take_along_axis`` bit for bit (but
    a -0.0 comes back +0.0).  At default precision the TPU rounds
    ``per_field`` to bfloat16."""
    return jnp.einsum(
        "bkf,bfe->bke", onehot, per_field, precision=jax.lax.Precision.HIGHEST
    )


# -- embedding tower ----------------------------------------------------------


def field_sum_tower(
    emb_rows: jax.Array,
    x: jax.Array,
    slots: jax.Array,
    num_fields: int,
) -> jax.Array:
    """THE embedding tower: value-scaled embeddings field-sum-pooled
    into ``num_fields`` buckets — [B, F, E] from gathered [B, K, E]
    rows.  One one-hot + one MXU batch-matmul, so variable
    features-per-field work under static shapes; out-of-range fields
    get an all-zero one-hot row and drop out.  Lifted verbatim from
    wide&deep's deep half; two_tower and dcn build their towers on
    it."""
    onehot = jax.nn.one_hot(
        slots, num_fields, dtype=x.dtype
    )  # [B, K, F]; out-of-range fields drop out
    embx = emb_rows * x[..., None]  # [B, K, E]
    return field_contract(onehot, embx)  # [B, F, E]


def flatten_tower(field_emb: jax.Array) -> jax.Array:
    """[B, F, E] -> [B, F*E]: the tower's dense-layer interface."""
    return field_emb.reshape(field_emb.shape[0], -1)


# -- MLP blocks (replicated dense params; plain-SGD updated — see
# parallel/step.py::apply_dense_sgd) -----------------------------------------

# The device scope of a family's dense half (docs/OBSERVABILITY.md): what
# runs over replicated dense parameters, forward and backward, read apart
# from the field contraction inside xf.forward_backward.
DENSE_SCOPE = "xf.dense"


def dense_dot(a: jax.Array, w: jax.Array) -> jax.Array:
    """``a @ w`` for a dense parameter ``w``, in float32 on every
    backend: THE matmul of the dense half (every MLP block here, DCN's
    output product), forward and, through autodiff's transposes,
    backward (``dy @ w.T`` and ``a.T @ dy`` inherit the precision).  At
    default precision the TPU rounds both operands to bfloat16, and the
    wide&deep and DCN steps then miss the benchmark's reference by 1e-5
    of a row and 1e-3 - 1e-2 of a bias's update (PERF.md section 7,
    PR 38: ``dh @ w1.T`` into ``emb``, ``dy @ w2.T`` into ``b1``,
    ``x0.T @ dh`` into ``w1``).  Unlike ``field_contract``'s one-hot
    neither operand is exact in bfloat16, so HIGHEST is what float32
    costs.  On the CPU precision changes nothing: the bitwise pins of
    tests/test_models.py hold as they were."""
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def mlp_stack_init(
    rng: jax.Array, in_dim: int, hidden: int | tuple[int, ...],
    layers: int = 1, prefix: str = "",
) -> dict[str, jax.Array]:
    """He-init stack of ReLU layers, keys ``w1, b1 ... wn, bn``:
    ``layers`` of ``hidden`` (in_dim -> hidden -> ... -> hidden), or, given
    a tuple, one of each of its widths (DLRM's 512-256-128).  Layer 1 draws
    from ``rng`` itself (the one-layer stack is the draw the families
    made before there was a stack), layer l > 1 from
    ``fold_in(rng, l)``."""
    widths = (hidden,) * layers if isinstance(hidden, int) else tuple(hidden)
    dense = {}
    for layer, (fan_in, width) in enumerate(
        zip((in_dim,) + widths, widths), start=1
    ):
        key = rng if layer == 1 else jax.random.fold_in(rng, layer)
        dense[f"{prefix}w{layer}"] = jax.random.normal(
            key, (fan_in, width), jnp.float32
        ) * jnp.sqrt(2.0 / fan_in)
        dense[f"{prefix}b{layer}"] = jnp.zeros((width,), jnp.float32)
    return dense


def mlp_stack(
    dense: dict, h: jax.Array, layers: int = 1, prefix: str = ""
) -> jax.Array:
    """``layers`` ReLU layers ``h <- ReLU(h w_l + b_l)`` -> [B, hidden]:
    the ONE hidden stack of the dense half, of whatever widths the
    arrays have.  ``mlp_head`` and ``mlp_tower`` are its one-layer case
    under a linear output; DCN's deep half is ``Config.deep_layers`` of
    it, DLRM's two stacks one layer a width of ``Config.mlp_bottom`` /
    ``mlp_top``."""
    for layer in range(1, layers + 1):
        h = jax.nn.relu(
            dense_dot(h, dense[f"{prefix}w{layer}"])
            + dense[f"{prefix}b{layer}"]
        )
    return h


def mlp_head_init(
    rng: jax.Array, in_dim: int, hidden: int
) -> dict[str, jax.Array]:
    """He-init 2-layer scalar head (wide&deep's exact dense geometry):
    in_dim -> hidden (ReLU) -> 1."""
    return mlp_tower_init(rng, in_dim, hidden, 1)


def mlp_head(dense: dict, h: jax.Array) -> jax.Array:
    """2-layer ReLU scalar head -> [B] (wide&deep's deep output): the
    tower of one output."""
    return mlp_tower(dense, h)[:, 0]


def mlp_tower_init(
    rng: jax.Array, in_dim: int, hidden: int, out_dim: int,
    prefix: str = "",
) -> dict[str, jax.Array]:
    """He-init 2-layer VECTOR tower: in_dim -> hidden (ReLU) ->
    out_dim, keys prefixed so two towers coexist in one dense pytree
    (two_tower's u_/i_ pair)."""
    k1, k2 = jax.random.split(rng)
    return {
        **mlp_stack_init(k1, in_dim, hidden, prefix=prefix),
        f"{prefix}w2": jax.random.normal(
            k2, (hidden, out_dim), jnp.float32
        ) * jnp.sqrt(1.0 / hidden),
        f"{prefix}b2": jnp.zeros((out_dim,), jnp.float32),
    }


@jax.named_scope(DENSE_SCOPE)
def mlp_tower(dense: dict, h: jax.Array, prefix: str = "") -> jax.Array:
    """2-layer ReLU vector tower -> [B, out_dim]."""
    h = mlp_stack(dense, h, prefix=prefix)
    return dense_dot(h, dense[f"{prefix}w2"]) + dense[f"{prefix}b2"]


# The device scope of DLRM's pairwise dots (docs/OBSERVABILITY.md): a
# sibling of xf.dense inside xf.forward_backward.
INTERACT_SCOPE = "xf.interact"


def vector_pairs(vectors: int) -> int:
    """``n (n - 1) / 2``: the pairs ``i > j`` of ``n`` vectors."""
    return vectors * (vectors - 1) // 2


@jax.named_scope(INTERACT_SCOPE)
def pairwise_dots(t: jax.Array) -> jax.Array:
    """DLRM's dot interaction over an example's vectors ``t [B, n, d]``
    (the bottom stack's output and the fields' embeddings) -> ``[B, n (n -
    1) / 2]``: the dot product of every pair ``i > j``, no vector with
    itself, pair (i, j) in column ``i (i - 1) / 2 + j``:

        Z = T T^T ;   out = [Z_ij for i > j]

    (Naumov et al., arXiv:1906.00091, "dot" interaction without
    self-interaction).  A product of two ACTIVATIONS of one example, as
    AutoInt's scores: nothing of it is a parameter, and its gradient
    reaches both stacks and the table.  One batched product on the MXU,
    float32 on every backend (Precision.HIGHEST: neither operand is exact
    in bfloat16), then the pairs picked out of the flat ``[B, n n]`` by a
    constant one-hot ``[n n, P]`` product, at HIGHEST an exact pick (as
    ``field_pick``'s).  What chose the form (scripts/probe_interact.py, at
    DLRM's Criteo-Terabyte sizes on a v5e, ms for the dense half around
    the block, PR 58): this one 35.0; the rows' lower parts cut out of
    ``[B, n, n]`` and laid side by side 39.6; one gather with P constant
    indices 36.7; the examples on the lanes and ``_lane_pair``'s kernel
    34.8, which in the cell's whole step is 1.4 ms SLOWER (104.03 against
    102.63 ms: its relayouts land in the program around it)."""
    n = t.shape[1]
    z = jnp.einsum("bid,bjd->bij", t, t, precision=jax.lax.Precision.HIGHEST)
    i, j = np.tril_indices(n, -1)
    pick = np.zeros((n * n, len(i)), np.float32)
    pick[i * n + j, np.arange(len(i))] = 1.0
    return jnp.matmul(
        z.reshape(len(t), n * n), pick, precision=jax.lax.Precision.HIGHEST
    )


def dot_interaction(u: jax.Array, v: jax.Array) -> jax.Array:
    """Row-wise dot product [B] of two [B, D] tower outputs — the
    two-tower training logit AND the serve-time top-k score (the
    index scan is the same dot against every item row)."""
    return jnp.sum(u * v, axis=-1)


def cross_network(
    x0: jax.Array, cross_w: jax.Array, cross_b: jax.Array
) -> jax.Array:
    """DCN explicit cross stack: ``x_{l+1} = x0 * (x_l . w_l) + b_l +
    x_l`` over ``cross_w [L, P]`` / ``cross_b [L, P]`` — each layer
    adds one learned degree of bounded polynomial feature interaction
    at O(P) parameters (vs the MLP's O(P*H))."""
    x = x0
    for layer in range(cross_w.shape[0]):
        xw = jnp.sum(x * cross_w[layer], axis=-1, keepdims=True)  # [B, 1]
        x = x0 * xw + cross_b[layer] + x
    return x


# -- factorization interactions ----------------------------------------------


def fm_pair_pieces(
    v_rows: jax.Array, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """FM second-order pieces over gathered [B, K, D] v rows:
    ``(sum_i v_i x_i, sum_i (v_i x_i)^2)`` both [B, D]
    (fm_worker.cc:63-86's square-of-sum/sum-of-squares identity).
    The forward combines them WITHOUT the standard ½ factor (reference
    quirk, models/fm.py docstring); the backward reads sum_vx
    directly."""
    vx = v_rows * x[..., None]  # [B, K, D]
    sum_vx = jnp.sum(vx, axis=1)  # [B, D]
    sum_vx2 = jnp.sum(vx * vx, axis=1)  # [B, D]
    return sum_vx, sum_vx2


def mvm_slot_terms(
    v_rows: jax.Array,
    x: jax.Array,
    slots: jax.Array,
    num_fields: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """MVM per-factor view products: ``(1 + slotsum [B, S, D],
    prod over S [B, D], the one-hot [B, K, S] that summed them)`` in
    the fixed consistent 1+sum form (models/mvm.py docstring;
    mvm_worker.cc:67-95).  The one-hot goes back to the caller so the
    backward picks with the one the forward summed with
    (``field_pick``)."""
    onehot = jax.nn.one_hot(
        slots, num_fields, dtype=x.dtype
    )  # [B, K, S]; fgid >= num_fields rows are all-zero → feature ignored
    vx = v_rows * x[..., None]  # [B, K, D]
    slotsum = field_contract(onehot, vx)  # [B, S, D]
    one_plus = 1.0 + slotsum
    prod = jnp.prod(one_plus, axis=1)  # [B, D]
    return one_plus, prod, onehot


def ffm_field_interaction(
    v_rows: jax.Array,
    x_eff: jax.Array,
    slot: jax.Array,
    valid: jax.Array,
    num_fields: int,
    v_dim: int,
) -> jax.Array:
    """FFM pairwise term via the field-aggregated identity
    (models/ffm.py docstring: O(B*K*F^2*D) MXU compute, O(B*F^2*D)
    memory, no [B, K, K, D] pair tensors).  ``v_rows`` is the flat
    [B, K, F*D] gathered v plane, ``x_eff`` the validity-zeroed
    values, ``slot`` the [0, F)-clipped field ids.  Returns the [B]
    interaction (½(cross − diag)); the TPU layout constraints
    (E = F*D stays the minor dim throughout) ride along unchanged."""
    b, k = slot.shape
    f, d = num_fields, v_dim
    # one-hot of each feature's own field; zero row for invalid
    onehot = (
        (slot[:, :, None] == jnp.arange(f)[None, None, :])
        & valid[:, :, None]
    ).astype(v_rows.dtype)  # [B, K, F]

    # TPU layout constraint: every materialized tensor keeps the
    # flattened E = F*D as its minor dimension (models/ffm.py round-4
    # log: a D-minor operand gets T(8,128) lane padding — 32x memory)
    vx = v_rows * x_eff[:, :, None]  # [B, K, E]
    # field-aggregated sums: one batch matmul contracting K (MXU)
    s = field_contract(onehot, vx)  # [B, F, E]

    # cross term sum_{f1,f2,d} S[b,f1,f2,d] * S[b,f2,f1,d]: stays an
    # elementwise fusion over s read twice — never a dot_general
    s4 = s.reshape(b, f, f, d)
    cross = jnp.sum(
        s4 * jnp.transpose(s4, (0, 2, 1, 3)), axis=(1, 2, 3)
    )
    # subtract the i == i diagonal: x_i^2 * ||v[k_i, f_i, :]||^2,
    # selecting each key's own-field block of E elementwise
    eslot = (jnp.arange(f * d) // d).astype(slot.dtype)  # [E]
    emask = eslot[None, None, :] == slot[:, :, None]  # [B, K, E]
    diag = jnp.sum(jnp.where(emask, vx * vx, 0.0), axis=(1, 2))
    return 0.5 * (cross - diag)


# -- compressed interaction network (xDeepFM) --------------------------------

# The device scope of the CIN (docs/OBSERVABILITY.md): a sibling of
# xf.dense inside xf.forward_backward, read apart from it.
CIN_SCOPE = "xf.cin"

# The most a slice's pair tensor may take: what ``cin_slice_rows`` sizes
# the slices of the batch from.  Read at ONE shape, the paper's Criteo
# sizes on a v5e (D = 10, m = 40, 200 maps, B = 16384: 312.5 KiB of pairs
# an example), where it yields the best slice of five measured, forward and
# backward of the stack alone, ms by examples a slice
# (scripts/probe_cin_slice.py, PR 43): 64: 156.0, 128: 152.8, 256: 163.0,
# 512: 175.4, 1024: 175.8.  Between those points and at any other width the
# rule (``_slice_rows``: as many examples as keep a slice about that size)
# is unmeasured; a family at other sizes runs the probe at its own.
CIN_PAIR_BYTES = 64 << 20
# A slice of part of the batch holds a multiple of the lane width: the
# CIN lays a slice's examples along the lanes of every array it makes.
_LANES = 128


def _slice_rows(batch: int, example_bytes: int, slice_bytes: int) -> int:
    """The one byte rule of the two sliced blocks (``cin_stack``,
    ``field_attention_stack``): as many examples as keep
    ``example_bytes`` each inside ``slice_bytes``, in whole lane widths and
    at least one; the whole batch where that is fewer.  The budgets are
    measured, each at one shape (``CIN_PAIR_BYTES``: xDeepFM's paper sizes,
    PR 43; ``ATTN_SLICE_BYTES``: AutoInt's, PR 47); the rule between is
    not."""
    rows = slice_bytes // example_bytes // _LANES * _LANES
    return min(batch, max(rows, _LANES))


def cin_slice_rows(batch: int, dim: int, max_fields: int, maps: int) -> int:
    """How many examples a slice of ``cin_stack`` holds, from shapes: as
    many as keep the widest layer's pair tensor (``max(maps, max_fields) *
    max_fields * dim`` floats an example) inside ``CIN_PAIR_BYTES``, in
    whole lane widths; the whole batch where that is fewer.  THE place
    the slice is decided: the model hands it to ``cin_stack`` and the
    step books it (``dense.cin_slice_rows``)."""
    per_example = 4 * max(maps, max_fields) * max_fields * dim
    return _slice_rows(batch, per_example, CIN_PAIR_BYTES)


def _to_slices(x: jax.Array, slice_rows: int) -> jax.Array:
    """``[B, ...] -> [slices, slice_rows, ...]``: the batch cut into whole
    slices, zero rows padding the last."""
    slices = -(-x.shape[0] // slice_rows)
    pad = ((0, slices * slice_rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, pad).reshape(slices, slice_rows, *x.shape[1:])


def _from_slices(y: jax.Array, batch: int) -> jax.Array:
    """``[slices, slice_rows, ...] -> [B, ...]``: the padding cut off."""
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])[:batch]


def cin_layer(w: jax.Array, xk: jax.Array, x0: jax.Array) -> jax.Array:
    """One CIN layer over a slice: ``w [H', H, m]``, ``xk [H, N]`` the
    previous layer's maps and ``x0 [m, N]`` the field tower, both with
    the slice's (embedding column, example) pairs along the last axis ->
    ``[H', N]``:

        out[h', n] = sum_{i, j} w[h', i, j] * xk[i, n] * x0[j, n]

    The pair tensor ``xk[i, n] * x0[j, n]`` is a float32 multiply and is
    contracted at once, in float32 on every backend (Precision.HIGHEST,
    as ``dense_dot``: neither operand is exact in bfloat16).  With N
    along the lanes ``[H, m, N] -> [H * m, N]`` moves nothing (m is a
    multiple of 8 at the benchmark's 40 fields)."""
    h, n = xk.shape
    pairs = (xk[:, None, :] * x0[None, :, :]).reshape(h * x0.shape[0], n)
    return jnp.matmul(
        w.reshape(w.shape[0], -1), pairs, precision=jax.lax.Precision.HIGHEST
    )


@jax.named_scope(CIN_SCOPE)
def cin_stack(
    weights: list[jax.Array], tower: jax.Array, slice_rows: int
) -> jax.Array:
    """xDeepFM's Compressed Interaction Network over the field tower
    ``[B, m, D]`` -> the sum-pooled maps of every layer ``[B, sum H_k]``
    (the paper's equations 6-8 with the identity activation):

        X^k[h, :] = sum_{i, j} W^k[h, i, j] (X^{k-1}[i, :] * X^0[j, :])
        p^k[h]    = sum_d X^k[h, d]

    with ``weights[k - 1] = W^k [H_k, H_{k-1}, m]`` and ``H_0 = m``.  A
    layer's pair tensor is ``H_{k-1} * m * D`` floats an example (80 000
    at the paper's sizes: 5.24 GB a layer at B = 16384), so it exists
    for ``slice_rows`` examples at a time, forward and backward: the
    batch goes through ``lax.map`` slice by slice (zero rows pad the
    last, and pool to zeros that are cut off), and each slice's backward
    keeps the layers' maps (the products' outputs, ``H_k * D`` floats an
    example) and multiplies its pairs again (``jax.checkpoint``: a
    multiply, no product is done twice).  Inside a slice the (column,
    example) pairs lie along the lanes, column-major, so pooling over D
    adds D aligned blocks of lanes."""
    b, m, d = tower.shape
    s = slice_rows
    # [slices, m, D * s]: slice c, field j, lane d * s + e <- example c*s + e
    x0 = _to_slices(tower, s).transpose(0, 2, 3, 1).reshape(-1, m, d * s)

    @functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        prevent_cse=False,  # lax.map's loop already keeps the two apart
    )
    def one_slice(x0c: jax.Array) -> jax.Array:
        xk, pooled = x0c, []
        for w in weights:
            xk = cin_layer(w, xk, x0c)
            pooled.append(jnp.sum(xk.reshape(-1, d, s), axis=1))  # [H_k, s]
        return jnp.concatenate(pooled, axis=0)

    p = jax.lax.map(one_slice, x0)  # [slices, sum H_k, s]
    return _from_slices(p.transpose(0, 2, 1), b)


# -- field self-attention (AutoInt) -------------------------------------------

# The device scope of the interacting layers (docs/OBSERVABILITY.md): a
# sibling of xf.dense and xf.cin inside xf.forward_backward.
ATTN_SCOPE = "xf.attn"

# The most a slice's activations may take as numbers (every layer's four
# projections, scores, weights and output: what the slice's backward holds
# at once): what ``attn_slice_rows`` sizes the slices of the batch from.
# Read at ONE shape, AutoInt's paper sizes on a v5e (m = 40, 3 layers of 2
# heads of 32, d = 16, B = 16384: 225 KiB an example), where it yields the
# best slice of five measured, forward and backward of the stack alone, ms
# by examples a slice (scripts/probe_attn_slice.py, PR 54, the lane form:
# one lane width a slice keeps a slice's arrays in VMEM): 128: 62.3,
# 256: 69.9, 512: 77.1, 1024: 87.1, 2048: 92.5 (the program's temporaries
# 0.27, 0.22, 0.16, 0.34, 0.69 GiB; PR 47's form read 93.8, 96.0, 96.5,
# 107.2, 127.6).  At another shape the rule (``_slice_rows``) is
# unmeasured, as the CIN's is.
ATTN_SLICE_BYTES = 32 << 20


def attn_slice_rows(
    batch: int, max_fields: int, heads: int, head_dim: int, layers: int
) -> int:
    """How many examples a slice of ``field_attention_stack`` holds, from
    shapes: as many as keep the slice's activations (a layer's Q, K, V and
    residual projection, ``4 m H d'`` floats an example, its scores and
    weights ``2 H m m`` and its output ``m H d'``, times the layers)
    inside ``ATTN_SLICE_BYTES``, in whole lane widths; the whole batch
    where that is fewer.  THE place the slice is decided: the model hands
    it to the stack and the step books it (``dense.attn_slice_rows``)."""
    width = heads * head_dim
    per_example = 4 * layers * (
        5 * max_fields * width + 2 * heads * max_fields * max_fields
    )
    return _slice_rows(batch, per_example, ATTN_SLICE_BYTES)


def field_presence(x: jax.Array, slots: jax.Array, num_fields: int) -> jax.Array:
    """``[B, F]``, 1.0 where the row has an entry of that field and 0.0
    where it has none: the one-hot ``field_sum_tower`` sums the embeddings
    by (the same expression: one array in the compiled step), taken over
    the entries whose value is not 0 (padding, or an entry the capacity
    rule dropped, ships 0).  No gather: the field ids are in the batch.
    What a softmax over fields needs and a sum never did: an absent field
    is a zero row of the tower, which adds nothing to a sum and takes
    weight e^0 as a key."""
    onehot = jax.nn.one_hot(slots, num_fields, dtype=x.dtype)  # [B, K, F]
    return jnp.max(onehot * (x != 0)[..., None].astype(x.dtype), axis=1)


def _lane_contract_xla(rows: jax.Array, tiles: jax.Array) -> jax.Array:
    """``_lane_contract`` as XLA writes it: one multiply and sum."""
    return jnp.sum(rows[:, :, :, None, :] * tiles[:, :, None, :, :], axis=1)


def _lane_contract_kernel(rows_ref, tiles_ref, out_ref):
    """One head, one lane tile: ``out[g] = sum_r rows[r, g] * tiles[r]``
    with eight ``[a, s]`` sums held in registers while ``r`` runs, so a
    ``[a, s]`` tile is read once for eight rows of ``rows``."""
    n_r, n_g = rows_ref.shape[:2]
    for g0 in range(0, n_g, 8):
        group = range(g0, min(g0 + 8, n_g))

        def add_r(r, sums, group=group):
            tile = tiles_ref[r]
            return tuple(
                acc + rows_ref[r, g:g + 1, :] * tile for acc, g in zip(sums, group)
            )

        zero = jnp.zeros(out_ref.shape[1:], out_ref.dtype)
        sums = jax.lax.fori_loop(0, n_r, add_r, (zero,) * len(group))
        for acc, g in zip(sums, group):
            out_ref[g] = acc


def _lane_contract_tpu(rows: jax.Array, tiles: jax.Array) -> jax.Array:
    """``_lane_contract`` as a Mosaic kernel over (head, lane tile)."""
    from jax.experimental import pallas as pl

    heads, n_r, n_g, s = rows.shape
    a = tiles.shape[2]
    lanes = _LANES if s % _LANES == 0 else s

    def block(*shape):
        return pl.BlockSpec((None, *shape, lanes), lambda h, j: (h, 0, 0, j))

    return pl.pallas_call(
        _lane_contract_kernel,
        grid=(heads, s // lanes),
        in_specs=[block(n_r, n_g), block(n_r, a)],
        out_specs=block(n_g, a),
        out_shape=jax.ShapeDtypeStruct((heads, n_g, a, s), rows.dtype),
    )(rows, tiles)


def _lane_contract(rows: jax.Array, tiles: jax.Array) -> jax.Array:
    """``rows [H, r, g, s]``, ``tiles [H, r, a, s]`` -> ``[H, g, a, s]``:

        out[h, g, a, :] = sum_r rows[h, r, g, :] * tiles[h, r, a, :]

    THE per-example product of ``field_attention_layer``, a float32
    multiply and sum with the slice's examples on the lanes: every row
    ``rows[h, r, g]`` (one number an example) times the ``[a, s]`` tile
    ``tiles[h, r]``, summed over the MAJOR axis ``r``, so that whole
    registers are added.  On the TPU a Mosaic kernel, elsewhere XLA's
    multiply and sum, picked where the program is lowered: XLA's fusion
    of this sum reads each tile again for every row, 17 - 33 us a call
    at AutoInt's paper sizes (H = 2, 32 or 40 rows and sums, tiles of
    ``[40, 128]``) where the kernel, which holds eight sums in registers
    under one read of a tile, takes 10.4 - 10.9 (PERF.md section 6,
    PR 54: 24 calls a slice, 79.5 against 55.9 ms a step)."""
    return jax.lax.platform_dependent(
        rows, tiles, tpu=_lane_contract_tpu, default=_lane_contract_xla
    )


@jax.custom_vjp
def _lane_pair(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x [H, c, a, s]``, ``y [H, c, b, s]`` -> ``[H, b, a, s]``:

        out[h, b, a, :] = sum_c y[h, c, b, :] * x[h, c, a, :]

    every pair of a field of ``y`` with a field of ``x``, summed over a
    head's width.  With ``_lane_mix`` the per-example products of
    ``field_attention_layer``.  Each is the other's transpose, and both
    are ``_lane_contract``, forward and backward, so that every sum runs
    over a major axis: where autodiff's transpose would sum over the
    second-minor axis (the 8 fields that share a register) the backward
    swaps the two field axes of the pair array first, and ``_lane_mix``
    the fields and the width of ``y``."""
    return _lane_contract(y, x)


@jax.custom_vjp
def _lane_mix(w: jax.Array, y: jax.Array) -> jax.Array:
    """``w [H, b, a, s]``, ``y [H, c, b, s]`` -> ``[H, c, a, s]``:

        out[h, c, a, :] = sum_b w[h, b, a, :] * y[h, c, b, :]

    the fields of ``y`` mixed by the pair array ``w``, summed over its
    major field axis (``_lane_pair`` has the rest)."""
    return _lane_contract(y.swapaxes(1, 2), w)


def _lane_pair_fwd(x, y):
    return _lane_pair(x, y), (x, y)


def _lane_pair_bwd(kept, g):
    x, y = kept
    return _lane_mix(g, y), _lane_mix(g.swapaxes(1, 2), x)


def _lane_mix_fwd(w, y):
    return _lane_mix(w, y), (w, y)


def _lane_mix_bwd(kept, g):
    w, y = kept
    return _lane_pair(g, y), _lane_mix(w.swapaxes(1, 2), g)


_lane_pair.defvjp(_lane_pair_fwd, _lane_pair_bwd)
_lane_mix.defvjp(_lane_mix_fwd, _lane_mix_bwd)


def field_attention_layer(
    wq: jax.Array, wk: jax.Array, wv: jax.Array, wr: jax.Array,
    e: jax.Array, present: jax.Array, heads: int,
) -> jax.Array:
    """One interacting layer over a slice in the LANE form, the slice's
    examples minor-most: ``e [d_l, m, s]`` the fields' vectors, ``present
    [m, s]`` (1.0 / 0.0), the four ``[d_l, H * d']`` projections ->
    ``[H * d', m, s]``:

        psi[h, i, j] = <W_Q^h e_i, W_K^h e_j>
        alpha[h, i, :] = softmax of psi[h, i, :] over the PRESENT fields j
        out_i = ReLU([sum_j alpha[h, i, j] W_V^h e_j]_h + W_Res e_i) * present_i

    (AutoInt's equations 5-8; no 1 / sqrt(d'), the paper has none).  The
    four projections are ONE product with the weights side by side,
    ``[4 H d', d_l] x [d_l, m s]``, float32 on every backend
    (Precision.HIGHEST as ``dense_dot``: no operand is exact in
    bfloat16), written with the fields kept apart (``[d_l, m, s] ->
    [4 H d', m, s]``): as ``[d_l, m s]`` arrays the compiler relays both
    sides under no scope's name.
    The scores and the weighted sum, per-example products of two
    activations, are float32 multiplies summed over ``d'`` and over the
    keys: with the examples on the lanes every operand of theirs, of the
    softmax, the residual, the ReLU and the mask fills its vector
    registers, where ``[s, H, m, m]`` put 40 and ``[s, m, H d']`` 32 or 64
    on a 128-lane axis.  The scores are held key-major, ``[H, j, i, s]``:
    the softmax over the keys and the weighted sum then add whole
    registers.  The softmax is float32 with the row's largest score over
    the present keys taken off first.  An absent field's key gets -inf, so
    it takes weight 0, and its own output row is 0; a row with NO present
    field gives zeros (the sum of its weights is held off 0), not NaN."""
    _, m, s = e.shape
    w = jnp.concatenate([wq, wk, wv, wr], axis=1)
    proj = jnp.einsum("dw,dms->wms", w, e, precision=jax.lax.Precision.HIGHEST)
    q, k, v, res = proj.reshape(4, heads, -1, m, s)
    psi = _lane_pair(q, k)
    psi = jnp.where(present[None, :, None, :] > 0, psi, -jnp.inf)
    top = jax.lax.stop_gradient(jnp.max(psi, axis=1, keepdims=True))
    ex = jnp.exp(psi - jnp.where(jnp.isfinite(top), top, 0.0))
    total = jnp.sum(ex, axis=1, keepdims=True)
    alpha = ex / jnp.maximum(total, jnp.finfo(ex.dtype).tiny)
    mixed = _lane_mix(alpha, v)
    return jax.nn.relu(mixed + res).reshape(-1, m, s) * present[None]


@jax.named_scope(ATTN_SCOPE)
def field_attention_stack(
    weights: list[tuple[jax.Array, jax.Array, jax.Array, jax.Array]],
    tower: jax.Array, present: jax.Array, heads: int, slice_rows: int,
) -> jax.Array:
    """AutoInt's interacting layers over the field tower ``[B, m, d]`` ->
    the fields' vectors after the last layer ``[B, m, H * d']``;
    ``weights[l] = (W_Q, W_K, W_V, W_Res)`` of layer l + 1, each
    ``[d_l, H * d']``; ``present [B, m]`` from ``field_presence``.  A
    layer's projections, scores and weights are ``5 m H d' + 2 H m m``
    floats an example (16 000 at the paper's sizes: 3.1 GB over three
    layers at B = 16384), so they exist for ``slice_rows`` examples at a
    time, forward and backward: the batch goes through ``lax.map`` slice
    by slice (zero rows with no present field pad the last, give zeros
    and are cut off), and each slice's backward computes its forward
    again (``jax.checkpoint``, nothing kept but the slice's tower and
    presence: a kept projection would be a ``[B, m, H d']`` array again).
    Inside a slice the examples lie along the lanes
    (``field_attention_layer``).  The tower is relaid to that form once,
    the batch's slices together, ``[slices, d + 1, m, s]`` with the
    presence as each slice's last row (one relayout under this scope: a
    plane of its own is relaid by the compiler, under no name); the last
    layer's output is relaid back a slice at a time, ``[s, m, H d']``:
    the one whole array the stack makes."""
    @functools.partial(jax.checkpoint, prevent_cse=False)  # as cin_stack's
    def one_slice(lanes: jax.Array) -> jax.Array:
        e, p = lanes[:-1], jax.lax.stop_gradient(lanes[-1])
        for wq, wk, wv, wr in weights:
            e = field_attention_layer(wq, wk, wv, wr, e, p, heads)
        return e.transpose(2, 1, 0)

    # [slices, d + 1, m, s]: a slice's tower and, its last row, its presence
    lanes = jnp.concatenate([tower, present[..., None]], axis=-1)
    out = jax.lax.map(
        one_slice, _to_slices(lanes, slice_rows).transpose(0, 3, 2, 1)
    )
    return _from_slices(out, tower.shape[0])


# -- SENET gates and pair-indexed bilinear products (FiBiNET) -----------------

# The device scope of the gate-and-bilinear block (docs/OBSERVABILITY.md): a
# sibling of xf.dense, xf.cin and xf.attn inside xf.forward_backward.
BILINEAR_SCOPE = "xf.bilinear"

# The most the block's batch-sized arrays may take whole: the pair tensor c,
# the two towers' left products (made again in the backward) and c's
# cotangent, ``6 P D`` floats an example.  Read at ONE shape, FiBiNET's paper
# sizes on a v5e (m = 40, D = 10: 183 KiB an example, 2.9 GiB at B = 16384),
# where the whole batch is the fastest form measured, forward and backward of
# the block alone, ms (scripts/probe_bilinear.py, PR 52): whole 16.0 (14.2
# without its ``jax.checkpoint``), slices of 4096 examples 57.5, of 1024
# 63.3; a ``[B, m D] x [m D, P D]`` product with the matrices in blocks 44.0
# and the equation's einsum over picked pairs 49.0.  A slice writes its part
# of c and a loop gives the compiler no room to fuse across it.  So the
# budget only has to hold the cell's batch whole; a batch too large for it
# goes in slices, at that price.
BILINEAR_WHOLE_BYTES = 4 << 30


def field_pairs(max_fields: int) -> int:
    """``m (m - 1) / 2``: the pairs ``i < j`` of ``m`` fields."""
    return max_fields * (max_fields - 1) // 2


def bilinear_slice_rows(batch: int, dim: int, max_fields: int) -> int:
    """How many examples a slice of ``senet_bilinear`` holds, from shapes: the
    whole batch where its arrays (``6 P D`` floats an example) fit
    ``BILINEAR_WHOLE_BYTES``, else as many as do, in whole lane widths.  THE
    place the slice is decided: the model hands it to the block."""
    per_example = 4 * 6 * field_pairs(max_fields) * dim
    return _slice_rows(batch, per_example, BILINEAR_WHOLE_BYTES)


def senet_gates(s1: jax.Array, s2: jax.Array, tower: jax.Array) -> jax.Array:
    """FiBiNET's SENET layer over the field tower ``[B, m, D]`` -> one gate a
    field ``[B, m]``, computed from ALL of the example's fields:

        z_i = mean_d e_i[d]                    (squeeze, mean pooling)
        a   = ReLU(ReLU(z S1) S2)              S1 [m, m // r], S2 [m // r, m]

    (the paper's equations 5-6; no bias).  Both products are float32 on every
    backend (``dense_dot``).  An absent field's squeeze is 0 and its gate
    multiplies a zero vector: no presence mask enters."""
    z = jnp.mean(tower, axis=-1)
    return jax.nn.relu(dense_dot(jax.nn.relu(dense_dot(z, s1)), s2))


def bilinear_pairs(w: jax.Array, tower: jax.Array) -> jax.Array:
    """FiBiNET's Field-Interaction bilinear layer: ``w [P, D, D]``, a matrix
    for every pair of fields ``i < j`` in lexicographic order, and the tower
    ``[B, m, D]`` -> ``[B, P * D]``, pair p = (i, j) in columns ``p D .. (p +
    1) D``:

        out_p = (e_i W_p) * e_j                (the paper's equation 9, type 3)

    Written field by field, in two-dimensional arrays whose minor axis is
    hundreds of columns wide (a ``[B, P, D]`` array pads D = 10 to a lane
    tile of 128 on the TPU, a field cut out of the ``[B, m, D]`` tower as
    ``[B, 1, D]`` pads its 1 to 128, 134 MB a field where the cut out of the
    flat tower is 1 MB, and a ``[B, P, D, D]`` array never exists): field
    i's vector meets the matrices of ALL its pairs ``(i, j > i)`` side by side
    in one product ``[B, D] x [D, (m - 1 - i) D]``, float32 on every backend
    (``dense_dot``), and the right factors of those pairs are the flat tower
    from field ``i + 1`` on, as it lies.  What chose the form:
    scripts/probe_bilinear.py (``BILINEAR_WHOLE_BYTES`` has its readings)."""
    b, m, d = tower.shape
    flat = tower.reshape(b, m * d)
    out, start = [], 0
    for i in range(m - 1):
        n = m - 1 - i
        # [n, D, D] -> [D, n * D]: field i's matrices side by side
        side = w[start:start + n].transpose(1, 0, 2).reshape(d, n * d)
        left = dense_dot(flat[:, i * d:(i + 1) * d], side)
        out.append(left * flat[:, (i + 1) * d:])
        start += n
    return jnp.concatenate(out, axis=-1)


@jax.named_scope(BILINEAR_SCOPE)
def senet_bilinear(
    s1: jax.Array, s2: jax.Array, wp: jax.Array, wq: jax.Array,
    tower: jax.Array, slice_rows: int,
) -> jax.Array:
    """FiBiNET's interaction over the field tower ``[B, m, D]`` -> the pair
    tensor ``c [B, 2 P D]``: the bilinear pairs of the embeddings (``wp``)
    beside those of the SENET-weighted embeddings ``v_i = a_i e_i`` (``wq``;
    ``senet_gates``).  Whole where ``slice_rows`` holds the batch; else the
    batch goes through ``lax.map`` slice by slice (zero rows pad the last,
    give zeros and are cut off).  Either way the backward computes the
    forward again (``jax.checkpoint``, nothing kept but the tower), as
    ``field_attention_stack``'s: alone the whole block is 1.8 ms faster with
    the towers' left products kept, but in the cell's step it is 1.3 ms
    SLOWER (90.86 against 89.53 ms: the kept products cost more in copies
    around the block than the second forward does) and 1.07 GiB larger
    (PERF.md section 6, PR 52)."""
    def one_slice(e: jax.Array) -> jax.Array:
        v = senet_gates(s1, s2, e)[..., None] * e
        return jnp.concatenate(
            [bilinear_pairs(wp, e), bilinear_pairs(wq, v)], axis=-1
        )

    if slice_rows >= tower.shape[0]:
        return jax.checkpoint(one_slice)(tower)
    out = jax.lax.map(
        jax.checkpoint(one_slice, prevent_cse=False),
        _to_slices(tower, slice_rows),
    )
    return _from_slices(out, tower.shape[0])

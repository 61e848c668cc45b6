"""Compiles for a DESCRIBED v5e, with no chip: what the TPU's compiler
refuses (a Mosaic kernel's tiling, fast memory, a program that does not
fit) shows here and costs no chip time.  Nothing runs, so nothing here
says a word about results or times.

One file on purpose, and the topology only inside a fixture: one process
at a time may load the TPU's library, so under several test workers only
the worker that is given this file describes the chip."""

import contextlib
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _without_compile_cache():
    """A compile for a described device is written to the persistent
    cache and cannot be read back without the device: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


@pytest.fixture()
def no_compile_cache():
    with _without_compile_cache():
        yield


# rows of 128 outputs: the flagship's hot and cold planes (B=131072 x 28 /
# 12), one that no block size divides, one smaller than a block
@pytest.mark.parametrize("rows", [28672, 12288, 1000, 37])
def test_lane_select_kernel_compiles_for_v5e(one_chip, no_compile_cache, rows):
    from xflow_tpu.ops import window

    win = jax.ShapeDtypeStruct((rows, 256), jnp.int32, sharding=one_chip)
    local = jax.ShapeDtypeStruct((rows, 128), jnp.int32, sharding=one_chip)
    compiled = jax.jit(window.lane_select_tpu).lower(win, local).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dict_wire_decode_compiles_for_v5e_at_flagship(
    one_chip, no_compile_cache
):
    """The whole decode as a TPU traces it, at the plane capacities of one
    real batch of the benchmark's train cell (T=2^28, B=131072, 12 + 28):
    it compiles, holds its Mosaic kernels, and its temporaries (669 MiB:
    [B, K] int32 planes padded to 128 lanes, and 600 MiB for the key
    resolve's two-word rows, one to a 128-lane tile row) stay under the
    1 GiB gradient buffer that the step allocates after them: a
    materialised one-hot would not."""
    from xflow_tpu.ops import window
    from xflow_tpu.parallel.step import expand_dict_wire

    u8, u16, u32 = np.uint8, np.uint16, np.uint32
    shapes = {
        "cw_cu": ((53248,), u32), "cw_cun": ((1,), np.int32),
        "cw_ci": ((1228800,), u16), "cw_ct": ((294912,), u32),
        "cw_cf": ((184320,), u8), "cw_cc": ((131072,), u8),
        "cw_lb": ((16384,), u8), "cw_wb": ((16384,), u8),
        "cw_h8": ((2293760,), u8), "cw_hx": ((1490944,), u8),
        "cw_hxh": ((745472,), u8), "cw_hf": ((458752,), u8),
        "cw_hc": ((131072,), u8),
    }
    wire = {
        k: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for k, (shape, dtype) in shapes.items()
    }
    cfg = types.SimpleNamespace(max_nnz=12, hot_nnz=28)
    compiled = jax.jit(
        lambda w: expand_dict_wire(cfg, window.lane_select_tpu, w)
    ).lower(wire).compile()
    # one monotone_take per flag plane and per tier of each section
    assert compiled.as_text().count("tpu_custom_call") >= 6
    assert compiled.memory_analysis().temp_size_in_bytes < 768 << 20


def _gather_lines(compiled) -> list[str]:
    return [
        line.split("metadata")[0]
        for line in compiled.as_text().splitlines() if " gather(" in line
    ]


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_wide_take_stays_a_gather_of_two_word_rows_on_v5e(
    one_chip, no_compile_cache, dtype
):
    """The occurrence resolve at the flagship's shapes (1 228 800 indices
    into the dictionary's 53 248 keys, or rows): what the TPU's compiler
    leaves of ops/window.py::wide_take is ONE gather of two-word rows.
    It folds a column pick into the gather and a gather beside a constant
    column into one of single elements, and either fold is the 8.6 ns an
    index again (PERF.md section 6, PR 30)."""
    from xflow_tpu.ops import window

    src = jax.ShapeDtypeStruct((53248,), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((1228800,), jnp.int32, sharding=one_chip)
    gathers = _gather_lines(jax.jit(window.wide_take).lower(src, idx).compile())
    assert len(gathers) == 1 and "slice_sizes={1,2}" in gathers[0], gathers


# plane capacities (dictionary, occurrences, tail) and padded cold slots
# of one real batch of lr_tb.train_packed and of ffm_tb.train_packed,
# whose batches ship no tail plane (PERF.md section 5), and FFM's beside
# a tail
_LR_PLANES = (53248, 1228800, 294912, 131072 * 12)
_FFM_PLANES = (53248, 118784, 0, 16384 * 8)
_FFM_PLANES_TAIL = (53248, 118784, 4096, 16384 * 8)


@pytest.mark.parametrize("rows,d,planes", [
    (1 << 28, 1, _LR_PLANES), (1 << 25, 10, _LR_PLANES),
    (1 << 21, 160, _FFM_PLANES), (1 << 21, 160, _FFM_PLANES_TAIL),
])
def test_dict_cold_rows_compile_for_v5e_at_flagship(
    one_chip, no_compile_cache, rows, d, planes
):
    """The cold rows through the dictionary at the plane capacities of
    one real batch of a benchmark train cell: LR's table, FM's and MVM's
    width beside it, FFM's v.  It compiles; the [T, D] table (the only
    float32 operand of its height) is gathered per dictionary and per
    tail entry and by nothing of a padded plane's size; the occurrence
    resolve reads rows at least two words wide.  A narrow row is laid
    out by its lane shuffles on float32 columns (two Mosaic calls a
    column); a row of ROW_LAYOUT_MIN_COLUMNS or more by one gather of
    WHOLE rows a stream, with no Mosaic call and no [slots, 1] column,
    which (8,128) tiles pad 128 x once it is a [B, max_nnz, 1] plane
    (FFM's 160 of them were 82 ms of a 202 ms step and what refused
    B = 32768: PERF.md section 6, PR 34-35)."""
    from xflow_tpu.ops import window
    from xflow_tpu.parallel.step import ROW_LAYOUT_MIN_COLUMNS, dict_cold_rows

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cap_u, cap_i, cap_t, slots = planes
    plan = {
        "cu": shaped((cap_u,), jnp.int32), "ct": shaped((cap_t,), jnp.int32),
        "ci": shaped((cap_i,), jnp.int32),
        "is_dict": shaped((slots,), jnp.bool_),
        "is_tail": shaped((slots,), jnp.bool_),
        "di_idx": shaped((slots,), jnp.int32),
        "tail_idx": shaped((slots,), jnp.int32),
    }
    compiled = jax.jit(
        lambda p, pl: dict_cold_rows(pl, {"t": p}, window.lane_select_tpu)
    ).lower(shaped((rows, d), jnp.float32), plan).compile()
    text = compiled.as_text()
    by_rows = d >= ROW_LAYOUT_MIN_COLUMNS
    # a take per stream and column, or none
    assert text.count("tpu_custom_call") == (0 if by_rows else 2 * d)
    assert (f"f32[{slots},1]" in text) == (not by_rows)
    # beside the takes' window rows: the table's rows of the tail and of
    # the dictionary, the occurrence resolve, and a wide row's layout, a
    # stream (an empty tail plane has no rows to gather or to lay out)
    gathers = [
        g for g in _gather_lines(compiled) if "slice_sizes={1,256}" not in g
    ]
    shapes = sorted(
        re.search(r"= f32\[([\d,]*)\]", g).group(1) for g in gathers
    )
    wide = f",{d}" if d > 1 else ""
    assert shapes == sorted(
        [f"{cap_t}{wide}"] * (cap_t > 0)
        + [f"{cap_u}{wide}", f"{cap_i},{max(d, 2)}"]
        + [f"{slots},{d}"] * ((1 + (cap_t > 0)) * by_rows)
    ), shapes
    if by_rows:
        assert all(f"slice_sizes={{1,{d}}}" in g for g in gathers), gathers
    # D = 160: 2 GiB of it is the table's copy to columns minor, which the
    # step holds anyway (PERF.md section 7)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        (2304 if d > 128 else 1536) << 20
    )


# H, D, slots a step: the heads of lr_tb / mvm_tb / dcn_tb.train_packed
@pytest.mark.parametrize("h,d,m", [
    (4096, 1, 3670016), (16384, 10, 4194304), (16384, 26, 2097152),
])
def test_hot_gather_views_its_product_by_a_bitcast_on_v5e(
    one_chip, no_compile_cache, h, d, m
):
    """ops/hot.py::hot_gather's SCAN (``impl="mxu"``) alone at the heads
    of the benchmark's lr_tb.train_packed (H = 2^12, D = 1, 3 670 016
    slots a step: the width the step runs it at), mvm_tb.train_packed
    and dcn_tb.train_packed (H = 2^14; D = 10 over 4 194 304 slots, D =
    26 over 2 097 152: since PR 45 the step indexes the slice at these
    widths, and the scan stays the contract a later width under
    hot.PLAIN_GATHER_MIN_COLUMNS would run) for a described v5e.
    The head is flattened [h1, D * h2] for both directions (PR 44), so
    the one-hot product [C, D * h2] IS [C, D, h2] in the device's tiles
    (h2 = 128 fills whole tiles of 8 sublanes; the chunk's slots lie on
    the lanes): the view is a bitcast.  Flattened [h1, h2 * D], as the
    gather had it until PR 44, the view was a real ``reshape
    f32[C,128,D]``, a shuffle of the product in each of the scan's 4 096
    chunks (8.0 ms of MVM's 271.7 ms step, 9.1 of DCN's 322.0; PERF.md
    section 6).  At D = 1 the two orders are one array and no view is
    left to move.  And the product still asks for float32
    (Precision.HIGHEST: the default rounds the table's rows to bfloat16
    on the way into the MXU)."""
    from xflow_tpu.ops import hot

    h1, h2 = hot.hot_factors(h)
    c = hot._chunk(h1, h2, d, m)
    w = jax.ShapeDtypeStruct((h, d), jnp.float32, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    scan = jax.jit(lambda w, k: hot.hot_gather(w, k, impl="mxu"))
    lines = [
        line.split(", metadata")[0].strip()
        for line in scan.lower(w, keys).compile().as_text().splitlines()
    ]
    views = (f"f32[{c},{h2},{d}]", f"f32[{c},{d},{h2}]")
    of_view = [
        line for line in lines
        if " = " in line and line.split(" = ", 1)[1].startswith(views)
    ]
    moved = [line for line in of_view if re.search(r" (reshape|copy)\(", line)]
    assert not moved, moved
    assert d == 1 or any(" bitcast(" in line for line in of_view), of_view
    products = [
        line for line in lines
        if " convolution(" in line and f"= f32[{c},{d * h2}]" in line
    ]
    assert products and all(
        "operand_precision={highest,highest}" in line for line in products
    ), products


def _optimizer_passes(text: str, elements: int) -> list[tuple[str, str]]:
    """(results, body) of every fusion of a compiled program that the
    source booked to xf.optimizer and that yields float32 arrays of
    ``elements`` rows: the types on the left of ``fusion(``, and the text
    of the computation it calls, whose parameters are its operands."""
    bodies, current = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([^ ]+) \(.*\{$", line)
        if head:
            current = bodies.setdefault(head.group(1), [])
        elif current is not None:
            current.append(line)
    out = []
    for line in text.splitlines():
        results, fusion, rest = line.partition(" fusion(")
        if (
            fusion and "xf.optimizer" in rest
            and re.search(rf"f32\[{elements}[,\]]", results)
        ):
            called = re.search(r"calls=%?([^ ,)]+)", rest).group(1)
            out.append((results.split(" = ", 1)[1], "\n".join(bodies[called])))
    return out


def _table_sized_copies(text: str, elements: int) -> list[str]:
    return [
        line.split("metadata")[0] for line in text.splitlines()
        if re.search(rf"= \(?f32\[{elements}[,\]][^=]* copy(?:-start)?\(", line)
    ]


def _assert_touched_rows_update(hlo: str, t: int, d: int, cap_u: int) -> None:
    """PR 57: the dense update of a [t, d] table that the rule selects
    (step.py::touched_rows_selects: xDeepFM's, AutoInt's and FiBiNET's
    ``emb`` under a dictionary of ``cap_u`` entries and an empty tail) as
    the TPU's compiler leaves it.  NO zeroed [T, D] gradient buffer (the
    parent's ``broadcast`` of 2 GiB, unscoped); under xf.optimizer NO
    fusion that walks the table (the parent's kLoop FTRL pass over param,
    n, z and the buffer): of the fusions that yield a table-shaped array,
    three are the sets of ``cap_u`` rows into param, n and z, and what is
    left is the head's H rows going back IN PLACE (every table-shaped
    instruction of its body a dynamic-update-slice of an operand); and
    the table is gathered at ``cap_u`` rows four times: param, n, z for
    the update, param once for the forward."""
    table = rf"f32\[{t},{d}\]"
    assert not re.findall(rf"= {table}\S* broadcast\(", hlo)
    writes = [
        body for results, body in _optimizer_passes(hlo, t)
        if re.search(table, results)
    ]
    sets = [body for body in writes if " scatter(" in body]
    assert len(sets) == 3, len(sets)
    assert all(f"s32[{cap_u}]" in body for body in sets)
    for body in writes:
        if body in sets:
            continue
        made = [
            line for line in body.splitlines()
            if re.search(rf"= \(?{table}", line) and " parameter(" not in line
        ]
        assert made and all(
            " dynamic-update-slice(" in line or " tuple(" in line
            for line in made
        ), made
    gathers = re.findall(
        rf"\(param_[\d.]+: {table}, param_[\d.]+: s32\[{cap_u}\]\) -> "
        rf"f32\[{cap_u},{d}\]", hlo,
    )
    assert len(gathers) == 4, gathers


def _lowered_fm_mesh_step(topo):
    """(cfg, lowered): the FM train step over the described 2x2 at the
    widths of the benchmark's fm_tb_x4.train_packed, a table and a batch
    cut to keep the compile short, on the compact wire."""
    from xflow_tpu.config import Config
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh, replicated, table_sharding
    from xflow_tpu.parallel.step import TrainStep

    cfg = Config(
        model="fm", optimizer="ftrl", v_dim=10, table_size_log2=22,
        batch_size=16384, max_nnz=8, hot_size_log2=14, hot_nnz=32,
        num_devices=4,
    )
    mesh = make_mesh(4, devices=list(topo.devices))
    model = make_model(cfg)
    step = TrainStep(model, make_optimizer(cfg), cfg, mesh)
    assert step._hot_impl == "auto" and step.wire_format == "compact"

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = {
        "tables": {
            spec.name: {
                name: shaped(
                    (cfg.table_size, spec.dim), jnp.float32, table_sharding(mesh)
                )
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {},
        "step": shaped((), jnp.int32, replicated(mesh)),
    }
    b = cfg.batch_size
    arrays = {
        "ckeys": shaped((b, cfg.max_nnz), jnp.int32, step._bsharding),
        "hot_ckeys_u16": shaped((b, cfg.hot_nnz), jnp.uint16, step._bsharding),
        "labels_u8": shaped((b,), jnp.uint8, step._bsharding),
        "weights_u8": shaped((b,), jnp.uint8, step._bsharding),
    }
    return cfg, step.train.lower(state, arrays)


def test_four_chip_fm_step_compiles_for_v5e_with_its_exchange(
    topo, no_compile_cache
):
    """The FM train step over the described 2x2 as the TPU's compiler
    leaves it (parallel/exchange.py; ``_lowered_fm_mesh_step``): every
    collective is the program's or a scalar's, none is issued by a loop's
    iterations, none has a block's rows.  The TPU runs a reduce-scatter
    as an all-reduce and a slice, and may continue an all-gather inside a
    neighbouring loop (``pieces`` > 1): collectives_in counts such a
    chain once."""
    from xflow_tpu.parallel.exchange import collectives_in

    cfg, lowered = _lowered_fm_mesh_step(topo)
    b = cfg.batch_size
    text = lowered.compile().as_text()
    # a chip's block of w goes through the FTRL pass on its flat view,
    # whole (8,128) tiles, sharded on its only axis; v's padded rows keep
    # their shape (_optimizer_pass; PERF.md section 6, PR 37), and the
    # collectives below are counted as before
    block = cfg.table_size // 4
    (v_out, v_body), (w_out, w_body) = sorted(_optimizer_passes(text, block))
    # (a block this small may sit in another memory space: "S(1)")
    assert w_out.count(f"f32[{block}]{{0:T(1024)") == 3, w_out
    assert f"f32[{block},1]" not in w_out + w_body, (w_out, w_body)
    assert v_out.count(f"f32[{block},10]{{0,1:T(8,128)}}") == 3, v_out
    assert f"f32[{block * 10}]" not in v_body, v_body
    found = collectives_in(text)
    assert " while(" in text and found
    assert not [c for c in found if c["in_loop"]], found
    slots = b * cfg.max_nnz
    assert slots < cfg.table_size // 4
    assert max(c["rows"] for c in found) <= slots + 1024, found  # + padding
    assert len(found) <= 4 * 2 + 2 + 3 + 2, found  # + the reduce-scatters' fix-ups


_U8, _U16, _U32 = np.uint8, np.uint16, np.uint32
# The dictionary wire's plane capacities of one real batch (seed 1) of each
# one-chip cell of the benchmark: name -> (shape, dtype).
LR_PLANES = {
    "cw_cu": ((53248,), _U32), "cw_cun": ((1,), np.int32),
    "cw_ci": ((1228800,), _U16), "cw_ct": ((294912,), _U32),
    "cw_cf": ((184320,), _U8), "cw_cc": ((131072,), _U8),
    "cw_lb": ((16384,), _U8), "cw_wb": ((16384,), _U8),
    "cw_h8": ((2293760,), _U8), "cw_hx": ((1490944,), _U8),
    "cw_hxh": ((745472,), _U8), "cw_hf": ((458752,), _U8),
    "cw_hc": ((131072,), _U8),
}
MVM_PLANES = {
    "cw_cu": ((43008,), _U32), "cw_cun": ((1,), np.int32),
    "cw_ci": ((688128,), _U16), "cw_ct": ((262144,), _U32),
    "cw_cf": ((118784,), _U8), "cw_cc": ((131072,), _U8),
    "cw_lb": ((16384,), _U8), "cw_wb": ((16384,), _U8),
    "cw_h8": ((2490368,), _U8), "cw_hx": ((1835008,), _U16),
    "cw_hxh": ((0,), _U8), "cw_hf": ((524288,), _U8),
    "cw_hc": ((131072,), _U8),
    "cw_cs": ((950272,), _U8), "cw_hs": ((4194304,), _U8),
}
FFM_PLANES = {
    "cw_cu": ((53248, 3), _U8), "cw_cun": ((1,), np.int32),
    "cw_ci": ((118784,), _U16), "cw_ct": ((0, 3), _U8),
    "cw_cf": ((14848,), _U8), "cw_cc": ((16384,), _U8),
    "cw_lb": ((2048,), _U8), "cw_wb": ((2048,), _U8),
    "cw_h8": ((311296,), _U8), "cw_hx": ((229376,), _U16),
    "cw_hxh": ((0,), _U8), "cw_hf": ((65536,), _U8),
    "cw_hc": ((16384,), _U8),
    "cw_cs": ((118784,), _U8), "cw_hs": ((524288,), _U8),
}
DCN_PLANES = {
    "cw_cu": ((40960, 3), _U8), "cw_cun": ((1,), np.int32),
    "cw_ci": ((344064,), _U16), "cw_ct": ((131072, 3), _U8),
    "cw_cf": ((59392,), _U8), "cw_cc": ((65536,), _U8),
    "cw_lb": ((8192,), _U8), "cw_wb": ((8192,), _U8),
    "cw_h8": ((1245184,), _U8), "cw_hx": ((917504,), _U16),
    "cw_hxh": ((0,), _U8), "cw_hf": ((262144,), _U8),
    "cw_hc": ((65536,), _U8),
    "cw_cs": ((475136,), _U8), "cw_hs": ((2097152,), _U8),
}
XDEEPFM_PLANES = {
    "cw_cu": ((55296,), _U32), "cw_cun": ((1,), np.int32),
    "cw_ci": ((118784,), _U16), "cw_ct": ((0,), _U32),
    "cw_cf": ((14848,), _U8), "cw_cc": ((16384,), _U8),
    "cw_lb": ((2048,), _U8), "cw_wb": ((2048,), _U8),
    "cw_h8": ((311296,), _U8), "cw_hx": ((229376,), _U16),
    "cw_hxh": ((0,), _U8), "cw_hf": ((65536,), _U8),
    "cw_hc": ((16384,), _U8),
    "cw_cs": ((118784,), _U8), "cw_hs": ((524288,), _U8),
}
# AutoInt's cell has xDeepFM's rows, table size, batch and head: one real
# batch (seed 1) ships the same plane capacities
# (scripts/aot_dense_step.py --config autoint_ftrl_criteo_tb, PR 47)
AUTOINT_PLANES = XDEEPFM_PLANES
# so has FiBiNET's, whose sparse half IS xDeepFM's (two tables, w and emb)
# (scripts/aot_dense_step.py --config fibinet_ftrl_criteo_tb, PR 52)
FIBINET_PLANES = XDEEPFM_PLANES


def _cell_train_step(topo, config: str):
    """(cfg, step): the TrainStep of a one-chip benchmark configuration
    (benchmarks/configs/<config>.json) built for a described v5e."""
    from benchmarks.harness import manifest
    from xflow_tpu.config import Config
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel import mesh as meshes
    from xflow_tpu.parallel.step import TrainStep

    doc = manifest.config_file(f"benchmarks/configs/{config}.json")
    cfg = Config(**{
        k: v for k, v in manifest.apply_rehearsal(doc, False).items()
        if k not in manifest.CONFIG_META
    })
    step = TrainStep(
        make_model(cfg), make_optimizer(cfg), cfg,
        meshes.make_mesh(1, devices=list(topo.devices)),
    )
    assert step._hot_impl == "auto"
    return cfg, step


def _lowered_cell_step(
    topo, config: str, planes: dict, ships_slots: bool = True
):
    """The train step of a one-chip benchmark configuration
    (benchmarks/configs/<config>.json) lowered for a described v5e, its
    dictionary-wire batch given as plane shapes (``planes``: name ->
    (shape, dtype), the capacities of one real batch; ``ships_slots``:
    whether the family reads field ids, so that its wire ships the slots
    planes).  A family that owns dense replicated parameters is handed
    the shapes of its ``dense_init``."""
    from xflow_tpu.parallel import mesh as meshes

    cfg, step = _cell_train_step(topo, config)
    mesh, model = step.mesh, step.model
    assert step.wire_format == "dict" and step._ship_slots == ships_slots

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows, whole = meshes.table_sharding(mesh), meshes.replicated(mesh)
    dense = (
        jax.eval_shape(model.dense_init, jax.random.PRNGKey(0))
        if hasattr(model, "dense_init") else {}
    )
    state = {
        "tables": {
            spec.name: {
                name: shaped((cfg.table_size, spec.dim), jnp.float32, rows)
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {
            name: shaped(a.shape, a.dtype, whole) for name, a in dense.items()
        },
        "step": shaped((), jnp.int32, whole),
    }
    batch = {
        k: shaped(shape, dtype, step._bsharding)
        for k, (shape, dtype) in planes.items()
    }
    return cfg, step, step.train.lower(state, batch)


def _program_peak(compiled) -> int:
    """Bytes the program needs on the device: the donated state's outputs
    take no room of their own."""
    ma = compiled.memory_analysis()
    return (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes
        + ma.output_size_in_bytes - ma.alias_size_in_bytes
    )


@pytest.fixture(scope="module")
def mvm_cell_step(topo):
    """(cfg, lowered, compiled): the MVM train step at the geometry of the
    benchmark's mvm_tb.train_packed (benchmarks/configs/
    mvm_ftrl_criteo_tb.json: 2^25 rows x 10, B=131072, 8 + 32 slots, 40
    fields, the dictionary wire's plane capacities of one real batch,
    seed 1) for a described v5e, compiled once (3 min here) for the tests
    that read it."""
    with _without_compile_cache():
        cfg, _, lowered = _lowered_cell_step(
            topo, "mvm_ftrl_criteo_tb", MVM_PLANES
        )
        return cfg, lowered, lowered.compile()


@pytest.fixture(scope="module")
def dcn_cell_step(topo):
    """(cfg, step, lowered, compiled): the DCN train step at the geometry
    of the benchmark's dcn_tb.train_packed for a described v5e, compiled
    once (1 min here) for the tests that read it."""
    with _without_compile_cache():
        cfg, step, lowered = _lowered_cell_step(
            topo, "dcn_ftrl_criteo_tb", DCN_PLANES
        )
        return cfg, step, lowered, lowered.compile()


@pytest.fixture(scope="module")
def autoint_cell_step(topo):
    """(cfg, step, lowered, compiled): the AutoInt train step at the
    geometry of the benchmark's autoint_tb.train_packed for a described
    v5e, compiled once (40 s here) for the tests that read it."""
    with _without_compile_cache():
        cfg, step, lowered = _lowered_cell_step(
            topo, "autoint_ftrl_criteo_tb", AUTOINT_PLANES
        )
        return cfg, step, lowered, lowered.compile()


@pytest.fixture(scope="module")
def ffm_cell_step(topo):
    """(cfg, step, lowered, compiled): the FFM train step at the geometry
    of the benchmark's ffm_tb.train_packed for a described v5e, compiled
    once (40 s here) for the tests that read it."""
    with _without_compile_cache():
        cfg, step, lowered = _lowered_cell_step(
            topo, "ffm_ftrl_criteo_tb", FFM_PLANES
        )
        return cfg, step, lowered, lowered.compile()


def _scatters(text: str) -> list[tuple[str, str, str]]:
    """(operand, indices, updates) types of every scatter of a compiled
    program, read off the signature of the fused computation that holds
    it: ``(param_0: f32[T,D], param_1: s32[n], param_2: f32[n,D])``."""
    found = []
    for block in text.split("\n\n"):
        if " scatter(" not in block:
            continue
        head = next(
            line for line in block.splitlines() if line.rstrip().endswith("{")
        )
        params = re.findall(r"param_[\d.]+: (\w+\[[\d,]*\])", head)
        assert len(params) == 3, head
        found.append(tuple(params))
    return found


# the cell's fixture, its widest table's name, the program peak of the
# parent's step (PR 47's tree, compiled here for the same described v5e;
# AutoInt's less the 2 GiB gradient buffer that PR 57 took from it), the
# writes into the wide table
@pytest.mark.parametrize("cell,planes,wide,parent_gib,writes", [
    ("mvm_cell_step", MVM_PLANES, "v", 9.189, 1),
    ("dcn_cell_step", DCN_PLANES, "emb", 9.316, 1),
    ("autoint_cell_step", AUTOINT_PLANES, "emb", 8.018 - 1.6, 3),
])
def test_cold_scatter_hands_a_wide_table_an_index_per_dictionary_and_tail_entry_on_v5e(
    request, cell, planes, wide, parent_gib, writes
):
    """PR 48, compiled for a described v5e at the plane capacities of one
    real batch of mvm_tb.train_packed, dcn_tb.train_packed and
    autoint_tb.train_packed: the scatter-add into the [T, D] gradient
    buffer of the cell's wide table takes cap(cu) + cap(ct) indices and
    as many float32 rows (MVM 305 152 of 1 048 576 padded slots, DCN
    172 032 of 524 288, AutoInt 55 296 of 131 072), never an operand of
    a padded plane's height; the padded slots' [B * max_nnz, D]
    gradients meet only the [cap(cu), D] dictionary buffer
    (step.py::dict_cold_grads); DCN's one-column ``w`` keeps an index
    per padded slot on its flat view; and the program's peak stays
    within 0.2 GiB of the parent's.  AutoInt's batch has no tail, so
    since PR 57 its ``emb`` has no gradient buffer at all
    (step.py::touched_rows_selects): the same 55 296 indices reach the
    table three times, as the sets of param, n and z, and the program
    needs 1.6 GiB less (8.018 -> 6.416: the 2 GiB buffer, less what now
    sets the peak)."""
    cfg, *_, compiled = request.getfixturevalue(cell)
    cap_u, cap_t = planes["cw_cu"][0][0], planes["cw_ct"][0][0]
    t, m = cfg.table_size, cfg.batch_size * cfg.max_nnz
    d = {"v": cfg.v_dim, "emb": cfg.emb_dim}[wide]
    assert cap_u + cap_t < m
    scatters = _scatters(compiled.as_text())
    into_table = [s for s in scatters if s[0] == f"f32[{t},{d}]"]
    assert into_table == writes * [(
        f"f32[{t},{d}]", f"s32[{cap_u + cap_t}]", f"f32[{cap_u + cap_t},{d}]"
    )], scatters
    by_slot = [s for s in scatters if s[2].startswith(f"f32[{m}")]
    narrow = [(f"f32[{t}]", f"s32[{m}]", f"f32[{m}]")] if cell == "dcn_cell_step" else []
    assert by_slot == [
        (f"f32[{cap_u},{d}]", f"s32[{m}]", f"f32[{m},{d}]")
    ] + narrow, scatters
    peak = _program_peak(compiled) / (1 << 30)
    assert abs(peak - parent_gib) < 0.2, peak


def test_mvm_step_contracts_fields_in_float32_and_fits_a_v5e(mvm_cell_step):
    """The MVM train step at the geometry of the benchmark's
    mvm_tb.train_packed for a described v5e (``mvm_cell_step``).
    Lowered: every contraction asks for float32 (Precision.HIGHEST), the
    one-hot field contractions of models/blocks.py among them:
    ``field_contract`` [B, K, F] x [B, K, D] over K, in ``logit`` and
    again in ``grad_logit``, and ``field_pick`` [B, K, F] x [B, F, D]
    over F, the backward's pick of each entry's own factor (PR 33).  At
    this geometry K = F = 40, so all three have the same operand types
    and only their contracting dimensions tell them apart.  At default
    precision the TPU rounds the operands to bfloat16: the field sums
    miss the reference (PERF.md section 6, PR 31), and a picked
    ``1 + s`` would keep 8 bits.  Compiled: XLA has not turned the pick
    back into the gather it replaced (one index per entry, 5.2 M of
    them, 112 ms of the parent's 390 ms step: no gather brings a
    [B, K, D] result out of a [B, F, D] operand), and the program fits
    the chip with the room the file's ``reduced`` argues from (9.19 GiB
    of 15.75, 9.22 with the gather; at 2^26 rows the compiler refuses
    it)."""
    cfg, lowered, compiled = mvm_cell_step
    dots = [
        line for line in lowered.as_text().splitlines() if "dot_general" in line
    ]
    b, k = cfg.batch_size, cfg.max_nnz + cfg.hot_nnz
    onehot = f"tensor<{b}x{k}x{cfg.max_fields}xf32>"
    by_field = [
        line for line in dots
        if f"({onehot}, tensor<{b}x{k}x{cfg.v_dim}xf32>)" in line
    ]
    over_k = [line for line in by_field if "contracting_dims = [1] x [1]" in line]
    over_f = [line for line in by_field if "contracting_dims = [2] x [1]" in line]
    # the sum by field in logit and in grad_logit; grad_logit's pick
    assert (len(by_field), len(over_k), len(over_f)) == (3, 2, 1), dots
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots
    per_entry = (f"f32[{b * k},{cfg.v_dim}]", f"f32[{b},{k},{cfg.v_dim}]")
    picks = [
        line for line in _gather_lines(compiled)
        if line.split("=", 1)[1].strip().startswith(per_entry)
    ]
    assert not picks, picks
    peak = _program_peak(compiled)
    assert 9.0 * (1 << 30) < peak < 9.5 * (1 << 30), peak


def test_mvm_pass_keeps_its_padded_rows_on_v5e(mvm_cell_step):
    """The trap beside the flat pass (_optimizer_pass; PERF.md section 6,
    PR 37): a [2^25, 10] table costs 16 columns in (8,128) tiles, so its
    flat view is not the same bytes and would be a copy of the state.
    MVM's one pass stays ONE fusion over the padded rows, and the program
    holds no table-sized copy."""
    cfg, _, compiled = mvm_cell_step
    text = compiled.as_text()
    t, d = cfg.table_size, cfg.v_dim
    ((results, _),) = _optimizer_passes(text, t)
    assert results.count(f"f32[{t},{d}]{{0,1:T(8,128)}}") == 3, results
    assert f"f32[{t * d}]" not in text
    assert not _table_sized_copies(text, t)
    assert not _table_sized_copies(text, t * d)


def test_mvm_step_indexes_its_head_and_scans_only_the_scatter_on_v5e(
    mvm_cell_step
):
    """PR 45: at D = 10 the head's GATHER is plain indexing of the
    [H, D] slice (ops/hot.py::gather_form; 1.5 ns a slot in the step
    against the scan's 7.2), so the compiled MVM step has no one-hot product
    ``f32[1024,1280]`` under xf.gather, while the head's SCATTER is still
    the scan (its product ``f32[128,1280]`` under xf.scatter).  The
    gather reads the slice, ``f32[16384,10]``, never the table, a piece
    of hot._PLAIN_GATHER_SLOTS slots at a time, and a piece leaves its
    loop with the slots on the lanes (``f32[pieces,10,slots]``, 16
    sublanes for 10 columns): gathered whole, ``f32[4194304,10]`` is one
    128-lane row a slot, 2 GiB, and put the program's peak at 10.2 GiB
    for 9.19 (test_mvm_step_contracts_fields_in_float32_and_fits_a_v5e
    holds the peak)."""
    from xflow_tpu.ops import hot
    from xflow_tpu.parallel.step import _HLO_OP_NAME_RE, scope_of

    cfg, _, compiled = mvm_cell_step
    h, d = cfg.hot_size, cfg.v_dim
    m, c = cfg.batch_size * cfg.hot_nnz, hot._PLAIN_GATHER_SLOTS
    h1, h2 = hot.hot_factors(h)
    assert (h, d, m, h1, h2) == (16384, 10, 4194304, 128, 128)
    by_scope: dict[str, list[str]] = {"xf.gather": [], "xf.scatter": []}
    text = compiled.as_text()
    for line in text.splitlines():
        found = _HLO_OP_NAME_RE.search(line)
        if found and scope_of(found.group(1)) in by_scope:
            by_scope[scope_of(found.group(1))].append(line.split(", metadata")[0])
    products = {
        scope: [
            line.split(" = ")[1].split("{")[0] for line in lines
            if " convolution(" in line
        ]
        for scope, lines in by_scope.items()
    }
    assert products == {"xf.gather": [], "xf.scatter": [f"f32[{h1},{d * h2}]"]}
    # (the gather is fused: its operand's type is in the fusion's head)
    pieces = [
        line for line in _gather_lines(compiled) if f"= f32[{c},{d}]" in line
    ]
    assert len(pieces) == 1 and f"slice_sizes={{1,{d}}}" in pieces[0], pieces
    assert re.search(
        rf"\(param_[\d.]+: f32\[{h},{d}\], param_[\d.]+: s32\[{c}\]\) -> "
        rf"f32\[{c},{d}\]", text
    )
    assert f"f32[{m // c},{d},{c}]{{2,1,0:" in text
    assert f"f32[{m},{d}]" not in text


# configuration, the hot slots a step that its gather indexes / scans, a
# table, and those whose gradients its scatter adds plainly / scans
@pytest.mark.parametrize("config,plain,scan,scatter_plain,scatter_scan", [
    ("lr_ftrl_criteo_tb", 0, 131072 * 28, 0, 131072 * 28),
    ("mvm_ftrl_criteo_tb", 4194304, 0, 0, 4194304),
    ("dcn_ftrl_criteo_tb", 2097152, 2097152, 2097152, 2097152),
])
def test_wire_row_books_the_head_slots_by_the_form_of_their_gather(
    topo, config, plain, scan, scatter_plain, scatter_scan
):
    """``hot_plain_slots`` / ``hot_scan_slots`` of TrainStep._book_wire
    (the ``_wire`` row's ``hot_plain_slots_per_step`` /
    ``hot_scan_slots_per_step``), from shapes, on the step built for a
    described v5e at the geometry of the benchmark's cells: MVM's one
    table of ten columns is indexed (4 194 304 / 0), DCN's ``emb`` is
    indexed and its ``w`` scanned (2 097 152 each), LR's ``w`` scanned
    (0 / all 3 670 016).  And, PR 49, ``hot_scatter_plain_slots`` /
    ``hot_scatter_scan_slots`` by the form of their SCATTER
    (ops/hot.py::scatter_form, a constant of its own): DCN's ``emb``
    (26 columns) is added plainly and its ``w`` scanned, MVM's ten
    columns and LR's one stay the scan.  Nothing is lowered."""
    cfg, step = _cell_train_step(topo, config)
    booked: dict[str, float] = {}
    step.obs = types.SimpleNamespace(
        counter=lambda name, v=1.0: booked.__setitem__(name, v)
    )
    b = cfg.batch_size
    step._book_wire(
        0, b, cold_slots=b * cfg.max_nnz, hot_slots=b * cfg.hot_nnz
    )
    assert (booked["wire.hot_plain_slots"], booked["wire.hot_scan_slots"]) == (
        plain, scan
    )
    assert (
        booked["wire.hot_scatter_plain_slots"],
        booked["wire.hot_scatter_scan_slots"],
    ) == (scatter_plain, scatter_scan)
    assert booked["wire.plain_hot_slots"] == 0  # no table off the head


def test_lr_step_runs_its_pass_on_the_flat_view_and_fits_a_v5e(
    topo, no_compile_cache
):
    """The LR train step at the geometry of the benchmark's
    lr_tb.train_packed (benchmarks/configs/lr_ftrl_criteo_tb.json: 2^28
    rows of one column, B=131072, 12 + 28 slots, the dictionary wire's
    plane capacities of one real batch, seed 1) for a described v5e.  The
    device's default for f32[2^28, 1] is tiles of ONE sublane
    (``{0,1:T(1,128)}``), on which the FTRL pass ran at 348 GB/s; the
    flat view of the same bytes has whole 8 x 128 tiles
    (``{0:T(1024)}``), which the scatter beside the pass already reads
    (_optimizer_pass; PERF.md section 6, PR 37).  Compiled: the one
    table-sized fusion under xf.optimizer takes and yields the flat
    arrays, nothing that runs in that scope is left on one-sublane tiles
    (the views are bitcasts), no table-sized copy is made for it, and the
    program's peak is the parent's 4.018 GiB."""
    cfg, _, lowered = _lowered_cell_step(
        topo, "lr_ftrl_criteo_tb", LR_PLANES, ships_slots=False
    )
    compiled = lowered.compile()
    text = compiled.as_text()
    t = cfg.table_size
    flat, column = f"f32[{t}]{{0:T(1024)}}", f"f32[{t},1]"
    ((results, body),) = _optimizer_passes(text, t)
    assert results.count(flat) == 3 and column not in results, results
    operands = [line for line in body.splitlines() if " parameter(" in line]
    assert len(operands) == 4 and all(f"f32[{t}]" in o for o in operands)
    assert column not in body, body
    left = [
        line.split("metadata")[0] for line in text.splitlines()
        if "xf.optimizer" in line and column in line.split("metadata")[0]
        and " bitcast(" not in line
    ]
    assert not left, left
    assert not _table_sized_copies(text, t)
    assert _program_peak(compiled) <= 1.01 * 4.018 * (1 << 30)


def test_ffm_step_contracts_fields_in_float32_and_fits_a_v5e(ffm_cell_step):
    """The FFM train step at the geometry of the benchmark's
    ffm_tb.train_packed (benchmarks/configs/ffm_ftrl_criteo_tb.json: 2^21
    rows, w of one column and v of 40 fields x 4 = 160, B=16384, 8 + 32
    slots, the dictionary wire's plane capacities of one real batch, seed
    1) for a described v5e.  Lowered: the field contraction [B, K, F] x
    [B, K, 160] over K (blocks.field_contract) and its transpose, which
    autodiff writes
    (grads_from_rows pulls the residual back through the logit), both ask
    for float32 (Precision.HIGHEST), as does every other dot; w's hot
    occurrences go through the MXU head (ops/hot.py: its one-hot matmuls,
    no gather of w by the hot plane) and v's, whose table opts out of it
    (TableSpec.hot=False), are one plain gather of table rows by the
    [B, hot_nnz] plane.  Compiled: the program takes 7.65 GiB of 15.75.
    Until PR 59 it took 13.78 (the file's ``reduced`` still argues 2^21
    rows from 13.94), most of it layout (PERF.md section 6, PR 34-35):
    v's state comes in rows-minor and was copied to columns-minor and
    back inside the step, six table-sized copies that set the peak; the
    pass now runs on the layout the state comes in
    (test_ffm_pass_runs_on_the_resident_layout_on_v5e).  The dictionary
    route lays v's 160-column row out by row gathers (dict_cold_rows):
    of the padded [B, max_nnz, 1] column planes, 160 families of them
    until PR 35 (0.16 GiB of the peak and 82 ms of the step), one is
    left, w's single column."""
    cfg, step, lowered, compiled = ffm_cell_step
    assert step._mxu_hot == {"w": True, "v": False}
    text = lowered.as_text().splitlines()
    b, k, f = cfg.batch_size, cfg.max_nnz + cfg.hot_nnz, cfg.max_fields
    t, e = cfg.table_size, f * cfg.ffm_v_dim
    onehot, rows = f"tensor<{b}x{k}x{f}xf32>", f"tensor<{b}x{k}x{e}xf32>"
    dots = [line for line in text if "dot_general" in line]
    forward = [line for line in dots if f"({onehot}, {rows})" in line]
    transposed = [line for line in dots if f"({rows}, {onehot})" in line]
    head = [line for line in dots if f"x{e}x" not in line]
    assert (len(forward), len(transposed)) == (1, 1), dots
    assert len(head) == len(dots) - 2 >= 2, dots  # w's one-hot scans
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots
    by_hot_plane = [
        line for line in text
        if "stablehlo.gather" in line
        and f"tensor<{b}x{cfg.hot_nnz}x1xi32>" in line
    ]
    assert len(by_hot_plane) == 1, by_hot_plane
    assert f"(tensor<{t}x{e}xf32>, " in by_hot_plane[0]
    # instructions whose result is a padded column plane of the cold
    # slots: w's one column and no more (9; 667 with v's 160 columns)
    planes = set(re.findall(
        rf"(\S+) = f32\[{b},{cfg.max_nnz},1\]", compiled.as_text()
    ))
    assert len(planes) <= 16, sorted(planes)
    peak = _program_peak(compiled)
    assert 7.4 * (1 << 30) < peak < 7.9 * (1 << 30), peak


def test_ffm_pass_runs_on_the_resident_layout_on_v5e(ffm_cell_step):
    """PR 59: the FTRL pass over FFM's v [2^21, 160] on the bytes the chip
    keeps (step.py::_optimizer_pass's third arm, resident_pass_selects).
    The device's layout for f32[2^21, 160] is rows-minor,
    ``{0,1:T(8,128)}`` (the rows on the lanes, 160 columns on 20 sublane
    tiles, 1.25 GiB, no padding); the step's row gathers and scatter-adds
    run columns-minor, ``{1,0:T(8,128)}``, where 160 columns cost 256
    (2 GiB).  Left alone, XLA put the elementwise pass on the padded
    layout too and relaid ``param``, ``n``, ``z`` in and the three
    results out: six table-sized copies, 31.6 of a 114.5 ms step (ledger,
    PR 58), a 13.78 GiB program.  With the operands and results held to
    the resident layout inside the program: the ONE table-sized fusion
    under xf.optimizer for v reads ``param``, ``n`` and ``z`` as the
    program's own arguments and yields three rows-minor arrays, which the
    program's outputs alias; ``n`` and ``z`` are never copied; and two
    table-sized copies are left, by name and not only by count (PR 54's
    lesson): ``param`` once to columns-minor for the row gathers, and the
    scatter's gradient buffer once to rows-minor on its way to the pass
    (booked to xf.scatter).  The ``_wire`` row's counter names the table;
    test_ffm_step_contracts_fields_in_float32_and_fits_a_v5e holds the
    program's peak (7.65 GiB, under the 8.5 ISSUE 59 asks)."""
    cfg, step, _, compiled = ffm_cell_step
    text = compiled.as_text()
    t, e = cfg.table_size, cfg.max_fields * cfg.ffm_v_dim
    assert (t, e) == (2097152, 160)
    assert step._resident_pass_tables == {"v": t * e}
    resident, padded = "{0,1:T(8,128)}", "{1,0:T(8,128)}"
    (results,) = [
        results for results, _ in _optimizer_passes(text, t)
        if f"f32[{t},{e}]" in results
    ]
    assert results.count(f"f32[{t},{e}]{resident}") == 3, results
    assert padded not in results, results
    (call,) = [
        line for line in text.splitlines()
        if " fusion(" in line and "xf.optimizer" in line
        and f"f32[{t},{e}]" in line.split(" fusion(")[0]
    ]
    operands = call.split(" fusion(")[1].split(")")[0]
    for name in ("param", "n", "z"):  # the arguments themselves, not copies
        assert f"%state__tables____v____{name}__" in operands, operands
    copies = [
        line for line in text.splitlines()
        if re.search(rf"= f32\[{t},{e}\]\S* copy\(", line)
    ]
    assert len(copies) <= 2, copies
    for line in copies:
        head = line.split(", metadata")[0]
        if f"f32[{t},{e}]{padded} copy(" in head:  # in, for the row gathers
            assert "copy(%state__tables____v____param__" in head, head
        else:  # the gradient buffer, from the scatter to the pass
            assert f"f32[{t},{e}]{resident} copy(%fusion" in head, head
            assert "xf.scatter" in line and "xf.optimizer" not in line, line
    # the three results are the program's outputs, in place
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= 3 * 4 * t * (e + 1), alias


# sha256 of the lowered train program (StableHLO text, the Mosaic kernels'
# serialized bodies blanked: they embed the checkout's path) of the four
# configurations the benchmark measured before PR 39, pinned on PR 38's
# tree BEFORE models/blocks.py was edited, anew by PR 44, MVM's and
# FM's again by PR 45, MVM's, DCN's and xDeepFM's again by PR 48, DCN's
# alone by PR 49, xDeepFM's alone by PR 57 and FFM's alone by PR 59 (the
# tests' docstrings say why).
MEASURED_PROGRAMS_SHA256 = {
    "lr_ftrl_criteo_tb": (
        "e288dfde6bd0a7646d26153ef9b2ad0ba6d6a5056fe6a02cc9440b498b84d5bc"
    ),
    "mvm_ftrl_criteo_tb": (
        "2a8d53c47ed6328a8605cd365c3e448c86db0d6bb9f37924f1eba2e367947be5"
    ),
    "ffm_ftrl_criteo_tb": (
        "b1be9bf1ee05f13864269d8f583c40d0acd195d3b21633431045d5356335ef87"
    ),
    "fm_ftrl_criteo_tb (cut, 2x2)": (
        "1a8e174f5811f4d551ad0a51af66c814e01e49657155944889138ec1c034b32a"
    ),
    # the two measured programs built on models/blocks.py's dense half,
    # pinned by PR 47 on PR 46's tree BEFORE blocks.py was edited (and equal
    # after: cin_stack's padding and slicing went into two helpers that the
    # attention block shares); anew by PR 48, whose cold scatter route
    # their emb tables take; DCN's again by PR 49 (emb's head scatter);
    # xDeepFM's again by PR 57 (emb's optimizer on the dictionary's rows)
    "dcn_ftrl_criteo_tb": (
        "22461118a9ecac7b78c7f12a043db151d345f5f75359bfd31e7b9491965154d1"
    ),
    "xdeepfm_ftrl_criteo_tb": (
        "015974fe6e82ab78886fe3adf7c1c41438557f040013b62df99ef1a69cbc7e7e"
    ),
}
DENSE_PROGRAMS = {
    "dcn_ftrl_criteo_tb": DCN_PLANES, "xdeepfm_ftrl_criteo_tb": XDEEPFM_PLANES,
}


def _program_sha256(lowered) -> str:
    import hashlib

    text = re.sub(
        r'backend_config = "[^"]*"', 'backend_config = ""', lowered.as_text()
    )
    return hashlib.sha256(text.encode()).hexdigest()


def test_measured_train_programs_lower_to_the_pinned_text(topo):
    """PR 39 put every matmul of the dense half (models/blocks.py: the MLP
    blocks, DCN's output product) through one float32 helper, opened the
    scope xf.dense inside xf.forward_backward and changed which ``xf.``
    name of a path ``op_scopes`` takes.  LR, FM, MVM and FFM call none of
    the MLP blocks and open no scope inside another, so their train
    programs lower to the text PR 38's tree lowered them to.  A PR that
    means to change one of these programs pins the new digest here and
    says so; one that does not has found what it changed by accident.
    PR 44 meant to change all four (every one has a head: the gather
    scan of ops/hot.py flattens it as [h1, D * h2] and emits a chunk as
    [D, C]; PERF.md section 6) and pinned the digests anew; PR 39 to 43
    had left them as they were.  PR 45 meant to change MVM's and the cut
    FM mesh's and NOT LR's and FFM's: the head's gather is chosen from
    the table's width (ops/hot.py::gather_form), plain indexing of the
    [H, D] slice at D = 10 (MVM's v, FM's v), the scan at D = 1 (LR's w,
    FM's and FFM's w; FFM's v is off the head), so LR's and FFM's digests
    are PR 44's, the control that every D = 1 head runs the parent's
    program, and the other two are pinned anew.  PR 48 meant to change
    MVM's and NOT LR's, FFM's and the cut FM mesh's: the cold gradients
    of a whole dictionary-wire batch reach a table of 2 to 64 columns
    through the batch's dictionary (step.py::dict_cold_grads,
    DICT_SCATTER_COLUMNS: MVM's v), and a one-column table (LR's w,
    FFM's w), a 160-column one (FFM's v) and every batch on a mesh keep
    the scatter-add per padded slot: those three digests are PR 45's and
    PR 44's, the control that the cells which bypass the route run the
    parent's program, and MVM's is pinned anew.  PR 49 meant to change
    NONE of the four: the head's scatter is chosen from the table's
    width (ops/hot.py::scatter_form), a plain scatter-add from
    PLAIN_SCATTER_MIN_COLUMNS = 16 columns up, the scan below: LR's and
    FFM's w (one column) and MVM's and the FM mesh's v (ten) keep the
    scan, and all four digests are the parent's.  PR 57 meant to change
    NONE of the four either: the dense update of a table runs on the
    dictionary's rows alone only under a dictionary-wire batch with an
    EMPTY tail plane, for a table of 2 to 64 columns large enough for
    its index count (step.py::touched_rows_selects).  LR's and FFM's w
    have one column, FFM's v 160 and is off the head, MVM's batch has a
    tail of 262 144 entries and the mesh ships no plan: the selection is
    empty, a Python-level set, and the traced programs are the parent's
    to the instruction.  PR 59 meant to change FFM's and NOT the other
    three: the dense update's pass over a table of 64 columns or more
    that the chip keeps rows-minor holds its four operands and three
    results to that layout (step.py::resident_pass_selects,
    _optimizer_pass: seven layout constraints in the lowered text).
    FFM's v [2^21, 160] is the one such table; LR's w and FM's w have one
    column and keep the flat view, MVM's and FM's v ten columns, and the
    mesh is left out whatever the width: their digests are PR 57's, the
    control that every other pass lowers to the parent's text, and FFM's
    is pinned anew."""
    got = {
        "lr_ftrl_criteo_tb": _lowered_cell_step(
            topo, "lr_ftrl_criteo_tb", LR_PLANES, ships_slots=False
        )[2],
        "mvm_ftrl_criteo_tb": _lowered_cell_step(
            topo, "mvm_ftrl_criteo_tb", MVM_PLANES
        )[2],
        "ffm_ftrl_criteo_tb": _lowered_cell_step(
            topo, "ffm_ftrl_criteo_tb", FFM_PLANES
        )[2],
        "fm_ftrl_criteo_tb (cut, 2x2)": _lowered_fm_mesh_step(topo)[1],
    }
    assert {
        name: _program_sha256(lowered) for name, lowered in got.items()
    } == {
        name: digest for name, digest in MEASURED_PROGRAMS_SHA256.items()
        if name not in DENSE_PROGRAMS
    }
    # no operation of theirs sits under two DIFFERENT xf. names, so the
    # innermost name op_scopes takes is the first name it took before
    from xflow_tpu.parallel.step import _SCOPE_RE

    for name, lowered in got.items():
        paths = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))
        scoped = [path for path in paths if _SCOPE_RE.search(path)]
        nested = [p for p in scoped if len(set(_SCOPE_RE.findall(p))) > 1]
        assert len(scoped) > 50 and not nested, (name, nested[:3])


@pytest.mark.parametrize("config", sorted(DENSE_PROGRAMS))
def test_measured_dense_programs_lower_to_the_pinned_text(topo, config):
    """DCN's and xDeepFM's train programs are the two measured programs
    built on models/blocks.py's dense half (``dense_dot``, ``mlp_stack``,
    ``field_sum_tower``, ``cin_stack``), which the four digests above do
    not cover: an edit of that file that moves one of them says so here.
    PR 47 added the field-attention block beside them and lifted
    ``cin_stack``'s padding and slicing into helpers; both programs
    lowered to the text PR 46's tree lowered them to.  PR 48 meant to
    change both (emb, 26 and 10 columns, takes the cold scatter's
    dictionary route; models/blocks.py is untouched) and pinned them
    anew.  PR 49 meant to change DCN's and NOT xDeepFM's: the head's
    scatter of emb's 26 columns is a plain scatter-add
    (ops/hot.py::scatter_form), xDeepFM's ten columns keep the scan and
    PR 48's digest.  PR 57 meant to change xDeepFM's and NOT DCN's:
    xDeepFM's batch is a dictionary of 55 296 entries with no tail, so
    emb [2^25, 10] gets no gradient buffer and takes FTRL on those rows
    and on the head (step.py::touched_rows_selects, _touched_rows_pass;
    w, one column, keeps its buffer and flat pass); DCN's batch has a
    tail of 131 072 entries, which rules its emb out before its size is
    asked, and its digest is PR 49's."""
    lowered = _lowered_cell_step(topo, config, DENSE_PROGRAMS[config])[2]
    assert _program_sha256(lowered) == MEASURED_PROGRAMS_SHA256[config]


def test_dcn_step_multiplies_in_float32_under_xf_dense_and_fits_a_v5e(
    dcn_cell_step
):
    """The DCN train step at the geometry of the benchmark's
    dcn_tb.train_packed (benchmarks/configs/dcn_ftrl_criteo_tb.json: 2^24
    rows, w of one column and emb of 26, B=65536, 8 + 32 slots, 40 fields,
    six cross layers beside two hidden layers of 1024, the dictionary
    wire's plane capacities of one real batch, seed 1; the dense arrays
    handed in as shapes) for a described v5e.  Lowered: every dot asks for
    float32 (Precision.HIGHEST), and among them are the products with each
    dense matrix forward (``h @ w``), transposed into the activations
    (``dy @ w.T``) and into the weights (``h.T @ dy``): models/blocks.py's
    ``dense_dot`` and what autodiff makes of it; at default precision the
    TPU rounds the operands to bfloat16 and the step misses the
    benchmark's reference (PERF.md section 7, PR 38).  Both tables go
    through the head (ops/hot.py: no gather out of a table by the hot
    plane; since PR 45 w's hot rows come by the one-hot scan and emb's
    by indexing the [16384, 26] slice).
    Compiled: the instructions of the dense half carry ``xf.dense`` in
    ``op_scopes``' reading (the innermost name), the three products of
    each hidden layer among them (convolutions, as the TPU's compiler
    writes a dot); no table-sized copy of emb's state
    is made; and the program fits with the room the file's ``reduced``
    argues from, 9.31 GiB of 15.75 (at 2^25 rows the compiler refuses
    it: 16.47 G)."""
    from xflow_tpu.parallel.step import _HLO_OP_NAME_RE, scope_of

    cfg, step, lowered, compiled = dcn_cell_step
    assert step._mxu_hot == {"w": True, "emb": True}
    assert (cfg.cross_layers, cfg.deep_layers, cfg.hidden_dim) == (6, 2, 1024)
    text = lowered.as_text().splitlines()
    b, h = cfg.batch_size, cfg.hidden_dim
    p = cfg.max_fields * cfg.emb_dim
    dots = [line for line in text if "dot_general" in line]
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots

    def dot(lhs: str, rhs: str, out: str) -> int:
        sig = f"(tensor<{lhs}xf32>, tensor<{rhs}xf32>) -> tensor<{out}xf32>"
        return sum(sig in line for line in dots)

    for k, n in [(p, h), (h, h), (p + h, 1)]:  # w1, w2, w_out
        assert dot(f"{b}x{k}", f"{k}x{n}", f"{b}x{n}") >= 1, (k, n)  # forward
        if (k, n) == (h, h):  # dy @ w2.T has the forward's operand types
            assert dot(f"{b}x{h}", f"{h}x{h}", f"{b}x{h}") == 2
        else:
            assert dot(f"{b}x{n}", f"{k}x{n}", f"{b}x{k}") == 1, (k, n)
    # h.T @ dy, as autodiff orders its operands
    assert dot(f"{b}x{h}", f"{b}x{p}", f"{h}x{p}") == 1
    assert dot(f"{b}x{h}", f"{b}x{h}", f"{h}x{h}") == 1
    assert dot(f"{b}x1", f"{b}x{p + h}", f"1x{p + h}") == 1
    by_hot_plane = [
        line for line in text
        if "stablehlo.gather" in line
        and f"tensor<{b}x{cfg.hot_nnz}x1xi32>" in line
    ]
    assert not by_hot_plane, by_hot_plane
    # emb's hot rows: a piece of the slots at a time out of the [H, 26]
    # slice; w's: the scan (no gather out of [H, 1])
    from xflow_tpu.ops.hot import _PLAIN_GATHER_SLOTS

    of_head = [
        line for line in text
        if "stablehlo.gather" in line and f"(tensor<{cfg.hot_size}x" in line
    ]
    assert len(of_head) == 1 and (
        f"(tensor<{cfg.hot_size}x{cfg.emb_dim}xf32>, "
        f"tensor<{_PLAIN_GATHER_SLOTS}x1xi32>)" in of_head[0]
    ), of_head

    hlo = compiled.as_text()
    in_dense = [
        line for line in hlo.splitlines()
        if (m := _HLO_OP_NAME_RE.search(line))
        and scope_of(m.group(1)) == "xf.dense"
    ]
    # the six products of the two hidden layers (forward, into the
    # activations, into the weights; the output's have one column and
    # compile to reductions)
    products = sorted(
        line.split(" = ")[1].split("{")[0] for line in in_dense
        if " convolution(" in line
    )
    assert products == sorted(
        [f"f32[{b},{h}]"] * 3 + [f"f32[{b},{p}]", f"f32[{p},{h}]", f"f32[{h},{h}]"]
    ), products
    assert len(in_dense) > 100
    assert all("xf.forward_backward" in line for line in in_dense)
    # (w's 64 MiB flat view may be moved to another memory space whole:
    # a prefetch, not a layout change)
    assert not [
        line for line in _table_sized_copies(hlo, cfg.table_size)
        if f"f32[{cfg.table_size},{cfg.emb_dim}]" in line
    ]
    peak = _program_peak(compiled)
    assert 9.0 * (1 << 30) < peak < 10.5 * (1 << 30), peak


# the cell's fixture, the scatter scans its step keeps (one-hot products
# under xf.scatter), the program peak of the parent's step (PR 48's tree,
# compiled here for the same described v5e; AutoInt's less the gradient
# buffer that PR 57 took from it), the dictionary's entries whose sums are
# folded into the head's (PR 57: AutoInt's alone, whose emb takes its
# optimizer on the dictionary's rows)
@pytest.mark.parametrize("cell,scans,parent_gib,folded", [
    ("dcn_cell_step", ["f32[128,128]"], 9.322, 0),
    ("autoint_cell_step", [], 8.014 - 1.6, 55296),
])
def test_wide_head_gradients_are_added_plainly_a_piece_at_a_time_on_v5e(
    request, cell, scans, parent_gib, folded
):
    """PR 49: from hot.PLAIN_SCATTER_MIN_COLUMNS = 16 columns the head's
    SCATTER is a plain scatter-add into the [H, D] slice
    (ops/hot.py::scatter_form), so the compiled DCN step has no one-hot
    product ``f32[128,3328]`` under xf.scatter (the scan's, 32.6 of the
    parent's 229.1 ms step) and keeps ``w``'s ``f32[128,128]`` one (D = 1
    stays the scan), and AutoInt's (one table, D = 16) has none.
    ``emb``'s hot gradients meet the ``f32[16384,D]`` slice
    hot._PLAIN_SCATTER_SLOTS slots at a time, the pieces handed to the
    loop with the slots on the lanes (``f32[pieces,D,slots]``): as
    ``[2097152, 26]`` rows DCN's would be one 128-lane row a slot, a
    1 GiB temporary.  And the program's peak stays within 0.2 GiB of the
    parent's."""
    from xflow_tpu.ops import hot
    from xflow_tpu.parallel.step import _HLO_OP_NAME_RE, scope_of

    cfg, step, _, compiled = request.getfixturevalue(cell)
    h, d = cfg.hot_size, cfg.emb_dim
    m, c = cfg.batch_size * cfg.hot_nnz, hot._PLAIN_SCATTER_SLOTS
    assert h == 16384 and m % c == 0 and hot.hot_factors(h) == (128, 128)
    assert hot.scatter_form(d, step._hot_impl) == "seg"
    assert hot.scatter_form(1, step._hot_impl) == "mxu"
    text = compiled.as_text()
    products = [
        line.split(" = ")[1].split("{")[0] for line in text.splitlines()
        if " convolution(" in line
        and (found := _HLO_OP_NAME_RE.search(line))
        and scope_of(found.group(1)) == "xf.scatter"
    ]
    assert products == scans, products
    into_head = [s for s in _scatters(text) if s[0] == f"f32[{h},{d}]"]
    # a piece of the hot slots at a time; then, where the table has no
    # gradient buffer, the sums of the dictionary's entries below H
    # (step.py::_touched_rows_pass; the others carry index H and drop)
    assert sorted(into_head) == sorted(
        (f"f32[{h},{d}]", f"s32[{n}]", f"f32[{n},{d}]")
        for n in (c, folded) if n
    )
    # the pieces: slots minor; never a row a slot
    assert re.search(rf"f32\[{m // c},{d},{c}\]\{{2,[01],[01]:", text)
    assert f"f32[{m},{d}]{{1,0:" not in text
    assert f"f32[{m // c},{c},{d}]{{2,1,0:" not in text
    peak = _program_peak(compiled) / (1 << 30)
    assert abs(peak - parent_gib) < 0.2, peak


def test_xdeepfm_step_contracts_pairs_in_float32_a_slice_at_a_time_on_v5e(
    topo, no_compile_cache
):
    """The xDeepFM train step at the geometry of the benchmark's
    xdeepfm_tb.train_packed (benchmarks/configs/xdeepfm_ftrl_criteo_tb.json:
    2^25 rows, w of one column and emb of 10, B=16384, 8 + 32 slots, 40
    fields, three CIN layers of 200 maps beside two hidden layers of 400,
    the dictionary wire's plane capacities of one real batch, seed 1; the
    dense arrays handed in as shapes) for a described v5e.  Lowered: every
    dot asks for float32 (Precision.HIGHEST), the CIN's contractions with a
    ``cin_w`` among them: three a layer (forward ``[maps, H m] x [H m, N]``,
    into the pairs, into the weights) less the one no gradient needs, none
    of them done twice for the rematerialised backward (the checkpoint
    keeps the products' outputs and multiplies the pairs again); at default
    precision the TPU rounds both operands to bfloat16.  A layer's pair
    tensor is ``B H m D`` = 1.31e9 floats whole (5.24 GB): no array of the
    lowered or the compiled program has as many elements, or a tenth as
    many, beside the tables' own; the largest the CIN makes is a slice's
    (128 examples: ``[200, 40, 1280]``).  Compiled: the CIN's instructions,
    forward, rematerialised and backward, and the two loops over the
    slices, carry ``xf.cin`` in ``op_scopes``' reading (the innermost
    name), the contractions (convolutions, as the TPU's compiler writes a
    dot) among them and none under ``xf.dense``, which keeps the DNN's; no
    table-sized copy of emb's state is made; emb takes its optimizer on
    the dictionary's rows with no gradient buffer (PR 57,
    ``_assert_touched_rows_update``); and the program fits with more than
    the room the file's ``reduced`` argues from (8.42 GiB of 15.75 with
    the buffer, 6.9 without)."""
    from xflow_tpu.models import blocks
    from xflow_tpu.parallel.step import _HLO_OP_NAME_RE, scope_of

    cfg, step, lowered = _lowered_cell_step(
        topo, "xdeepfm_ftrl_criteo_tb", XDEEPFM_PLANES
    )
    assert step._mxu_hot == {"w": True, "emb": True}
    assert (cfg.cross_layers, cfg.cin_maps) == (3, 200)
    assert (cfg.deep_layers, cfg.hidden_dim, cfg.emb_dim) == (2, 400, 10)
    b, m, d, maps = cfg.batch_size, cfg.max_fields, cfg.emb_dim, cfg.cin_maps
    s = blocks.cin_slice_rows(b, d, m, maps)
    n = s * d  # a slice's (column, example) pairs, along the lanes
    assert (s, n) == (128, 1280)
    text = lowered.as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots

    def dot(lhs: str, rhs: str, out: str) -> int:
        sig = f"(tensor<{lhs}xf32>, tensor<{rhs}xf32>) -> tensor<{out}xf32>"
        return sum(sig in line for line in dots)

    for h in (m, maps):  # layer 1; layers 2 and 3 share their types
        k, layers = h * m, 1 if h == m else 2
        # forward: w [maps, k] x pairs [k, N]
        assert dot(f"{maps}x{k}", f"{k}x{n}", f"{maps}x{n}") == layers, (h, dots)
        # into the weights: dX [maps, N] x pairs [k, N] over N
        assert dot(f"{maps}x{n}", f"{k}x{n}", f"{maps}x{k}") == layers, (h, dots)
        # into the pairs: dX [maps, N] x w [maps, k] over the maps
        assert dot(f"{maps}x{n}", f"{maps}x{k}", f"{n}x{k}") == layers, (h, dots)

    def elements(shape: str) -> int:
        return math.prod(int(x) for x in re.split("[x,]", shape) if x)

    whole = b * maps * m * d
    table = cfg.table_size * d
    made = {
        shape for shape in re.findall(r"tensor<([0-9x]+)xf32>", text)
        if elements(shape) != table
    }
    assert max(map(elements, made)) < whole // 10, sorted(made, key=elements)[-3:]

    compiled = lowered.compile()
    hlo = compiled.as_text()
    arrays = {
        shape for shape in re.findall(r"= \(?f32\[([0-9,]+)\]", hlo)
        if elements(shape) not in (table, cfg.table_size)
    }
    assert max(map(elements, arrays)) < whole // 10, sorted(arrays, key=elements)[-3:]
    assert f"{maps},{m},{n}" in arrays  # a slice's pair tensor
    in_scope: dict[str, list[str]] = {"xf.cin": [], "xf.dense": []}
    for line in hlo.splitlines():
        found = _HLO_OP_NAME_RE.search(line)
        if found and scope_of(found.group(1)) in in_scope:
            in_scope[scope_of(found.group(1))].append(line)
    cin, dense = in_scope["xf.cin"], in_scope["xf.dense"]
    assert all("xf.forward_backward" in line for line in cin + dense)
    paths = {_HLO_OP_NAME_RE.search(line).group(1) for line in cin}
    assert [p for p in paths if "/jvp(xf.cin)/while/body/" in p]
    assert [p for p in paths if "transpose(jvp(xf.cin))/while/body/closed_call/checkpoint" in p]
    assert sum(" while(" in line for line in cin) == 2  # forward's, backward's
    # the CIN's contractions: under xf.cin, each with a slice's lanes or
    # a layer's weights as its result; the DNN's under xf.dense
    products = [
        line.split(" = ")[1].split("{")[0] for line in cin if " convolution(" in line
    ]
    assert len(products) >= 8, products
    assert not [line for line in dense if f"{n}]" in line.split("metadata")[0]]
    assert sum(" convolution(" in line for line in dense) >= 6
    assert not [
        line for line in _table_sized_copies(hlo, cfg.table_size)
        if f"f32[{cfg.table_size},{d}]" in line
    ]
    _assert_touched_rows_update(
        hlo, cfg.table_size, d, XDEEPFM_PLANES["cw_cu"][0][0]
    )
    peak = _program_peak(compiled)
    assert 6.5 * (1 << 30) < peak < 7.5 * (1 << 30), peak


def test_autoint_step_attends_in_float32_a_slice_at_a_time_on_v5e(
    autoint_cell_step
):
    """The AutoInt train step at the geometry of the benchmark's
    autoint_tb.train_packed (benchmarks/configs/autoint_ftrl_criteo_tb.json:
    2^25 rows of 16 columns, ONE table, B=16384, 8 + 32 slots, 40 fields,
    three interacting layers of 2 heads of 32, the dictionary wire's plane
    capacities of one real batch, seed 1; the dense arrays handed in as
    shapes) for a described v5e.  Lowered: every dot asks for float32
    (Precision.HIGHEST); at default precision the TPU rounds both operands
    to bfloat16.  Inside the stack the only dots are the projections, a
    layer's four side by side over a slice in the lane form, ``[256, d_l]
    x [d_l, m, s]`` (``[d_l, m s]`` with the fields kept apart), and what
    autodiff makes of them, into the weights and into the activations: the
    per-example scores and weighted sums are float32 multiplies and sums
    with the slice's ``s`` examples minor-most (``blocks._lane_contract``,
    on the TPU a Mosaic kernel: eight a layer, two forward, two done again
    and four backward), so no dot has a batch dimension of ``s`` and no
    array of the stack has 32, 40 or 64 as its last axis but the slice's
    output, relaid once to ``[s, m, H d']``.  A
    step's scores are ``B H m m`` = 5.2e7 floats a layer whole and its
    projections ``4 B m H d'`` = 1.7e8: no array of the lowered or the
    compiled program has ``B H m m`` elements or more beside the table's
    own, but the stack's OUTPUT ``[B, m H d']`` (the input of the output
    product, 160 MiB) and its cotangent.  Compiled: the block's
    instructions, forward, forward done again and backward, and the two
    loops over the slices, carry ``xf.attn`` in ``op_scopes``' reading
    (the innermost name), the products (convolutions, as the TPU's
    compiler writes a dot) among them; NO instruction that runs inside
    either loop has an empty scope (but the backward loop's own counter
    test), and outside them none that makes an array of the stack's (the
    slices' tower with its presence row, relaid to the lane form; the
    output and its relayouts) but the compiler's asynchronous moves
    between memories; the output product is ``xf.dense``'s; no table-sized
    copy of emb's state is made; emb takes its optimizer on the
    dictionary's rows with no gradient buffer (PR 57,
    ``_assert_touched_rows_update``); and the program fits with more than
    the room the file's ``reduced`` argues from (8.01 GiB of 15.75 with
    the buffer, 6.42 without)."""
    from xflow_tpu.models import blocks
    from xflow_tpu.parallel.step import (
        _HLO_COMPUTATION_RE, _HLO_FUSED_RE, _HLO_INSTRUCTION_RE,
        _HLO_NEVER_RUNS_RE, _HLO_OP_NAME_RE, scope_of,
    )

    cfg, step, lowered, compiled = autoint_cell_step
    assert step._mxu_hot == {"emb": True}
    assert (cfg.cross_layers, cfg.attn_heads, cfg.attn_dim, cfg.emb_dim) == (3, 2, 32, 16)
    b, m, heads, head = cfg.batch_size, cfg.max_fields, cfg.attn_heads, cfg.attn_dim
    width = heads * head
    s = blocks.attn_slice_rows(b, m, heads, head, cfg.cross_layers)
    assert b % s == 0 and s == 128
    text = lowered.as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots

    def dot(lhs: str, rhs: str, out: str) -> int:
        sig = f"(tensor<{lhs}xf32>, tensor<{rhs}xf32>) -> tensor<{out}xf32>"
        return sum(sig in line for line in dots)

    # the projections of layer 1 and of layers 2 and 3, forward and done
    # again in the backward ...
    d, lanes = cfg.emb_dim, f"{m}x{s}"
    assert dot(f"{d}x{4 * width}", f"{d}x{lanes}", f"{4 * width}x{lanes}") == 2
    assert dot(f"{width}x{4 * width}", f"{width}x{lanes}", f"{4 * width}x{lanes}") == 4
    # ... into the weights and into the activations
    assert dot(f"{4 * width}x{lanes}", f"{d}x{lanes}", f"{4 * width}x{d}") == 1
    assert dot(f"{4 * width}x{lanes}", f"{width}x{lanes}", f"{4 * width}x{width}") == 2
    assert dot(f"{4 * width}x{lanes}", f"{d}x{4 * width}", f"{lanes}x{d}") == 1
    assert dot(f"{4 * width}x{lanes}", f"{width}x{4 * width}", f"{lanes}x{width}") == 2
    # and no other dot of a slice: the scores and the weighted sums are
    # multiplies and sums, the examples on the lanes
    assert sum(f"x{lanes}xf32>" in line for line in dots) == 12
    assert not [line for line in dots if f"tensor<{s}x" in line]
    scored = f"tensor<{heads}x{m}x{lanes}xf32>"  # [H, j, i, s]
    assert sum(
        "stablehlo.exponential" in line and scored in line
        for line in text.splitlines()
    ) >= 3

    def elements(shape: str) -> int:
        return math.prod(int(x) for x in re.split("[x,]", shape) if x)

    scores, out = b * heads * m * m, b * m * width
    table = cfg.table_size * cfg.emb_dim
    made = {
        shape for shape in re.findall(r"tensor<([0-9x]+)xf32>", text)
        if elements(shape) not in (table, out)
    }
    assert max(map(elements, made)) < scores, sorted(made, key=elements)[-3:]

    hlo = compiled.as_text()
    arrays = {
        shape for shape in re.findall(r"= \(?f32\[([0-9,]+)\]", hlo)
        if elements(shape) not in (table, out)
    }
    assert max(map(elements, arrays)) < scores, sorted(arrays, key=elements)[-3:]
    in_scope: dict[str, list[str]] = {"xf.attn": [], "xf.dense": []}
    for line in hlo.splitlines():
        found = _HLO_OP_NAME_RE.search(line)
        if found and scope_of(found.group(1)) in in_scope:
            in_scope[scope_of(found.group(1))].append(line)
    attn, dense = in_scope["xf.attn"], in_scope["xf.dense"]
    assert all("xf.forward_backward" in line for line in attn + dense)
    paths = {_HLO_OP_NAME_RE.search(line).group(1) for line in attn}
    assert [p for p in paths if "/jvp(xf.attn)/while/body/" in p]
    assert [
        p for p in paths
        if "transpose(jvp(xf.attn))/while/body/closed_call/checkpoint/rematted_computation" in p
    ]
    loops = [line for line in attn if " while(" in line]
    assert len(loops) == 2  # forward's, backward's
    assert sum(" convolution(" in line for line in attn) >= 12
    kernels = [line for line in attn if '"tpu_custom_call"' in line]
    assert len(kernels) == 8 * cfg.cross_layers, len(kernels)
    assert all(
        re.search(rf"= f32\[{heads},(?:{m}|{head}),{m},{s}\]", line) for line in kernels
    )
    assert [line for line in attn if "exponential" in line]
    assert dense and not [line for line in dense if " while(" in line]
    assert [line for line in dense if "jvp(xf.dense)/dot_general" in line]

    # what runs with no scope (as op_scopes reads the program: a fusion's
    # inside never runs on its own): nothing inside the loops, and of the
    # stack's own arrays nothing but the compiler's moves between memories
    fused = set(_HLO_FUSED_RE.findall(hlo))
    bodies = {re.search(r"body=%?([^ ,)]+)", line).group(1) for line in loops}
    slices = b // s
    stack_arrays = re.compile(
        rf"f32\[(?:{slices},{d + 1},{m},{s}|{slices},{s},{m},{width}"
        rf"|{b},{m},(?:{d + 1}|{width})|{b},{m * width})\]"
    )
    moves = re.compile(r" (?:copy|slice)-(?:start|done)\(|\"ConcatBitcast\"")
    unscoped_inside, unscoped_arrays, inside, current = [], [], 0, ""
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION_RE.match(line)
        if head:
            current = head.group(1)
            continue
        if (
            current in fused or not _HLO_INSTRUCTION_RE.match(line)
            or _HLO_NEVER_RUNS_RE.search(line)
        ):
            continue
        found = _HLO_OP_NAME_RE.search(line)
        scope = scope_of(found.group(1)) if found else ""
        inside += current in bodies
        if scope or " bitcast(" in line:
            continue
        if moves.search(line):
            continue
        if current in bodies:
            unscoped_inside.append(line.split(", metadata")[0].strip()[:120])
        elif stack_arrays.search(line.split(" = ")[1].split("(")[0]):
            unscoped_arrays.append(line.split(", backend_config")[0].strip()[:160])
    assert inside > 150, inside
    assert all(
        re.match(r"%?compare[.0-9]* = pred\[\]", line) for line in unscoped_inside
    ), unscoped_inside
    assert not unscoped_arrays, unscoped_arrays
    assert not [
        line for line in _table_sized_copies(hlo, cfg.table_size)
        if f"f32[{cfg.table_size},{cfg.emb_dim}]" in line
    ]
    _assert_touched_rows_update(
        hlo, cfg.table_size, cfg.emb_dim, AUTOINT_PLANES["cw_cu"][0][0]
    )
    peak = _program_peak(compiled)
    assert 6.0 * (1 << 30) < peak < 6.75 * (1 << 30), peak


def test_fibinet_step_multiplies_pairs_in_float32_without_a_pair_matrix_array_on_v5e(
    topo, no_compile_cache
):
    """The FiBiNET train step at the geometry of the benchmark's
    fibinet_tb.train_packed (benchmarks/configs/fibinet_ftrl_criteo_tb.json:
    2^25 rows, w of one column and emb of 10, B=16384, 8 + 32 slots, 40
    fields, reduction 3, 780 pairs on two towers, three hidden layers of 400,
    the dictionary wire's plane capacities of one real batch, seed 1; the
    dense arrays handed in as shapes) for a described v5e.  Lowered: every
    dot asks for float32 (Precision.HIGHEST), a field's product with the
    matrices of its pairs among them (``[B, 10] x [10, n 10]``, the first
    field's n = 39 on both towers, forward and again for the backward); at
    default precision the TPU rounds both operands to bfloat16.  A tower's
    pairs times their matrices would be ``B P D D`` = 1.28e9 floats whole
    (5.1 GB) and the picked pairs ``B P D`` = 1.28e8: no array of the lowered
    or the compiled program has ``B P D D`` elements, and beside the tables'
    own none is larger than the pair tensor c ``[B, 2 P D]`` (the first
    hidden layer's operand, 975 MiB, and its cotangent).  Compiled: no field
    is cut out of the ``[B, m, D]`` tower as ``[B, 1, D]`` (one 128-lane row
    an example: 134 MB a field, 10 GiB over both towers and both passes; the
    fields come out of the flat ``[B, m D]`` tower), the block's
    instructions carry ``xf.bilinear`` in ``op_scopes``' reading (the
    innermost name) with its products (convolutions, as the TPU's compiler
    writes a dot) among them, the hidden stack's stay ``xf.dense``'s, no
    table-sized copy of emb's state is made, emb takes its optimizer on the
    dictionary's rows with no gradient buffer (PR 57,
    ``_assert_touched_rows_update``), and the program fits with the room
    the file's ``reduced`` argues from (its peak is the dense half's, the
    pair tensor and its cotangent, not the buffer's)."""
    from xflow_tpu.models import blocks
    from xflow_tpu.parallel.step import _HLO_OP_NAME_RE, scope_of

    cfg, step, lowered = _lowered_cell_step(
        topo, "fibinet_ftrl_criteo_tb", FIBINET_PLANES
    )
    assert step._mxu_hot == {"w": True, "emb": True}
    assert (cfg.senet_reduction, cfg.deep_layers, cfg.hidden_dim, cfg.emb_dim) == (3, 3, 400, 10)
    b, m, d, h = cfg.batch_size, cfg.max_fields, cfg.emb_dim, cfg.hidden_dim
    pairs = blocks.field_pairs(m)
    assert pairs == 780 and blocks.bilinear_slice_rows(b, d, m) == b  # whole
    text = lowered.as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots

    def dot(lhs: str, rhs: str, out: str) -> int:
        sig = f"(tensor<{lhs}xf32>, tensor<{rhs}xf32>) -> tensor<{out}xf32>"
        return sum(sig in line for line in dots)

    n = (m - 1) * d  # the first field's 39 pairs, side by side
    assert dot(f"{b}x{d}", f"{d}x{n}", f"{b}x{n}") >= 2  # both towers
    assert dot(f"{b}x{n}", f"{b}x{d}", f"{n}x{d}") + dot(f"{b}x{d}", f"{b}x{n}", f"{d}x{n}") >= 2
    assert dot(f"{b}x{2 * pairs * d}", f"{2 * pairs * d}x{h}", f"{b}x{h}") == 1
    # the excitation: 40 gates squeezed to 13 and back
    assert dot(f"{b}x{m}", f"{m}x{m // 3}", f"{b}x{m // 3}") >= 1
    assert dot(f"{b}x{m // 3}", f"{m // 3}x{m}", f"{b}x{m}") >= 1

    def elements(shape: str) -> int:
        return math.prod(int(x) for x in re.split("[x,]", shape) if x)

    c, table = b * 2 * pairs * d, cfg.table_size * d
    made = {
        shape for shape in re.findall(r"tensor<([0-9x]+)xf32>", text)
        if elements(shape) != table
    }
    assert max(map(elements, made)) == c, sorted(made, key=elements)[-3:]
    assert not [shape for shape in made if f"{pairs}x{d}x{d}" in shape and shape.startswith(str(b))]

    compiled = lowered.compile()
    hlo = compiled.as_text()
    arrays = {
        shape for shape in re.findall(r"= \(?f32\[([0-9,]+)\]", hlo)
        if elements(shape) not in (table, cfg.table_size)
    }
    assert max(map(elements, arrays)) == c, sorted(arrays, key=elements)[-3:]
    assert f"{b},{pairs},{d},{d}" not in arrays and f"{b},{pairs},{d}" not in arrays
    assert f"{b},1,{d}" not in arrays  # a field cut out of the 3-D tower
    in_scope: dict[str, list[str]] = {"xf.bilinear": [], "xf.dense": []}
    for line in hlo.splitlines():
        found = _HLO_OP_NAME_RE.search(line)
        if found and scope_of(found.group(1)) in in_scope:
            in_scope[scope_of(found.group(1))].append(line)
    block, dense = in_scope["xf.bilinear"], in_scope["xf.dense"]
    assert all("xf.forward_backward" in line for line in block + dense)
    paths = {_HLO_OP_NAME_RE.search(line).group(1) for line in block}
    assert [p for p in paths if "transpose(jvp(xf.bilinear))" in p]
    assert [p for p in paths if "transpose" not in p]
    assert sum(" convolution(" in line for line in block) >= 4 * (m - 1)
    assert sum(" convolution(" in line for line in dense) >= 9
    assert not [line for line in block + dense if " while(" in line]
    assert not [
        line for line in _table_sized_copies(hlo, cfg.table_size)
        if f"f32[{cfg.table_size},{d}]" in line
    ]
    _assert_touched_rows_update(
        hlo, cfg.table_size, d, FIBINET_PLANES["cw_cu"][0][0]
    )
    peak = _program_peak(compiled)
    assert 8.0 * (1 << 30) < peak < 10.0 * (1 << 30), peak


def test_serving_program_takes_one_packed_buffer_on_v5e(topo, no_compile_cache):
    """The serving engine's bucket-512 predict program at the geometry of
    the benchmark's lr_tb.serve_rows (benchmarks/configs/
    lr_ftrl_criteo_tb.json: 2^28 rows of one column, 12 + 28 slots, hot
    2^12, the plain compact wire) for a described v5e.  A request batch
    crosses as ONE byte buffer (serve/engine.py::_put_packed; PERF.md
    section 6, PR 40): beside the state the program has a single batch
    parameter, u8[512, 106] = ckeys 12 x int32 + hot_ckeys_u16 28 x
    uint16 + labels_u8 + weights_u8, and unpacks the planes itself.
    Compiled: the unpack copies nothing of the table's size, and the
    program holds the table and next to nothing else."""
    from benchmarks.harness import manifest
    from xflow_tpu.config import Config
    from xflow_tpu.io.batch import Batch
    from xflow_tpu.parallel import mesh as meshes
    from xflow_tpu.parallel.step import pack_wire_np
    from xflow_tpu.serve.engine import PredictEngine

    doc = manifest.config_file("benchmarks/configs/lr_ftrl_criteo_tb.json")
    cfg = Config(**{
        k: v for k, v in manifest.apply_rehearsal(doc, False).items()
        if k not in manifest.CONFIG_META
    })
    mesh = meshes.make_mesh(1, devices=list(topo.devices))

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    t = cfg.table_size
    state = {
        "tables": {"w": {"param": shaped(
            (t, 1), jnp.float32, meshes.table_sharding(mesh)
        )}},
        "dense": {},
        "step": shaped((), jnp.int32, meshes.replicated(mesh)),
    }
    # the remap steers requests on the host; the program never sees it
    engine = PredictEngine(cfg, state, remap=np.zeros(0, np.int32), mesh=mesh)
    step = engine.step
    assert step.wire_format == "compact" and step._hot_u16
    rows, kc, kh = 512, cfg.max_nnz, cfg.hot_nnz

    def plane(k, dtype):
        return np.zeros((rows, k), dtype)

    wire, _ = step.host_wire_np(Batch(
        keys=plane(kc, np.int32), slots=plane(kc, np.int32),
        vals=plane(kc, np.float32), mask=plane(kc, np.float32),
        labels=np.zeros(rows, np.float32), weights=np.zeros(rows, np.float32),
        hot_keys=plane(kh, np.int32), hot_slots=plane(kh, np.int32),
        hot_vals=plane(kh, np.float32), hot_mask=plane(kh, np.float32),
    ))
    assert sorted(wire) == [
        "ckeys", "hot_ckeys_u16", "labels_u8", "weights_u8"
    ]
    buf, layout = pack_wire_np(wire)
    assert buf.shape == (rows, 4 * kc + 2 * kh + 2) == (512, 106)
    lowered = engine.predict_jit.lower(
        state, shaped(buf.shape, buf.dtype, step._bsharding), layout=layout
    )
    (main,) = [
        line for line in lowered.as_text().splitlines()
        if "func.func public @main(" in line
    ]
    args = re.findall(r"%arg\d+: (tensor<[^>]*>)", main)
    # (the state's step scalar is not read, so it is no parameter)
    assert args == [f"tensor<{t}x1xf32>", "tensor<512x106xui8>"], main
    compiled = lowered.compile()
    assert not _table_sized_copies(compiled.as_text(), t)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes < 1.001 * 4 * t + (1 << 20)
    assert ma.temp_size_in_bytes < 64 << 20, ma.temp_size_in_bytes

"""Compiles for a DESCRIBED v5e, with no chip: what the TPU's compiler
refuses (a Mosaic kernel's tiling, fast memory, a program that does not
fit) shows here and costs no chip time.  Nothing runs, so nothing here
says a word about results or times.

One file on purpose, and the topology only inside a fixture: one process
at a time may load the TPU's library, so under several test workers only
the worker that is given this file describes the chip."""

import contextlib
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


def test_four_chip_fm_step_compiles_for_v5e_with_its_exchange(
    topo, no_compile_cache
):
    """The FM train step over the described 2x2 as the TPU's compiler
    leaves it (parallel/exchange.py; the benchmark cell's widths, a table
    and a batch cut to keep the compile short): every collective is the
    program's or a scalar's, none is issued by a loop's iterations, none
    has a block's rows.  The TPU runs a reduce-scatter as an all-reduce
    and a slice, and may continue an all-gather inside a neighbouring
    loop (``pieces`` > 1): collectives_in counts such a chain once."""
    from xflow_tpu.config import Config
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.exchange import collectives_in
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
    assert step._hot_impl == "mxu" and step.wire_format == "compact"

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
    text = step.train.lower(state, arrays).compile().as_text()
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


def _lowered_cell_step(
    topo, config: str, planes: dict, ships_slots: bool = True
):
    """The train step of a one-chip benchmark configuration
    (benchmarks/configs/<config>.json) lowered for a described v5e, its
    dictionary-wire batch given as plane shapes (``planes``: name ->
    (shape, dtype), the capacities of one real batch; ``ships_slots``:
    whether the family reads field ids, so that its wire ships the slots
    planes)."""
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
    mesh = meshes.make_mesh(1, devices=list(topo.devices))
    model = make_model(cfg)
    step = TrainStep(model, make_optimizer(cfg), cfg, mesh)
    assert step.wire_format == "dict" and step._ship_slots == ships_slots
    assert step._hot_impl == "mxu"

    def shaped(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    rows = meshes.table_sharding(mesh)
    state = {
        "tables": {
            spec.name: {
                name: shaped((cfg.table_size, spec.dim), jnp.float32, rows)
                for name in ("param", "n", "z")
            }
            for spec in model.tables()
        },
        "dense": {},
        "step": shaped((), jnp.int32, meshes.replicated(mesh)),
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
    u8, u16, u32 = np.uint8, np.uint16, np.uint32
    with _without_compile_cache():
        cfg, _, lowered = _lowered_cell_step(topo, "mvm_ftrl_criteo_tb", {
            "cw_cu": ((43008,), u32), "cw_cun": ((1,), np.int32),
            "cw_ci": ((688128,), u16), "cw_ct": ((262144,), u32),
            "cw_cf": ((118784,), u8), "cw_cc": ((131072,), u8),
            "cw_lb": ((16384,), u8), "cw_wb": ((16384,), u8),
            "cw_h8": ((2490368,), u8), "cw_hx": ((1835008,), u16),
            "cw_hxh": ((0,), u8), "cw_hf": ((524288,), u8),
            "cw_hc": ((131072,), u8),
            "cw_cs": ((950272,), u8), "cw_hs": ((4194304,), u8),
        })
        return cfg, lowered, lowered.compile()


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
    u8, u16, u32 = np.uint8, np.uint16, np.uint32
    cfg, _, lowered = _lowered_cell_step(topo, "lr_ftrl_criteo_tb", {
        "cw_cu": ((53248,), u32), "cw_cun": ((1,), np.int32),
        "cw_ci": ((1228800,), u16), "cw_ct": ((294912,), u32),
        "cw_cf": ((184320,), u8), "cw_cc": ((131072,), u8),
        "cw_lb": ((16384,), u8), "cw_wb": ((16384,), u8),
        "cw_h8": ((2293760,), u8), "cw_hx": ((1490944,), u8),
        "cw_hxh": ((745472,), u8), "cw_hf": ((458752,), u8),
        "cw_hc": ((131072,), u8),
    }, ships_slots=False)
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


def test_ffm_step_contracts_fields_in_float32_and_fits_a_v5e(
    topo, no_compile_cache
):
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
    [B, hot_nnz] plane.  Compiled: the program fits with the room the
    file's ``reduced`` argues from, 13.94 GiB of 15.75 (at 2^22 rows the
    compiler refuses it).  Most of that is layout (PERF.md section 6,
    PR 34-35): v's state comes in rows-minor and is copied to
    columns-minor and back inside the step, six table-sized copies that
    set the peak.  The dictionary route lays v's 160-column row out by
    row gathers (dict_cold_rows): of the padded [B, max_nnz, 1] column
    planes, 160 families of them until PR 35 (0.16 GiB of the peak and
    82 ms of the step), one is left, w's single column."""
    u8, u16 = np.uint8, np.uint16
    cfg, step, lowered = _lowered_cell_step(topo, "ffm_ftrl_criteo_tb", {
        "cw_cu": ((53248, 3), u8), "cw_cun": ((1,), np.int32),
        "cw_ci": ((118784,), u16), "cw_ct": ((0, 3), u8),
        "cw_cf": ((14848,), u8), "cw_cc": ((16384,), u8),
        "cw_lb": ((2048,), u8), "cw_wb": ((2048,), u8),
        "cw_h8": ((311296,), u8), "cw_hx": ((229376,), u16),
        "cw_hxh": ((0,), u8), "cw_hf": ((65536,), u8),
        "cw_hc": ((16384,), u8),
        "cw_cs": ((118784,), u8), "cw_hs": ((524288,), u8),
    })
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
    compiled = lowered.compile()
    # instructions whose result is a padded column plane of the cold
    # slots: w's one column and no more (9; 667 with v's 160 columns)
    planes = set(re.findall(
        rf"(\S+) = f32\[{b},{cfg.max_nnz},1\]", compiled.as_text()
    ))
    assert len(planes) <= 16, sorted(planes)
    peak = _program_peak(compiled)
    assert 13.5 * (1 << 30) < peak < 14.0 * (1 << 30), peak

"""DLRM's train step compiled for a described v5e, with no chip: in a file
of its own so that xdist gives it a worker beside tests/test_tpu_compile.py's
(``--dist loadfile``), whose helpers it borrows."""

import math
import re

import numpy as np
from test_tpu_compile import (  # noqa: F401  (topo is a fixture)
    _U8, _U16, _lowered_cell_step, _program_peak, _table_sized_copies, topo,
)

# one real batch of the benchmark's rows at the cell's geometry, seed 1
# (scripts/aot_dense_step.py --config dlrm_ftrl_criteo_tb, PR 58)
DLRM_PLANES = {
    "cw_cu": ((20480, 3), _U8), "cw_cun": ((1,), np.int32),
    "cw_ci": ((155648,), _U16), "cw_ct": ((81920, 3), _U8),
    "cw_cf": ((29696,), _U8), "cw_cc": ((32768,), _U8),
    "cw_lb": ((4096,), _U8), "cw_wb": ((4096,), _U8),
    "cw_h8": ((622592,), _U8), "cw_hx": ((458752,), _U16),
    "cw_hxh": ((0,), _U8), "cw_hf": ((131072,), _U8),
    "cw_hc": ((32768,), _U8), "cw_nv": ((32768, 13), np.float32),
    "cw_cs": ((237568,), _U8), "cw_hs": ((1048576,), _U8),
}


def test_dlrm_step_multiplies_in_float32_on_todays_routes_and_fits_a_v5e(topo):
    """The DLRM train step at the geometry of the benchmark's
    dlrm_tb.train_packed (benchmarks/configs/dlrm_ftrl_criteo_tb.json: 2^22
    rows of 128, B = 32768, 8 + 32 slots, 13 numeric fields whose values
    arrive as the plane ``cw_nv``, stacks 512-256-128 and 1024-1024-512-256;
    the dictionary wire's plane capacities of one real batch; the dense
    arrays handed in as shapes) for a described v5e.  Lowered: every dot asks
    for float32 (Precision.HIGHEST), the first product of each stack and the
    per-example ``T T^T`` of 27 vectors among them; at default precision the
    TPU rounds both operands to bfloat16.  The table is 128 columns wide,
    which no threshold was moved for: its cold rows come by row gathers
    (``ROW_LAYOUT_MIN_COLUMNS``), its cold gradients go back an index a padded
    slot (128 is outside ``DICT_SCATTER_COLUMNS``), the head reads and sums it
    plainly, and the dense update keeps its ``[T, 128]`` gradient buffer and
    pass (``touched_rows_selects`` stops at 64), which no layout constraint
    touches (``resident_pass_selects``, PR 59: 128 columns fill a lane tile,
    the chip keeps the state columns-minor and the pass reads it as it
    lies).  Compiled: the interaction's
    instructions carry ``xf.interact`` in ``op_scopes``' reading, the stacks'
    ``xf.dense``; no table-sized copy of the state is made; and the program
    fits with the room the file's ``reduced`` argues from (8.23 GiB of 15.75;
    at 2^23 rows the compiler refuses it: 16.15 G)."""
    from xflow_tpu.ops import hot
    from xflow_tpu.parallel.step import (
        _HLO_OP_NAME_RE, DICT_SCATTER_COLUMNS, ROW_LAYOUT_MIN_COLUMNS,
        resident_pass_selects, scope_of, touched_rows_selects,
    )

    cfg, step, lowered = _lowered_cell_step(topo, "dlrm_ftrl_criteo_tb", DLRM_PLANES)
    assert step._mxu_hot == {"emb": True}
    assert (cfg.numeric_fields, cfg.emb_dim, cfg.max_fields) == (13, 128, 40)
    b, d, n = cfg.batch_size, cfg.emb_dim, step.model.vectors
    assert (n, step.model.top_in) == (27, 479)
    assert d >= ROW_LAYOUT_MIN_COLUMNS and step._row_layout_tables == 1
    assert d not in DICT_SCATTER_COLUMNS and step._dict_scatter_tables == 0
    assert hot.gather_form(d, "auto") == hot.scatter_form(d, "auto") == "seg"
    assert not step._touched_rows_names(
        DLRM_PLANES["cw_cu"][0][0], DLRM_PLANES["cw_ct"][0][0], True
    )
    assert not touched_rows_selects(cfg.table_size, d, 20480, 0)
    assert not resident_pass_selects(d) and not step._resident_pass_tables
    text = lowered.as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), dots

    def dot(lhs: str, rhs: str, out: str) -> int:
        sig = f"(tensor<{lhs}xf32>, tensor<{rhs}xf32>) -> tensor<{out}xf32>"
        return sum(sig in line for line in dots)

    assert dot(f"{b}x13", "13x512", f"{b}x512") == 1
    assert dot(f"{b}x479", "479x1024", f"{b}x1024") == 1
    assert dot(f"{b}x{n}x{d}", f"{b}x{n}x{d}", f"{b}x{n}x{n}") == 1

    compiled = lowered.compile()
    hlo = compiled.as_text()
    in_scope: dict[str, list[str]] = {"xf.interact": [], "xf.dense": []}
    for line in hlo.splitlines():
        found = _HLO_OP_NAME_RE.search(line)
        if found and scope_of(found.group(1)) in in_scope:
            in_scope[scope_of(found.group(1))].append(line)
    block, dense = in_scope["xf.interact"], in_scope["xf.dense"]
    assert block and all("xf.forward_backward" in line for line in block + dense)
    paths = {_HLO_OP_NAME_RE.search(line).group(1) for line in block}
    assert [p for p in paths if "transpose(jvp(xf.interact))" in p]
    assert [p for p in paths if "transpose" not in p]
    # seven stack layers forward, into the activations and into the weights,
    # less the first layer's input, which takes no gradient (the output
    # product, one column wide, is a multiply and a sum)
    assert sum(" convolution(" in line for line in dense) >= 3 * 7 - 1
    assert not _table_sized_copies(hlo, cfg.table_size)

    def elements(shape: str) -> int:
        return math.prod(int(x) for x in shape.split(",") if x)

    table = cfg.table_size * d
    arrays = {
        shape for shape in re.findall(r"= \(?f32\[([0-9,]+)\]", hlo)
        if elements(shape) != table
    }
    # beside the table's own nothing is larger than the gathered rows
    assert max(map(elements, arrays)) <= b * 40 * d, sorted(arrays, key=elements)[-3:]
    peak = _program_peak(compiled)
    assert 0.25 * 16e9 < peak < 8.5 * 2**30, peak / 2**30

"""Chip probe of the cold parameter gather (PERF.md section 6, PR 30): a
row per padded slot out of the [T, D] table against a row per dictionary
and tail entry plus a resolve out of the batch-sized rows, at the shapes
of one real batch of a one-chip train cell.

    python scripts/probe_cold_gather.py [--seed N] [--calls 20]
        [--config lr_ftrl_criteo_tb | ffm_ftrl_criteo_tb]

The batch is the benchmark cell's own: its generator, remap and steering
at ``--seed``, compacted by ``CompactBatch.from_batch``.  Each form is
timed as ``--calls`` chained calls closed by one fetch: ms a call, ns an
index.  At the LR cell's geometry (the default) the table is [2^28, 1]
for D = 1 (the cell's) and [2^25, 10] for D = 10 (FM's and MVM's width
at a size one chip holds beside it; keys >> 3); at the FFM cell's, its
own w [2^21, 1] and v [2^21, 160].

The route lays the padded rows out of its two flat row streams column by
column or row by row, by the row's width (step.py::dict_cold_rows,
ROW_LAYOUT_MIN_COLUMNS).  The probe times the WHOLE route in both forms,
each checked against ``param[keys]``, at the cell's widths and over a
sweep of widths between them on a [2^20, D] table: where the two forms
cross is where the constant belongs (PERF.md section 6, PR 35).

A measurement path: exits 1 without a TPU, every row names the device it
ran on, and a form whose result differs from ``param[keys]`` on an
unmasked slot ends the probe with a non-zero exit code.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

CELL_CONFIG = "lr_ftrl_criteo_tb"
CELL_TRAFFIC = "benchmarks/traffic/replay_packed_zipf.json"


def cell_batch(seed: int, config: str = CELL_CONFIG):
    """(fields, CompactBatch): the first batch of the first shard of the
    train cell of ``benchmarks/configs/<config>.json`` as ``io/packed.py``
    would pack it, made with the benchmark's own generator, remap and
    the program's steering."""
    from benchmarks.generators.rows import RowGenerator, RowSpec
    from benchmarks.harness import corpus
    from xflow_tpu.io.batch import make_batch
    from xflow_tpu.io.compact import CompactBatch

    with open(
        os.path.join(ROOT, "benchmarks", "configs", f"{config}.json")
    ) as f:
        fields = json.load(f)
    with open(os.path.join(ROOT, CELL_TRAFFIC)) as f:
        mix = json.load(f)
    t, h = 1 << fields["table_size_log2"], 1 << fields["hot_size_log2"]
    b, kc, kh = fields["batch_size"], fields["max_nnz"], fields["hot_nnz"]
    gen = RowGenerator(RowSpec.from_params(mix["rows"]), seed)
    remap, _ = corpus.hot_remap(gen, t, h, seed)
    gid, labels = corpus.shard_rows(gen, 0, b)
    rows = remap[gen.keys(gid, t, seed)]
    nf = rows.shape[1]
    pad = kc + kh - nf
    keys = np.pad(rows, ((0, 0), (0, pad))).astype(np.int32)
    mask = np.pad(np.ones((b, nf), np.float32), ((0, 0), (0, pad)))
    slots = np.broadcast_to(
        np.arange(kc + kh, dtype=np.int32), keys.shape
    ).copy()
    batch = make_batch(
        keys, slots, mask.copy(), mask, labels.astype(np.float32),
        np.ones(b, np.float32), hot_size=h, hot_nnz=kh,
    )
    return fields, CompactBatch.from_batch(batch, t, h)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000000007)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--config", default=CELL_CONFIG,
                    help="the one-chip train cell whose batch and "
                    "widths the probe takes (benchmarks/configs/)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops import window
    from xflow_tpu.parallel import step
    from xflow_tpu.parallel.step import expand_dict_wire

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU (found {device.platform}): nothing measured",
              file=sys.stderr)
        return 1
    stamp = {"platform": device.platform, "device_kind": device.device_kind}
    t0 = time.perf_counter()
    fields, cb = cell_batch(args.seed, args.config)
    stamp["config"] = args.config
    print(f"batch made in {time.perf_counter() - t0:.1f} s: n_cold "
          f"{cb.n_cold}, dict {cb.n_dict} entries / {cb.n_dict_occ} "
          f"occurrences, caps cu {len(cb.cu)} ci {len(cb.ci)} ct "
          f"{len(cb.ct)}", flush=True)
    rows_out: list[dict] = []

    def run(name: str, fn, *xs, indices: int, layout: str | None = None):
        shipped = step.ROW_LAYOUT_MIN_COLUMNS
        if layout:  # read while dict_cold_rows is traced, below
            step.ROW_LAYOUT_MIN_COLUMNS = {"rows": 1, "columns": 1 << 30}[
                layout
            ]
        try:
            # a function of its own: jit's cache is keyed by the function,
            # and a second layout of one function would find the first's
            f = jax.jit(lambda *a: fn(*a))
            out = jax.block_until_ready(f(*xs))  # compile + warm
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            # a finding, not a fault: the form does not fit the chip
            rows_out.append({
                "form": name, "refused": str(e).splitlines()[0], **stamp,
            })
            print(rows_out[-1], flush=True)
            return None
        finally:
            step.ROW_LAYOUT_MIN_COLUMNS = shipped
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = f(*xs)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        rows_out.append({
            "form": name, "indices": indices, "ms": ms,
            "ns_per_index": ms * 1e6 / indices, **stamp,
        })
        print(rows_out[-1], flush=True)
        return out

    def check(what: str, same: bool) -> None:
        rows_out.append({"check": what, "equal": bool(same), **stamp})
        print(rows_out[-1], flush=True)

    cfg = types.SimpleNamespace(
        max_nnz=fields["max_nnz"], hot_nnz=fields["hot_nnz"]
    )
    wire = jax.tree.map(jnp.asarray, cb.wire(False))
    decode = jax.jit(functools.partial(
        expand_dict_wire, cfg, window.lane_select_tpu
    ))
    planes = decode(wire)
    plan = planes["cold_plan"]
    keys = planes["keys"]
    mask = np.asarray(planes["mask"]) > 0
    b, kc = keys.shape
    slots = b * kc
    cu, ct, ci = plan["cu"], plan["ct"], plan["ci"]
    n_tab = cu.shape[0] + ct.shape[0]
    check("decoded keys == CompactBatch.expand()",
          np.array_equal(np.asarray(keys), cb.expand().keys))

    def bits(x):
        return np.asarray(x).view(np.uint32)

    key = jax.random.key(args.seed & 0x7FFFFFFF)
    t_rows = 1 << fields["table_size_log2"]
    if args.config == CELL_CONFIG:
        tables = {
            1: (t_rows, 0),
            # FM's width beside it on one chip: 2^25 rows x 10, keys >> 3
            10: (t_rows >> 3, 3),
        }
    else:  # the cell's own tables: FFM's w, and v's row of a vector a field
        tables = {
            d: (t_rows, 0)
            for d in (1, fields["max_fields"] * fields["ffm_v_dim"])
        }
    # between them a sweep of widths over a [2^20, D] table, the route in
    # both layouts alone: the rest of the route is the same in both, so
    # where they cross is where the layouts do
    sweep = [d for d in (2, 4, 8, 10, 16, 32, 64, 128) if d not in tables]
    tables.update(
        {d: (1 << 20, fields["table_size_log2"] - 20) for d in sweep}
    )

    def route(p, pl):
        return step.dict_cold_rows(
            pl, {"t": p}, window.lane_select_tpu
        )["t"].reshape(b, kc, -1)

    for d, (n_rows, shift) in sorted(tables.items()):
        tag = f"D={d}"
        param = jax.random.normal(key, (n_rows, d), jnp.float32)
        k2, u, t = keys >> shift, cu >> shift, ct >> shift
        want = run(
            f"{tag} param[keys], a row per padded slot (the parent's)",
            lambda p, k: p[k], param, k2, indices=slots)
        if d not in sweep:
            run(f"{tag} param[cu] + param[ct]",
                lambda p, a, c: (p[a], p[c]), param, u, t, indices=n_tab)
            rows_u = param[u]
            run(f"{tag} rows_u[ci], [{cu.shape[0]}, {d}] row gather",
                lambda r, i: r[i], rows_u, ci, indices=ci.shape[0])
            keyed = jnp.concatenate([
                u[:, None], jax.lax.bitcast_convert_type(rows_u, jnp.int32)
            ], axis=1)
            run(f"{tag} keyed[ci], [{cu.shape[0]}, 1+{d}] int32 rows, the "
                "key beside the row's bits", lambda r, i: r[i], keyed, ci,
                indices=ci.shape[0])
        shifted = {**plan, "cu": u, "ct": t}
        by_rows = d >= step.ROW_LAYOUT_MIN_COLUMNS
        for layout in ("columns", "rows"):
            ships = "shipped" if by_rows == (layout == "rows") else "not shipped"
            got = run(
                f"{tag} dict_cold_rows over [{n_rows}, {d}]: the whole "
                f"route, laid out by {layout} ({ships})",
                route, param, shifted, indices=slots, layout=layout)
            if got is None:
                continue
            check(f"{tag} route by {layout} == param[keys] on every "
                  "unmasked slot",
                  np.array_equal(bits(got)[mask], bits(want)[mask]))
            check(f"{tag} route by {layout} gives 0 on every padding slot",
                  not bits(got)[~mask].any())

    if args.config != CELL_CONFIG:
        return finish(rows_out)
    # the single forms behind PR 30, at the LR cell's D = 1
    param = jax.random.normal(key, (t_rows, 1), jnp.float32)
    flat = param.reshape(-1)
    rows_u = flat[cu]
    run("D=1 rows_u[ci], element gather of a 1-D source",
        lambda r, i: r[i], rows_u, ci, indices=ci.shape[0])
    run("D=1 cu[ci] and rows_u[ci], two element gathers in one program",
        lambda a, r, i: (a[i], r[i]), cu, rows_u, ci,
        indices=2 * ci.shape[0])
    got = run("D=1 wide_take(rows_u, ci): the row beside a second column "
              "(shipped)", window.wide_take, rows_u, ci,
              indices=ci.shape[0])
    check("wide_take(rows_u, ci) == rows_u[ci]",
          np.array_equal(bits(got), bits(rows_u[ci])))
    got = run("wide_take(cu, ci): the decode's key resolve (shipped)",
              window.wide_take, cu, ci, indices=ci.shape[0])
    check("wide_take(cu, ci) == cu[ci]", np.array_equal(got, cu[ci]))
    run("whole decode, expand_dict_wire (shipped)",
        functools.partial(expand_dict_wire, cfg, window.lane_select_tpu),
        wire, indices=slots)
    run("D=1 param.reshape(-1)[keys], 1-D view of the table",
        lambda p, k: p[k], flat, keys, indices=slots)
    run("D=1 param[keys] mode=promise_in_bounds",
        lambda p, k: p.at[k].get(mode="promise_in_bounds"), param, keys,
        indices=slots)
    tab_idx = jnp.concatenate([cu, ct])
    # a [T/2, 2] or [T/8, 8] view would be rows of the width that is cheap
    # out of a small source, but its (8, 128) tiles pad the 1 GiB table to
    # 64 GiB (refused on the chip, PR 30): 128 is the one view that is free
    for what, k in (("dictionary and tail entry", tab_idx),
                    ("padded slot", keys.reshape(-1))):
        run(f"D=1 [T/128, 128] view: a 128-wide row per {what} + a lane pick",
            lambda p, k: jnp.take_along_axis(
                p.reshape(-1, 128)[k >> 7], (k & 127)[:, None], axis=1),
            param, k, indices=k.shape[0])
    # the same count of uniform indices out of a small and the big source
    n_src = 1 << 20
    for name, n in (("4 MiB source", n_src), ("1 GiB table", t_rows)):
        idx = jax.random.randint(
            jax.random.key(1), (slots,), 0, n, jnp.int32)
        run(f"D=1 {slots} uniform indices out of the {name}",
            lambda p, i: p[i], flat[:n], idx, indices=slots)
    # the occurrence resolve by slot: one index per padded slot into the
    # dictionary's and tail's rows laid end to end
    slot_idx = jnp.where(
        plan["is_dict"],
        jnp.take(ci, plan["di_idx"], mode="clip"),
        cu.shape[0] + plan["tail_idx"],
    )
    rows_all = flat[tab_idx]
    got = run("D=1 rows_all[slot_idx]: a row per padded slot out of the "
              f"[{n_tab}] dictionary and tail rows",
              lambda r, i: jnp.take(r, i, mode="clip"), rows_all, slot_idx,
              indices=slots)
    check("D=1 by-slot resolve == param[keys] on every unmasked slot",
          np.array_equal(bits(got)[mask.ravel()],
                         bits(flat[keys.reshape(-1)])[mask.ravel()]))

    return finish(rows_out)


def finish(rows_out: list[dict]) -> int:
    os.makedirs("chiprun_out", exist_ok=True)
    name = f"probe_cold_gather.{rows_out[0]['config']}.json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump(rows_out, f, indent=1)
    return 0 if all(r.get("equal", True) for r in rows_out) else 1


if __name__ == "__main__":
    sys.exit(main())

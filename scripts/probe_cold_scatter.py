"""Chip probe of the cold gradients' way back (PERF.md section 6, PR 48): a
scatter-add per padded slot into the [T, D] gradient buffer (the parent's
form, and every plain-wire batch's) against the route of a
dictionary-wire batch, which sums the occurrences of a dictionary key
first and hands the buffer one index per dictionary and tail entry
(step.py::dict_scatter_plan, dict_cold_grads), at the shapes of one real
batch of each one-chip train cell.

    chiprun -- python scripts/probe_cold_scatter.py [--seed N] [--calls 10]
        [--configs mvm_ftrl_criteo_tb,dcn_ftrl_criteo_tb,...]

The batch is the benchmark cell's own (probe_cold_gather.cell_batch: its
generator, remap and steering at ``--seed``).  At each cell's geometry
and at the width of its widest table, every form is timed as ``--calls``
chained calls on a donated buffer, closed by one fetch (ms a call, ns an
index handed to the table), the route's parts alone beside the whole
(the plan; the dictionary sum as a plain scatter-add into its
[cap(cu), D] buffer, in pieces under a scan, into a [D, cap(cu)] buffer,
and as ops/hot.py's one-hot scan at H = 65536; the tail's order by a
sort and by a one-column scatter of positions; the table write of ready
rows), and the buffer each whole form leaves is compared with the sums
in float64 on every touched row.  Then the per-slot form and the route
over a sweep of widths on a [2^21, D] table at MVM's and at FFM's
batch: the widths at which the route wins at both are
``step.DICT_SCATTER_COLUMNS``.

A measurement path: exits 1 without a TPU, every row names the device it
ran on, and a whole form further than 1e-4 of the largest sum from
float64 (probe_hot2.py's line; both forms' distances are printed) ends
the probe with a non-zero exit code.
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
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import numpy as np  # noqa: E402

from probe_cold_gather import cell_batch  # noqa: E402

# cell -> the width of its widest table
CELLS = {
    "mvm_ftrl_criteo_tb": 10,
    "dcn_ftrl_criteo_tb": 26,
    "autoint_ftrl_criteo_tb": 16,
    "ffm_ftrl_criteo_tb": 160,
    "lr_ftrl_criteo_tb": 1,
}
SWEEP = (1, 2, 4, 8, 16, 32, 64, 160)
SWEEP_AT = ("mvm_ftrl_criteo_tb", "ffm_ftrl_criteo_tb")
SWEEP_ROWS_LOG2 = 21
PIECE = 1 << 15


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4800000007)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--configs", default=",".join(CELLS))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops import hot, window
    from xflow_tpu.parallel import step

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU (found {device.platform}): nothing measured",
              file=sys.stderr)
        return 1
    stamp = {"platform": device.platform, "device_kind": device.device_kind}
    rows_out: list[dict] = []

    def emit(row: dict) -> None:
        rows_out.append({**row, **stamp})
        print(json.dumps(rows_out[-1]), flush=True)

    def timed(tag, form, fn, *xs, indices, donated=None):
        """ms a call of ``fn(*xs)``; with ``donated`` (a [T, D] buffer)
        ``fn(buffer, *xs)`` chained on its own result.  Returns the first
        call's result."""
        try:
            if donated is None:
                f = jax.jit(lambda *a: fn(*a))
                first = jax.block_until_ready(f(*xs))
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = f(*xs)
                jax.block_until_ready(out)
            else:
                f = jax.jit(lambda g, *a: fn(g, *a), donate_argnums=0)
                out = jax.block_until_ready(f(donated(), *xs))
                first = out
                out = f(donated(), *xs)  # warm on a buffer of its own
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(args.calls):
                    out = f(out, *xs)
                jax.block_until_ready(out)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            emit({"at": tag, "form": form, "refused": str(e).splitlines()[0]})
            return None
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        emit({"at": tag, "form": form, "indices": indices, "ms": ms,
              "ns_per_index": ms * 1e6 / indices})
        return first

    def forms_at(tag, planes, d, t_rows, shift, key, whole_only=False):
        plan = planes["cold_plan"]
        b, kc = planes["keys"].shape
        m = b * kc
        mask = planes["mask"].reshape(-1)
        shifted = {**plan, "cu": plan["cu"] >> shift, "ct": plan["ct"] >> shift}
        keys_eff = jnp.where(
            mask > 0, planes["keys"].reshape(-1) >> shift, t_rows
        )
        occ = jax.random.normal(key, (m, d), jnp.float32) * mask[:, None]
        cap_u, cap_t = plan["cu"].shape[0], plan["ct"].shape[0]
        n_tab = cap_u + cap_t
        lane = window.lane_select_tpu

        def zeros():
            return jnp.zeros((t_rows, d), jnp.float32)

        def per_slot(g, k, o):
            return g.at[k].add(o, mode="drop")

        def route(g, pl, o):
            sp = step.dict_scatter_plan(pl, t_rows, lane)
            return g.at[sp["rows"]].add(
                step.dict_cold_grads(sp, o), mode="drop"
            )

        # float64 sums of the touched rows
        k_np = np.asarray(keys_eff)
        live = k_np < t_rows
        uniq, inv = np.unique(k_np[live], return_inverse=True)
        occ64 = np.asarray(occ, np.float64)[live]
        ref = np.zeros((len(uniq), d))
        for j in range(d):
            ref[:, j] = np.bincount(inv, occ64[:, j], len(uniq))
        scale = np.abs(ref).max()
        uniq_dev = jnp.asarray(uniq.astype(np.int32))

        def off_float64(form, buf):
            if buf is None:
                return True
            got = np.asarray(buf[uniq_dev], np.float64)
            total = float(np.asarray(jnp.sum(jnp.abs(buf))))
            off = float(np.abs(got - ref).max() / scale)
            # nothing landed outside the touched rows
            clean = abs(total - np.abs(got).sum()) <= 1e-3 * total
            emit({"at": tag, "check": form, "off_float64": off,
                  "untouched_rows_zero": bool(clean),
                  "equal": bool(off <= 1e-4 and clean)})
            return off <= 1e-4 and clean

        ok = off_float64("per slot", timed(
            tag, f"D={d} per padded slot into [{t_rows}, {d}] (the "
            "parent's; shipped for a plain-wire batch)",
            per_slot, keys_eff, occ, indices=m, donated=zeros))
        ok &= off_float64("route", timed(
            tag, f"D={d} the whole route: plan + dictionary sum + tail "
            f"rows + table write of {n_tab} indices"
            + (" (shipped)" if d in step.DICT_SCATTER_COLUMNS
               else " (not shipped)"),
            route, shifted, occ, indices=n_tab, donated=zeros))
        if whole_only:
            return ok

        sp = jax.jit(
            lambda pl: step.dict_scatter_plan(pl, t_rows, lane)
        )(shifted)
        seg, order = sp["seg"], sp["order"]
        timed(tag, "the plan alone (seg, order, rows)",
              lambda pl: step.dict_scatter_plan(pl, t_rows, lane),
              shifted, indices=m)
        ready = jax.jit(step.dict_cold_grads)(sp, occ)
        timed(tag, f"D={d} table write alone: ready rows, one scatter-add "
              f"of {n_tab} indices", per_slot, sp["rows"], ready,
              indices=n_tab, donated=zeros)
        if cap_u and cap_t:
            def two_writes(g, r, o):
                g = g.at[r[:cap_u]].add(o[:cap_u], mode="drop")
                return g.at[r[cap_u:]].add(o[cap_u:], mode="drop")

            timed(tag, f"D={d} table write alone: the dictionary's rows and "
                  "the tail's as two scatter-adds", two_writes, sp["rows"],
                  ready, indices=n_tab, donated=zeros)
        if cap_u:
            def plain_sum(s, o):
                return jnp.zeros((cap_u, d), o.dtype).at[s].add(
                    o, mode="drop")

            want = timed(tag, f"D={d} dictionary sum: plain scatter-add of "
                         f"{m} slots into [{cap_u}, {d}] (shipped)",
                         plain_sum, seg, occ, indices=m)

            def close(form, got):
                if got is None:
                    return
                off = float(jnp.abs(got - want).max() / jnp.abs(want).max())
                emit({"at": tag, "check": form, "off_plain_sum": off,
                      "equal": off <= 1e-5})

            m_pad = -(-m // PIECE) * PIECE

            def pieces_sum(s, o):
                s = jnp.pad(s, (0, m_pad - m), constant_values=cap_u)
                o = jnp.pad(o, ((0, m_pad - m), (0, 0)))

                def body(acc, xs):
                    k, g = xs
                    return acc.at[k].add(g, mode="drop"), None

                acc, _ = jax.lax.scan(
                    body, jnp.zeros((cap_u, d), o.dtype),
                    (s.reshape(-1, PIECE), o.reshape(-1, PIECE, d)))
                return acc

            close("pieces", timed(
                tag, f"D={d} dictionary sum: {PIECE} slots at a time under "
                "a scan", pieces_sum, seg, occ, indices=m))

            def columns_sum(s, o):
                return jnp.zeros((d, cap_u), o.dtype).at[:, s].add(
                    o.T, mode="drop").T

            close("columns-minor buffer", timed(
                tag, f"D={d} dictionary sum: into a [{d}, {cap_u}] buffer, "
                "the entries on the lanes", columns_sum, seg, occ,
                indices=m))
            if d <= 32:
                big = 1 << 16  # the head's scan wants a power of two

                def scan_sum(s, o):
                    return hot.hot_scatter(
                        jnp.where(s >= cap_u, big, s), o, big)[:cap_u]

                close("one-hot scan", timed(
                    tag, f"D={d} dictionary sum: ops/hot.py's one-hot scan "
                    "at H = 65536", scan_sum, seg, occ, indices=m))
        if cap_t:
            is_tail, tail_idx = plan["is_tail"], plan["tail_idx"]

            def by_sort(t):
                pos = jnp.arange(m, dtype=jnp.int32)
                return jnp.sort(jnp.where(t, pos, m))[:cap_t]

            timed(tag, f"tail order: one sort of {m} positions (shipped)",
                  by_sort, is_tail, indices=m)

            def by_scatter(t, ti):
                pos = jnp.arange(m, dtype=jnp.int32)
                return jnp.full((cap_t,), m, jnp.int32).at[
                    jnp.where(t, ti, cap_t)].set(pos, mode="drop")

            got = timed(tag, "tail order: a one-column scatter of positions "
                        f"into [{cap_t}]", by_scatter, is_tail, tail_idx,
                        indices=m)
            emit({"at": tag, "check": "scatter of positions == sort",
                  "equal": bool(jnp.array_equal(got, order))})
            timed(tag, f"D={d} tail rows: occ[order], ONE row gather of "
                  f"{cap_t} rows (shipped)",
                  lambda o, i: jnp.take(o, i, axis=0, mode="clip"),
                  occ, order, indices=cap_t)
        return ok

    ok = True
    key = jax.random.key(args.seed & 0x7FFFFFFF)
    for config in args.configs.split(","):
        t0 = time.perf_counter()
        fields, cb = cell_batch(args.seed, config)
        print(f"{config}: batch made in {time.perf_counter() - t0:.1f} s: "
              f"n_cold {cb.n_cold}, dict {cb.n_dict} entries / "
              f"{cb.n_dict_occ} occurrences, caps cu {len(cb.cu)} ci "
              f"{len(cb.ci)} ct {len(cb.ct)}", flush=True)
        cfg = types.SimpleNamespace(
            max_nnz=fields["max_nnz"], hot_nnz=fields["hot_nnz"]
        )
        wire = jax.tree.map(jnp.asarray, cb.wire(False))
        planes = jax.jit(functools.partial(
            step.expand_dict_wire, cfg, window.lane_select_tpu
        ))(wire)
        emit({"at": config, "slots": int(planes["keys"].size),
              "cap_cu": len(cb.cu), "cap_ct": len(cb.ct),
              "n_dict": cb.n_dict, "n_tail": cb.n_cold - cb.n_dict_occ})
        log2 = fields["table_size_log2"]
        ok &= forms_at(config, planes, CELLS[config], 1 << log2, 0, key)
        if config in SWEEP_AT:
            for d in SWEEP:
                if d == CELLS[config]:
                    continue
                ok &= forms_at(
                    f"{config} sweep [2^{SWEEP_ROWS_LOG2}, D]", planes, d,
                    1 << SWEEP_ROWS_LOG2, max(log2 - SWEEP_ROWS_LOG2, 0),
                    key, whole_only=True)

    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "probe_cold_scatter.json"), "w") as f:
        json.dump(rows_out, f, indent=1)
    ok &= all(r.get("equal", True) for r in rows_out)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

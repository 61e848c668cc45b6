"""Chip probe of the dictionary-wire decode (PERF.md section 6, PR 25):
the shipped window form against the parent's scalar gathers, at the
shapes of one real flagship batch.

    python scripts/probe_wire_decode.py WIRE.npz [--old OLD_STEP.py]

``WIRE.npz`` holds one batch's ``CompactBatch.wire()`` planes.  Each form
is timed as 20 chained calls closed by one fetch.  ``--old`` names a copy
of the parent's parallel/step.py, whose ``TrainStep._expand_dict_wire`` is
timed, and compared bit for bit, against this tree's ``expand_dict_wire``.

A measurement path: exits 1 without a TPU, every row names the device it
ran on, and a form that fails or differs from the host ends the probe
with a non-zero exit code.  (The forms that did not ship, with their
times, are rows of the PERF.md table only.)
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def running_count(counts, flags, width):
    """Tier-A entries before each padded position of a [B, width]
    section, on the host: the index stream the decode takes with."""
    counts = counts.astype(np.int64)
    col = np.arange(width)[None, :]
    valid = col < counts[:, None]
    start = np.cumsum(counts) - counts
    pos = (start[:, None] + np.minimum(col, counts[:, None])).ravel()
    f = np.append(np.unpackbits(flags, bitorder="little"), 0)
    is_a = valid.ravel() & (f[np.minimum(pos, len(f) - 1)] == 1)
    return (np.cumsum(is_a) - is_a).astype(np.int32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("wire")
    ap.add_argument("--old", default="")
    ap.add_argument("--table-size-log2", type=int, default=28)
    ap.add_argument("--max-nnz", type=int, default=12)
    ap.add_argument("--hot-nnz", type=int, default=28)
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops import window
    from xflow_tpu.parallel.step import expand_dict_wire

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU (found {device.platform}): nothing measured",
              file=sys.stderr)
        return 1
    stamp = {"platform": device.platform, "device_kind": device.device_kind}
    wire_np = dict(np.load(args.wire))
    rows: list[dict] = []

    def run(name: str, fn, *xs, outputs: int):
        f = jax.jit(fn)
        dev = jax.tree.map(jnp.asarray, xs)
        out = jax.block_until_ready(f(*dev))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = f(*dev)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        rows.append({
            "form": name, "outputs": outputs, "ms": ms,
            "ns_per_output": ms * 1e6 / outputs, **stamp,
        })
        print(rows[-1], flush=True)
        return out

    def check(what: str, same: bool) -> None:
        rows.append({"check": what, "equal": bool(same), **stamp})
        print(rows[-1], flush=True)

    sections = {"cold": ("cw_cc", "cw_cf", "cw_ci", args.max_nnz)}
    if "cw_hc" in wire_np:
        sections["hot"] = ("cw_hc", "cw_hf", "cw_h8", args.hot_nnz)
    for sec, (counts, flags, plane, width) in sections.items():
        idx = running_count(wire_np[counts], wire_np[flags], width)
        src = wire_np[plane].astype(np.int32)
        tag = f"{sec}[{len(idx)}]"
        want = np.append(src, 0)[idx]
        run(f"{tag} scalar gather src[idx] (the parent's form)",
            lambda s, i: jnp.take(s, i, mode="clip"), src, idx,
            outputs=len(idx))
        got = run(
            f"{tag} monotone_take, Mosaic lane select (shipped)",
            lambda i, s: window.monotone_take(
                i, s, lane_select=window.lane_select_tpu),
            idx, src, outputs=len(idx))
        check(f"{tag} monotone_take == host", np.array_equal(got, want))

    cu = wire_np["cw_cu"].astype(np.int32)
    ci = wire_np["cw_ci"].astype(np.int32)
    if cu.ndim == 1 and len(cu):
        run(f"dict resolve cu[ci] [{len(ci)}] scalar gather (shipped)",
            lambda t, i: jnp.take(t, i, mode="clip"), cu, ci,
            outputs=len(ci))

    cfg = types.SimpleNamespace(
        max_nnz=args.max_nnz, hot_nnz=args.hot_nnz,
        table_size=1 << args.table_size_log2,
    )
    m_all = sum(len(wire_np[s[0]]) * s[3] for s in sections.values())
    new = run(
        "whole decode, this tree",
        functools.partial(expand_dict_wire, cfg, window.lane_select_tpu),
        wire_np, outputs=m_all)
    if args.old:
        spec = importlib.util.spec_from_file_location("old_step", args.old)
        old_step = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old_step)
        old = run(
            "whole decode, the parent's scalar gathers",
            functools.partial(
                old_step.TrainStep._expand_dict_wire,
                types.SimpleNamespace(cfg=cfg)),
            wire_np, outputs=m_all)
        check("decode == parent's, every plane", set(new) == set(old) and all(
            new[k].dtype == v.dtype and np.array_equal(new[k], v)
            for k, v in old.items()))

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_wire_decode.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r.get("equal", True) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

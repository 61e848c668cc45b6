"""Time the hot head's two scans (``ops/hot.py``: ``hot_gather`` and
``hot_scatter``, alone) on the chip against the plain gather and
scatter-add of the same slots, by default at the heads of
``mvm_tb.train_packed`` (H = 16384, D = 10, 4 194 304 slots a step) and
``dcn_tb.train_packed`` (D = 26, 2 097 152): whether the MXU head still
wins at D = 26, and by how much at 10 (ROADMAP A11; PERF.md section 6).

    chiprun -- python scripts/probe_hot2.py [--shapes 16384,10,4194304;16384,26,2097152]
        [--old HOT.py]

Keys are drawn twice: zipf-1.2 ranks as the cells' rows draw them (a
fifth of the slots on the first row: the scatter-add's worst case), and
uniform over the head; either way a key beyond the head is the sentinel
H (a padded slot: about a seventh).  ``--old`` times another tree's
``ops/hot.py`` (a copy of the file) beside this one's, AFTER it.  Prints
one JSON object (ms a call and ns a slot by shape, keys and form) and
writes it to ``chiprun_out/hot_probe.json``.  Exit 1 without a TPU (a CPU
run times nothing worth writing down), or where a head's gather is not
bit for bit the plain one, or its scatter further than 1e-4 of the
largest sum from the sums in float64 (the plain scatter-add's own
distance is printed beside it: 10^6 float32 adds into one row)."""

from __future__ import annotations

import argparse
import importlib.util
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.ops import hot

SHAPES = "16384,10,4194304;16384,26,2097152"  # H, D, slots: MVM's and DCN's heads


def _ms(fn, *args, steps: int) -> tuple[float, jax.Array]:
    out = jax.block_until_ready(fn(*args))  # compiles
    start = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / steps * 1e3, out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--old", help="another tree's ops/hot.py, timed after this one's")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    forms = {"mxu": hot}
    if args.old:
        spec = importlib.util.spec_from_file_location("old_hot", args.old)
        forms["old_mxu"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(forms["old_mxu"])
    out: dict = {"device": jax.devices()[0].device_kind}
    ok = True
    for shape, dist in itertools.product(args.shapes.split(";"), ("zipf", "uniform")):
        h, d, m = map(int, shape.split(","))
        rng = np.random.default_rng(0)
        draws = (
            rng.zipf(1.2, size=m) - 1 if dist == "zipf"
            else rng.integers(0, h + h // 6, size=m)
        )
        keys_np = np.where(draws < h, draws, h).astype(np.int32)
        grads_np = rng.normal(size=(m, d)).astype(np.float32)
        exact = np.stack([  # the sums in float64, on the host
            np.bincount(keys_np, weights=grads_np[:, j], minlength=h + 1)[:h]
            for j in range(d)
        ], axis=1)
        keys, grads = jnp.asarray(keys_np), jnp.asarray(grads_np)
        w = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))

        def off_exact(sums):
            return float(np.max(np.abs(np.asarray(sums) - exact)) / np.max(np.abs(exact)))

        plain_gather = jax.jit(lambda w, k: hot.hot_gather(w, k, impl="seg"))
        plain_scatter = jax.jit(
            lambda k, g: jnp.zeros((h, d), jnp.float32).at[k].add(g, mode="drop")
        )
        g_ms, rows = _ms(plain_gather, w, keys, steps=args.steps)
        s_ms, sums = _ms(plain_scatter, keys, grads, steps=args.steps)
        cell = {"plain": {
            "gather_ms": g_ms, "scatter_ms": s_ms,
            "scatter_off_float64": off_exact(sums),
        }}
        for name, mod in forms.items():
            g_ms, got_rows = _ms(jax.jit(mod.hot_gather), w, keys, steps=args.steps)
            s_ms, got_sums = _ms(
                jax.jit(lambda k, g, mod=mod: mod.hot_scatter(k, g, h)),
                keys, grads, steps=args.steps,
            )
            cell[name] = {
                "gather_ms": g_ms, "scatter_ms": s_ms,
                "gather_bit_equal": bool(jnp.array_equal(got_rows, rows)),
                "scatter_off_float64": off_exact(got_sums),
            }
            ok &= cell[name]["gather_bit_equal"]
            ok &= cell[name]["scatter_off_float64"] <= 1e-4
        for form in cell.values():
            form["gather_ns_per_slot"] = form["gather_ms"] * 1e6 / m
            form["scatter_ns_per_slot"] = form["scatter_ms"] * 1e6 / m
        out[f"H{h}_D{d}_M{m}_{dist}"] = cell
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hot_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Time the hot head's two scans (``ops/hot.py``: ``hot_gather`` and
``hot_scatter``, alone) on the chip against the plain gather and
scatter-add of the same slots, by default at the heads of
``mvm_tb.train_packed`` (H = 16384, D = 10, 4 194 304 slots a step) and
``dcn_tb.train_packed`` (D = 26, 2 097 152), and the two gathers alone
over a sweep of widths at MVM's slots: where the scan's price (0.7 ns a
slot and column) crosses the plain row's (2-2.6 ns a slot in pieces, 4.2
gathered whole) is where ``hot.PLAIN_GATHER_MIN_COLUMNS`` belongs
(ROADMAP A11; PERF.md section 6).

    chiprun -- python scripts/probe_hot2.py [--shapes "H,D,slots[,g];..."]
        [--old HOT.py] [--seed N]
    chiprun -- python scripts/probe_hot2.py --scatter [--seed N]

A shape that ends in ``,g`` times the gathers alone, on zipf keys.  Every
other shape's keys are drawn three ways: ``zipf``, zipf-1.2 ranks over
the whole head (a fifth of the slots on the first row, ~800 000 adds
into it: the scatter-add's worst case); ``uniform`` over the head;
either way a key beyond the head is the sentinel H (a padded slot: about
a seventh); and, where the shape is a benchmark cell's head, ``cell``:
the hot plane of that cell's first batch as the benchmark's generator
and remap make it (``probe_cold_gather.cell_batch``: zipf-1.2 ranks PER
FIELD, so a row takes at most one add an example, B and not 10^6), a
masked slot the sentinel, as the step's scatter sees it.  Under ``zipf``
the plain gather is also timed by the slots it reads at a time
(``hot._PLAIN_GATHER_SLOTS``).  Under ``cell``
the plain scatter-add's and the scan's distance from the float64 sums is
the number that says whether the head's scatter at D = 26 can go the
plain way under the benchmark's ``ROWS_RTOL`` 1e-6.  ``--old`` times
another tree's ``ops/hot.py`` (a copy of the file) beside this one's,
AFTER it.  Prints one JSON object (ms a call and ns a slot by shape,
keys and form) and writes it to ``chiprun_out/hot_probe.json``.  Exit 1
without a TPU (a CPU run times nothing worth writing down), or where a
head's gather is not bit for bit the plain one, or its scatter further
than 1e-4 of the largest sum from the sums in float64 (the plain
scatter-add's own distance is printed beside it).

``--scatter`` (PR 49) asks the other question alone: where
``hot.PLAIN_SCATTER_MIN_COLUMNS`` belongs and what the plain scatter
should look like.  Under the cells' OWN keys (DCN's hot plane for
2 097 152 slots, MVM's for 4 194 304) it times the scatter scan, the
plain scatter-add written whole and the shipped plain form
(``hot_scatter(impl="seg")``: ``hot._PLAIN_SCATTER_SLOTS`` slots at a
time) over D = 4..64; the same three at AutoInt's and xDeepFM's heads
(524 288 slots, D = 16 and 10) with each form's distance from the
float64 sums; and at DCN's and MVM's heads the plain form by the slots
it adds at a time, in four writings, each with its distance: ``lanes``
(the shipped one: a piece arrives ``[D, C]``, the slots on the lanes, as
a chunk of the scan does, and is turned in the loop), ``rows`` (the
pieces cut out of ``[M, D]`` as they lie: on the TPU one 128-lane row a
slot, 1 GiB at DCN's head), ``dh`` (the accumulator carried ``[D, H]``)
and ``two`` (each piece summed into its own zeroed ``[H, D]`` partial,
the partials added: a shorter chain of adds a row, the fallback if the
plain form's rows do not hold ``ROWS_RTOL``).  The losers stay here.
Writes ``chiprun_out/hot_scatter_probe.json``."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from xflow_tpu.ops import hot

# H, D, slots: MVM's and DCN's heads, then the gathers alone over D at
# MVM's slots
SHAPES = "16384,10,4194304;16384,26,2097152;" + ";".join(
    f"16384,{d},4194304,g" for d in (1, 2, 4, 8, 16, 32)
)
# the benchmark configurations whose head a shape is
CELLS = {
    (16384, 10, 4194304): "mvm_ftrl_criteo_tb",
    (16384, 26, 2097152): "dcn_ftrl_criteo_tb",
}


def _keys(dist: str, h: int, m: int, rng, seed: int, config: str | None):
    """int32 [m] keys of one draw (module docstring); the sentinel is H."""
    if dist == "cell":
        from probe_cold_gather import cell_batch

        batch = cell_batch(seed, config)[1].expand()
        keys = np.where(batch.hot_mask > 0, batch.hot_keys, h).reshape(-1)
        assert keys.shape == (m,), (keys.shape, m)
        return keys.astype(np.int32)
    draws = (
        rng.zipf(1.2, size=m) - 1 if dist == "zipf"
        else rng.integers(0, h + h // 6, size=m)
    )
    return np.where(draws < h, draws, h).astype(np.int32)


def _ms(fn, *args, steps: int) -> tuple[float, jax.Array]:
    out = jax.block_until_ready(fn(*args))  # compiles
    start = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / steps * 1e3, out


def _by_piece(w, keys, rows, steps: int) -> dict:
    """ms a call of the plain gather by the slots it reads at a time
    (``hot._PLAIN_GATHER_SLOTS``; the last is all of them, no loop: a
    [slots, D] result of 512 B a slot)."""
    shipped = hot._PLAIN_GATHER_SLOTS
    out = {}
    try:
        for piece in (4096, 16384, 65536, 262144, keys.shape[0]):
            hot._PLAIN_GATHER_SLOTS = piece
            ms, got = _ms(
                jax.jit(lambda w, k: hot.hot_gather(w, k, impl="seg")),
                w, keys, steps=steps,
            )
            assert bool(jnp.array_equal(got, rows)), piece
            out[str(piece)] = ms
    finally:
        hot._PLAIN_GATHER_SLOTS = shipped
    return out


def _float64_sums(keys_np, grads_np, h: int):
    """The sums in float64, on the host (the sentinel H is the one key
    outside the head)."""
    return np.stack([
        np.bincount(keys_np, weights=grads_np[:, j], minlength=h + 1)[:h]
        for j in range(grads_np.shape[1])
    ], axis=1)


def _off_float64(sums, exact) -> float:
    """The largest distance from the float64 sums, of the largest sum."""
    return float(np.max(np.abs(np.asarray(sums) - exact)) / np.max(np.abs(exact)))


def _scatters(cell, forms, keys_np, rng, h: int, d: int, steps: int) -> bool:
    """Adds to ``cell`` the plain scatter-add's and every form's scatter
    scan's ms a call and distance from the float64 sums (of the largest
    sum), and the most adds one row takes; whether every scan is within
    1e-4."""
    m = len(keys_np)
    grads_np = rng.normal(size=(m, d)).astype(np.float32)
    exact = _float64_sums(keys_np, grads_np, h)
    keys, grads = jnp.asarray(keys_np), jnp.asarray(grads_np)

    def off_exact(sums):
        return _off_float64(sums, exact)

    plain_scatter = jax.jit(
        lambda k, g: jnp.zeros((h, d), jnp.float32).at[k].add(g, mode="drop")
    )
    s_ms, sums = _ms(plain_scatter, keys, grads, steps=steps)
    cell["plain"].update(scatter_ms=s_ms, scatter_off_float64=off_exact(sums))
    cell["plain"]["most_adds_a_row"] = int(np.bincount(keys_np, minlength=h)[:h].max())
    ok = True
    for name, mod in forms.items():
        s_ms, got_sums = _ms(
            jax.jit(lambda k, g, mod=mod: mod.hot_scatter(k, g, h)),
            keys, grads, steps=steps,
        )
        cell[name].update(scatter_ms=s_ms, scatter_off_float64=off_exact(got_sums))
        ok &= cell[name]["scatter_off_float64"] <= 1e-4
    return ok


# the heads --scatter reads: H, D, slots -> the cell's configuration
SCATTER_CELLS = {
    **CELLS,
    (16384, 16, 524288): "autoint_ftrl_criteo_tb",
    (16384, 10, 524288): "xdeepfm_ftrl_criteo_tb",
}
SWEEP_COLUMNS = (4, 8, 10, 16, 26, 32, 64)
PIECES = (4096, 16384, 32768, 65536, 262144)


def _shipped(k, g, h: int, c: int):
    """``hot_scatter(impl="seg")`` at ``c`` slots a piece."""
    was = hot._PLAIN_SCATTER_SLOTS
    hot._PLAIN_SCATTER_SLOTS = c
    try:
        return hot.hot_scatter(k, g, h, impl="seg")
    finally:
        hot._PLAIN_SCATTER_SLOTS = was


def _whole(k, g, h: int):
    """The plain scatter-add written whole: the shipped form at one
    piece, no loop."""
    return _shipped(k, g, h, k.shape[0])


def _drop(k, h: int):
    return jnp.where((k >= 0) & (k < h), k, h)


def _in_pieces(k, g, h: int, c: int, writing: str):
    """The losers' writings of the plain scatter-add, ``c`` slots at a
    time (module docstring); ``c`` divides the slots."""
    d = g.shape[1]
    ks = k.reshape(-1, c)
    if writing == "rows":
        def body(acc, xs):
            return acc.at[_drop(xs[0], h)].add(xs[1], mode="drop"), None
        return jax.lax.scan(
            body, jnp.zeros((h, d), jnp.float32), (ks, g.reshape(-1, c, d))
        )[0]
    lanes = hot.with_layout_constraint(
        g.reshape(-1, c, d).transpose(0, 2, 1),
        hot.Layout(major_to_minor=(0, 1, 2)),
    )  # [M/C, D, C]
    if writing == "dh":
        def body(acc, xs):
            return acc.at[:, _drop(xs[0], h)].add(xs[1], mode="drop"), None
        return jax.lax.scan(
            body, jnp.zeros((d, h), jnp.float32), (ks, lanes)
        )[0].T
    assert writing == "two", writing

    def body(acc, xs):
        return acc + _whole(xs[0], xs[1].T, h), None
    return jax.lax.scan(body, jnp.zeros((h, d), jnp.float32), (ks, lanes))[0]


def scatter_probe(seed: int, steps: int) -> tuple[dict, bool]:
    """The ``--scatter`` run (module docstring): (report, whether every
    form stands within 1e-4 of the float64 sums)."""
    from probe_cold_gather import cell_batch

    out: dict = {}
    ok = True
    planes = {}
    for (h, d, m), config in SCATTER_CELLS.items():
        batch = cell_batch(seed, config)[1].expand()
        keys_np = np.where(batch.hot_mask > 0, batch.hot_keys, h).reshape(-1)
        assert keys_np.shape == (m,), (keys_np.shape, m)
        planes[(h, d, m)] = keys_np.astype(np.int32)
    rng = np.random.default_rng(0)

    def timed(fn, keys, grads):
        return _ms(jax.jit(fn), keys, grads, steps=steps)

    # 1. the width sweep, under DCN's keys (2 097 152) and MVM's (4 194 304)
    for cell in CELLS:
        h, _, m = cell
        keys = jnp.asarray(planes[cell])
        sweep = {}
        for d in SWEEP_COLUMNS:
            grads = jax.random.normal(jax.random.PRNGKey(d), (m, d), jnp.float32)
            row = {}
            row["scan_ms"], want = timed(
                lambda k, g: hot.hot_scatter(k, g, h, impl="mxu"), keys, grads
            )
            row["whole_ms"], got = timed(lambda k, g: _whole(k, g, h), keys, grads)
            row["plain_ms"], got2 = timed(
                lambda k, g: hot.hot_scatter(k, g, h, impl="seg"), keys, grads
            )
            scale = float(jnp.max(jnp.abs(want)))
            row["whole_off_scan"] = float(jnp.max(jnp.abs(got - want))) / scale
            row["plain_off_scan"] = float(jnp.max(jnp.abs(got2 - want))) / scale
            ok &= max(row["whole_off_scan"], row["plain_off_scan"]) <= 1e-4
            for key in ("scan_ms", "whole_ms", "plain_ms"):
                row[key[:-3] + "_ns_per_slot"] = row[key] * 1e6 / m
            sweep[str(d)] = row
        out[f"sweep_M{m}"] = sweep

    # 2. each head under its own keys, with the distance from float64;
    # 3. DCN's and MVM's by the piece and the writing
    for (h, d, m), keys_np in planes.items():
        grads_np = rng.normal(size=(m, d)).astype(np.float32)
        exact = _float64_sums(keys_np, grads_np, h)
        keys, grads = jnp.asarray(keys_np), jnp.asarray(grads_np)
        head = {"most_adds_a_row": int(np.bincount(keys_np, minlength=h)[:h].max())}
        forms = {
            "scan": lambda k, g: hot.hot_scatter(k, g, h, impl="mxu"),
            "whole": lambda k, g: _whole(k, g, h),
            "plain": lambda k, g: hot.hot_scatter(k, g, h, impl="seg"),
        }
        if (h, d, m) in CELLS:
            for c in PIECES:
                forms[f"lanes_{c}"] = lambda k, g, c=c: _shipped(k, g, h, c)
                for writing in ("rows", "dh", "two"):
                    forms[f"{writing}_{c}"] = (
                        lambda k, g, c=c, w=writing: _in_pieces(k, g, h, c, w)
                    )
        for name, fn in forms.items():
            ms, sums = timed(fn, keys, grads)
            head[name] = {
                "ms": ms, "ns_per_slot": ms * 1e6 / m,
                "off_float64": _off_float64(sums, exact),
            }
            ok &= head[name]["off_float64"] <= 1e-4
        out[f"H{h}_D{d}_M{m}_cell"] = head
    return out, ok


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--old", help="another tree's ops/hot.py, timed after this one's")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1, help="of the cells' batches")
    ap.add_argument(
        "--scatter", action="store_true",
        help="the scatter's widths, pieces and writings alone (PR 49)",
    )
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing to time", file=sys.stderr)
        return 1
    if args.scatter:
        out, ok = scatter_probe(args.seed, args.steps)
        out["device"] = jax.devices()[0].device_kind
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/hot_scatter_probe.json", "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))
        return 0 if ok else 1
    forms = {"mxu": hot}
    if args.old:
        spec = importlib.util.spec_from_file_location("old_hot", args.old)
        forms["old_mxu"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(forms["old_mxu"])
    out: dict = {"device": jax.devices()[0].device_kind}
    ok = True
    for shape in args.shapes.split(";"):
        h, d, m, *only = shape.split(",")
        h, d, m = int(h), int(d), int(m)
        config = CELLS.get((h, d, m))
        dists = ("zipf",) if only else ("zipf", "uniform") + ("cell",) * bool(config)
        for dist in dists:
            rng = np.random.default_rng(0)
            keys_np = _keys(dist, h, m, rng, args.seed, config)
            keys = jnp.asarray(keys_np)
            w = jnp.asarray(rng.normal(size=(h, d)).astype(np.float32))
            plain_gather = jax.jit(lambda w, k: hot.hot_gather(w, k, impl="seg"))
            g_ms, rows = _ms(plain_gather, w, keys, steps=args.steps)
            cell = {"plain": {"gather_ms": g_ms}}
            for name, mod in forms.items():
                g_ms, got_rows = _ms(
                    jax.jit(lambda w, k, mod=mod: mod.hot_gather(w, k, impl="mxu")),
                    w, keys, steps=args.steps,
                )
                cell[name] = {
                    "gather_ms": g_ms,
                    "gather_bit_equal": bool(jnp.array_equal(got_rows, rows)),
                }
                ok &= cell[name]["gather_bit_equal"]
            if not only:
                ok &= _scatters(cell, forms, keys_np, rng, h, d, args.steps)
            if not only and dist == "zipf":
                cell["plain"]["gather_ms_by_piece"] = _by_piece(
                    w, keys, rows, args.steps
                )
            for form in cell.values():
                for key in [k for k in form if k.endswith("_ms")]:
                    form[key[:-3] + "_ns_per_slot"] = form[key] * 1e6 / m
            out[f"H{h}_D{d}_M{m}_{dist}"] = cell
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hot_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

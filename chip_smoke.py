"""chip_smoke.py — does the main path still start on the chip?

    python chip_smoke.py              # on a machine with a TPU
    python chip_smoke.py --rehearsal  # toy size, any backend, never "ok"

One process (a chip belongs to one process at a time) drives the LR
flagship through the entry points a user calls, at full width, on data
generated here from a seed:

  0  device   JAX must report a TPU; there is no CPU fallback
  -  build    the native parser, rebuilt from the committed source
  1  train    scripts/gen_synth -> xflow_tpu.io.binary -> .io.packed ->
              xflow_tpu.train.main: packed-v2 shards -> input fan-out ->
              dictionary wire -> staging ring -> TrainStep.train, then
              evaluate, checkpoint, --export-artifact
  2  parity   ops/hot.py on the chip against its references, and two
              steps of one TrainStep on the chip against the CPU backend
  3  serve    xflow_tpu.serve.__main__.main over the exported artifact:
              ``score`` against the trainer's own predictions, then
              ``loadgen`` single-row traffic through ReplicaFleet and
              MicroBatcher
  4  mesh     Phase 1's geometry row-sharded over four chips, when the
              machine has them, and three steps of FM (v_dim=10) on the
              same mesh against one chip

Any failed check raises: the exit code is non-zero and no result line
is printed.  On success stdout ends with two JSON lines.  The one before
last is the report: geometry, compile-cache directory and per-phase
seconds (compile apart from run) with what each phase observed.  The
LAST is the verdict the driver reads, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it — or ``"rehearsal": true`` in place
of ``ok`` under --rehearsal, which exists to debug this script off the
chip.  Rates seen here are not metrics; the benchmark owns those.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# generated data, checkpoints and artifacts: gitignored, wiped at the
# start of every run so nothing is read that this run did not produce
WORK = os.path.join(REPO, ".bench_cache", "chip_smoke")

SEED = 7
# the LR flagship's geometry of rounds 3-5 (docs/PERF.md): T=2^24,
# B=131072, hot head 2^12 x 32 on the MXU, cold capacity 16 on the DMA
# path
FLAGSHIP = dict(table_size_log2=24, batch_size=131072, max_nnz=16,
                hot_size_log2=12, hot_nnz=32)
TOY = dict(table_size_log2=16, batch_size=1024, max_nnz=16,
           hot_size_log2=8, hot_nnz=32)
TRAIN_SHARDS = 2  # each: two full batches and a quarter-batch tail
EPOCHS = 8
# AUC the planted-signal test split (65536 rows: one standard error is
# ~0.0025) must clear after EPOCHS x 6 steps.  Mean-over-batch gradients
# make B=131072 move only the head of the key distribution in so few
# steps (docs/CONVERGENCE.md: B=8192 reaches 0.53 where B=512 reaches
# 0.65), so this is a check that training learns, not of quality: the
# same run on the CPU backend scores 0.5233.
MIN_AUC = 0.515
SERVE_QPS, SERVE_SECONDS = 100.0, 3.0  # ~300 single-row requests
PARITY_ROWS = 512


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Seconds and counts of XLA compilations, from the program's own
    compile watch (xflow_tpu/obs/startup.py: JAX's monitoring events,
    one listener pair a process, installed by enable_compile_cache),
    so every phase can report compile time apart from run time and say
    how many programs came out of the persistent cache."""

    @staticmethod
    def totals() -> dict:
        from xflow_tpu.obs import startup

        return startup.compile_totals()

    @property
    def compiles(self) -> int:
        return self.totals()["requests"]

    @contextlib.contextmanager
    def phase(self, report: dict, name: str):
        print(f"chip_smoke: phase {name} ...", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        before = self.totals()
        out: dict = {}
        yield out
        wall = time.perf_counter() - t0
        after = self.totals()
        compile_s = after["seconds"] - before["seconds"]
        report[name] = {
            "seconds": round(wall, 2),
            "compile_seconds": round(compile_s, 2),
            "run_seconds": round(wall - compile_s, 2),
            "compiles": after["requests"] - before["requests"],
            "cache_hits": after["cache_hits"] - before["cache_hits"],
            **out,
        }


class StepProbe:
    """What no metrics row carries: the TrainStep that train.main built
    (its hot_impl), every step's logloss and how many programs XLA
    compiled or loaded for it, and the first batch and last state as
    they sit on the devices.  Wraps TrainStep.dispatch_train for the
    duration of one train.main call."""

    def __init__(self, meter: CompileMeter) -> None:
        self.meter = meter
        self.step = None
        self.metrics: list = []
        self.compiles: list[int] = []
        self.first_arrays = None
        self.state = None

    @contextlib.contextmanager
    def installed(self):
        from xflow_tpu.parallel.step import TrainStep

        orig = TrainStep.dispatch_train
        probe = self

        def dispatch_train(step, state, arrays):
            before = probe.meter.compiles
            new_state, metrics = orig(step, state, arrays)
            # trace and compile run inside the call, on this thread
            probe.compiles.append(probe.meter.compiles - before)
            if probe.first_arrays is None:
                probe.first_arrays = arrays
            probe.step, probe.state = step, new_state
            probe.metrics.append(metrics)
            return new_state, metrics

        TrainStep.dispatch_train = dispatch_train
        try:
            yield self
        finally:
            TrainStep.dispatch_train = orig

    def loglosses(self) -> list[float]:
        import jax

        return [float(m["logloss"]) for m in jax.device_get(self.metrics)]


def rows_of(rows: list[dict], kind: str) -> list[dict]:
    return [r for r in rows if r.get("kind") == kind]


# -- phases ----------------------------------------------------------------


def phase_device(rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if device["platform"] != "tpu" and not rehearsal:
        print(
            f"chip_smoke: no chip found — JAX reports {device}; this "
            "script has no CPU fallback (--rehearsal debugs it off the "
            "chip and never prints ok)",
            file=sys.stderr,
        )
        sys.exit(1)
    return device


def phase_build(out: dict) -> None:
    """Both machines must run the committed parser.cc: the working tree
    may hold a stale ignored .so whose mtime a copy made fresh, and the
    driver's checkout holds none."""
    from xflow_tpu import native
    from xflow_tpu.native.build import build_if_needed

    t0 = time.perf_counter()
    build_if_needed(force=True)
    out["build_seconds"] = round(time.perf_counter() - t0, 2)
    check(native.available(), "native parser did not build or load")
    check(native.has_dict_encode(), "native library lacks xf_dict_encode")


def make_data(geom: dict, out: dict) -> dict:
    """Text shards from the seed, then the README's three commands'
    first two: CSR cache, packed-v2 cache (with the hot remap the
    trainer will load from its checkpoint dir)."""
    import scripts.gen_synth as gen
    from xflow_tpu.io import binary, freq, packed
    from xflow_tpu.trainer import find_shards

    b = geom["batch_size"]
    per_shard = 2 * b + b // 4
    paths = {
        "text": os.path.join(WORK, "data", "d"),
        "csr": os.path.join(WORK, "csr", "train"),
        "packed": os.path.join(WORK, "packed", "train"),
        "ckpt": os.path.join(WORK, "ckpt"),
    }
    for key in ("csr", "packed"):
        os.makedirs(os.path.dirname(paths[key]))
    os.makedirs(paths["ckpt"])
    t0 = time.perf_counter()
    gen.generate_dataset(
        paths["text"], TRAIN_SHARDS * per_shard, num_test=b // 2,
        train_shards=TRAIN_SHARDS, seed=SEED,
    )
    paths["train_text"] = paths["text"] + ".train"
    paths["test"] = paths["text"] + ".test"
    check(
        binary.main(["--train", paths["train_text"], "--out", paths["csr"]])
        == 0,
        "xflow_tpu.io.binary failed",
    )
    counts = freq.count_keys(
        find_shards(paths["csr"]), None, 1 << geom["table_size_log2"],
        64 << 20,
    )
    remap = freq.build_remap(counts, 1 << geom["hot_size_log2"])
    paths["remap"] = os.path.join(paths["ckpt"], "remap.npy")
    freq.save_remap(paths["remap"], remap)
    check(
        packed.main([
            "--train", paths["csr"], "--out", paths["packed"],
            "--batch-size", str(b),
            "--max-nnz", str(geom["max_nnz"]),
            "--table-size-log2", str(geom["table_size_log2"]),
            "--hot-size-log2", str(geom["hot_size_log2"]),
            "--hot-nnz", str(geom["hot_nnz"]),
            "--remap", paths["remap"],
        ]) == 0,
        "xflow_tpu.io.packed failed",
    )
    out["data_seconds"] = round(time.perf_counter() - t0, 2)
    out["examples_per_epoch"] = TRAIN_SHARDS * per_shard
    out["hot_mass"] = round(
        freq.hot_mass(counts, remap, 1 << geom["hot_size_log2"]), 4
    )
    return paths


def wire_shape_buckets(packed_prefix: str) -> int:
    """How many distinct wire shapes the packed shards hold — one
    compiled train program each (full batches share plane capacities;
    the tails land in smaller ones)."""
    from xflow_tpu.io import packed
    from xflow_tpu.trainer import find_shards

    shapes = set()
    for path in find_shards(packed_prefix):
        with open(path, "rb") as f:
            for cb, _, _ in packed.iter_compact_batches(f):
                wire = cb.wire(ship_slots=False)
                shapes.add(tuple(sorted(
                    (k, v.shape, str(v.dtype)) for k, v in wire.items()
                )))
    return len(shapes)


def train_argv(geom: dict, paths: dict, num_devices: int) -> list[str]:
    return [
        "--model", "lr", "--optimizer", "ftrl",
        "--train", paths["packed"],
        "--batch-size", str(geom["batch_size"]),
        "--table-size-log2", str(geom["table_size_log2"]),
        "--max-nnz", str(geom["max_nnz"]),
        "--hot-size-log2", str(geom["hot_size_log2"]),
        "--hot-nnz", str(geom["hot_nnz"]),
        "--num-devices", str(num_devices),
        "--input-streams", str(TRAIN_SHARDS),
        "--seed", "0",
    ]


def phase_train(
    geom: dict, paths: dict, device: dict, rehearsal: bool,
    meter: CompileMeter, out: dict,
) -> list:
    from xflow_tpu import train
    from xflow_tpu.obs.schema import load_jsonl

    paths["metrics"] = os.path.join(WORK, "train.jsonl")
    paths["artifact"] = os.path.join(WORK, "artifact")
    paths["pred"] = os.path.join(WORK, "pred.txt")
    probe = StepProbe(meter)
    with probe.installed():
        rc = train.main([
            *train_argv(geom, paths, 1),
            "--test", paths["test"],
            "--epochs", str(EPOCHS),
            "--checkpoint-dir", paths["ckpt"],
            "--metrics-out", paths["metrics"],
            "--pred-out", paths["pred"],
            "--export-artifact", paths["artifact"],
        ])
    check(rc == 0, f"train.main returned {rc}")
    rows = load_jsonl(paths["metrics"])
    epochs = rows_of(rows, "train_epoch")
    check(len(epochs) == EPOCHS, f"{len(epochs)} train_epoch rows")
    steps = sum(r["steps"] for r in epochs)
    ll = probe.loglosses()
    check(len(ll) == steps, f"{len(ll)} probed steps vs {steps} in rows")
    check(all(map(math.isfinite, ll)), f"logloss not finite: {ll}")
    check(ll[-1] < ll[0], f"logloss did not fall: {ll[0]} -> {ll[-1]}")
    check(
        epochs[-1]["train_logloss"] < epochs[0]["train_logloss"],
        "epoch logloss did not fall",
    )
    evals = rows_of(rows, "eval")
    check(len(evals) == 1, f"{len(evals)} eval rows")
    auc = evals[0]["auc"]
    # the toy split has a few thousand rows over 3.9 M ids: nothing to
    # learn, so the rehearsal only asks for a number
    min_auc = 0.0 if rehearsal else MIN_AUC
    check(auc > min_auc, f"test AUC {auc} not above {min_auc}")
    check(
        evals[0]["logloss"] < ll[0],
        f"held-out logloss {evals[0]['logloss']} no better than the "
        f"untrained model's {ll[0]}",
    )
    wire = rows_of(rows, "wire")
    check(
        wire and all(r["format"] == "dict" for r in wire),
        f"wire rows: {wire}",
    )
    want_impl = "auto" if device["platform"] == "tpu" else "seg"
    check(
        probe.step._hot_impl == want_impl,
        f"hot_impl {probe.step._hot_impl!r}, expected {want_impl!r}",
    )
    mem = rows_of(rows, "device_mem")
    check(
        mem and mem[0]["devices"][0]["platform"] == device["platform"],
        f"device_mem rows: {mem[:1]}",
    )
    # compilations: at most one train program per wire-shape bucket
    # (fewer where the step pads a shorter batch up to a length it has
    # shipped: TrainStep._settle_planes), all of them inside the first
    # epoch, none after
    buckets = wire_shape_buckets(paths["packed"])
    per_epoch = steps // EPOCHS
    check(
        1 <= sum(probe.compiles[:per_epoch]) <= buckets
        and not any(probe.compiles[per_epoch:]),
        f"train-step compiles per step {probe.compiles}: expected "
        f"1 to {buckets} in the first epoch and none after",
    )
    check(
        os.path.exists(os.path.join(paths["ckpt"], "LATEST")),
        "no checkpoint written",
    )
    check(
        os.path.exists(os.path.join(paths["artifact"], "manifest.json")),
        "no artifact exported",
    )
    out.update({
        "steps": steps,
        "first_logloss": round(ll[0], 6),
        "last_logloss": round(ll[-1], 6),
        "epoch_logloss": [round(r["train_logloss"], 6) for r in epochs],
        "eval_logloss": round(evals[0]["logloss"], 6),
        "auc": round(auc, 6),
        "wire": "dict",
        "wire_bytes_per_example": wire[0]["wire_bytes_per_example"],
        "hot_impl": probe.step._hot_impl,
        "train_programs": buckets,
    })
    return ll


def synthetic_batches(cfg, rng, count: int, head_share: float) -> list:
    """``count`` batches of 39 binary features a row at ``cfg``'s geometry:
    ``head_share`` of the keys in the hot head, the rest anywhere in the
    table."""
    import numpy as np

    from xflow_tpu.io.batch import make_batch

    k = cfg.max_nnz + cfg.hot_nnz
    batches = []
    for _ in range(count):
        keys = rng.integers(0, cfg.table_size, (cfg.batch_size, k))
        head = rng.integers(0, cfg.hot_size, (cfg.batch_size, k))
        keys = np.where(rng.random(keys.shape) < head_share, head, keys)
        mask = np.zeros((cfg.batch_size, k), np.float32)
        mask[:, :39] = 1.0
        batches.append(make_batch(
            keys.astype(np.int32),
            np.broadcast_to(np.arange(k, dtype=np.int32), keys.shape).copy(),
            np.ones(keys.shape, np.float32), mask,
            rng.integers(0, 2, cfg.batch_size).astype(np.float32),
            np.ones(cfg.batch_size, np.float32),
            cfg.hot_size, cfg.hot_nnz,
        ))
    return batches


def run_steps(cfg, devices: list, batches: list) -> tuple:
    """One TrainStep over a mesh of ``devices`` from a fresh state through
    ``batches``: (the hot implementation it chose, each step's logloss,
    every table's parameters on the host)."""
    import jax
    import numpy as np

    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    mesh = make_mesh(len(devices), devices=devices)
    model, opt = make_model(cfg), make_optimizer(cfg)
    step = TrainStep(model, opt, cfg, mesh)
    state = init_state(model, opt, cfg, mesh)
    lls = []
    for batch in batches:
        state, metrics = step.train(state, step.put_batch(batch))
        lls.append(float(jax.device_get(metrics["logloss"])))
    return step._hot_impl, lls, {
        name: np.asarray(jax.device_get(t["param"]))
        for name, t in state["tables"].items()
    }


def phase_parity(geom: dict, rehearsal: bool, out: dict) -> None:
    """(a) ops/hot.py's float32 promise, on the device: the MXU gather
    is bitwise ``w_hot[keys]``, the MXU scatter equals the segment-sum
    to summation order.  (b) two steps of one TrainStep on the device
    and on the CPU backend agree."""
    import jax
    import numpy as np

    from xflow_tpu.config import Config
    from xflow_tpu.ops.hot import hot_gather, hot_scatter

    rng = np.random.default_rng(SEED)
    h = 1 << geom["hot_size_log2"]
    m = 1 << (12 if rehearsal else 18)
    # N(0,1) float32 weights: a bfloat16 rounding of them moves nearly
    # every value, by up to 2^-9 relative.  D=1 is LR's row, D=10 the
    # FM/MVM row (the rehearsal saves the second set of CPU compiles).
    for d in (1,) if rehearsal else (1, 10):
        w = rng.normal(0, 1, (h, d)).astype(np.float32)
        keys = rng.integers(0, h, m).astype(np.int32)
        keys[::97] = h + 5  # out-of-range: zero row, nothing scattered
        g = rng.normal(0, 1, (m, d)).astype(np.float32)
        ref = np.where((keys < h)[:, None], w[np.clip(keys, 0, h - 1)], 0)
        ref = ref.astype(np.float32)
        # the scan, and what the step runs at this width ("auto": the
        # scan at D=1, the slice indexed a piece at a time at D=10)
        for impl in ("mxu", "auto"):
            got = np.asarray(jax.jit(
                lambda w, k, impl=impl: hot_gather(w, k, impl=impl)
            )(w, keys))
            check(
                bool((got.view(np.uint32) == ref.view(np.uint32)).all()),
                f"hot_gather({impl}, float32) D={d} is not bitwise "
                f"w_hot[keys]: max abs err {np.abs(got - ref).max()}",
            )
        mxu = np.asarray(jax.jit(
            lambda k, g: hot_scatter(k, g, h, impl="mxu")
        )(keys, g))
        seg = np.asarray(jax.jit(
            lambda k, g: hot_scatter(k, g, h, impl="seg")
        )(keys, g))
        rel = float(np.abs(mxu - seg).max() / np.abs(seg).max())
        check(
            rel < 1e-6,
            f"hot_scatter(mxu, float32) D={d} vs seg: relative {rel}",
        )
        out[f"scatter_rel_d{d}"] = float(f"{rel:.3g}")
    out["gather_bitwise"] = True

    cfg = Config(
        model="lr", optimizer="ftrl", table_size_log2=18, batch_size=4096,
        max_nnz=geom["max_nnz"], hot_size_log2=geom["hot_size_log2"],
        hot_nnz=geom["hot_nnz"], num_devices=1,
    )
    batches = synthetic_batches(cfg, rng, 2, head_share=0.6)
    runs = {}
    for name, dev in (("device", jax.devices()[0]),
                      ("cpu", jax.devices("cpu")[0])):
        if name == "cpu" and dev == jax.devices()[0]:
            runs["cpu"] = runs["device"]  # rehearsal: one and the same
            continue
        impl, lls, tables = run_steps(cfg, [dev], batches)
        runs[name] = (impl, lls, tables["w"])
    (impl, ll_d, w_d), (_, ll_c, w_c) = runs["device"], runs["cpu"]
    ll_err = max(abs(a - b) for a, b in zip(ll_d, ll_c))
    w_err = float(np.abs(w_d - w_c).max())
    check(np.abs(w_c).max() > 0, "reference step touched no row")
    check(ll_err < 1e-5, f"step logloss device vs cpu: {ll_d} vs {ll_c}")
    check(w_err < 1e-5, f"touched rows device vs cpu: max abs err {w_err}")
    out.update({
        "step_hot_impl": impl,
        "step_logloss_err": float(f"{ll_err:.3g}"),
        "step_rows_err": float(f"{w_err:.3g}"),
    })


def phase_serve(paths: dict, rehearsal: bool, out: dict) -> None:
    from xflow_tpu.obs.schema import load_jsonl
    from xflow_tpu.serve.__main__ import main as serve_main

    # the trainer's own predictions for the first rows of the test
    # split (evaluate() wrote "label\tpctr" per row, in file order)
    rows_path = os.path.join(WORK, "rows.ffm")
    with open(paths["test"] + "-00000") as f, open(rows_path, "w") as g:
        for _ in range(PARITY_ROWS):
            g.write(f.readline())
    with open(paths["pred"]) as f:
        want = [float(f.readline().split("\t")[1]) for _ in range(PARITY_ROWS)]
    # toy size: one bucket keeps the rehearsal's CPU compiles short
    buckets = ["--buckets", "8"] if rehearsal else []
    n_buckets = 1 if rehearsal else 4  # serve/engine.py DEFAULT_BUCKETS
    scored = os.path.join(WORK, "scored.txt")
    rc = serve_main([
        "score", paths["artifact"], "--input", rows_path, "--out", scored,
        *buckets,
    ])
    check(rc == 0, f"serve score returned {rc}")
    with open(scored) as f:
        got = [float(line) for line in f]
    check(len(got) == PARITY_ROWS, f"scored {len(got)} rows")
    # both sides print 6 decimals: equal to one unit of the last place
    err = max(abs(a - b) for a, b in zip(got, want))
    check(err < 1.5e-6, f"served pctr vs trainer predict: max err {err}")

    metrics = os.path.join(WORK, "serve.jsonl")
    # a CPU backend answers a toy row in tens of ms: offer it less
    qps = SERVE_QPS / 5 if rehearsal else SERVE_QPS
    rc = serve_main([
        "loadgen", paths["artifact"], "--replicas", "1",
        "--qps", str(qps), "--duration-s", str(SERVE_SECONDS),
        # every request must be answered: this is a check that serving
        # works, not of its latency, so admission control never sheds
        "--deadline-budget-ms", "10000", "--depth-budget", "100000",
        "--cache-capacity", "0", "--metrics-out", metrics, *buckets,
    ])
    check(rc == 0, f"serve loadgen returned {rc}")
    rows = load_jsonl(metrics)
    load, bench = rows_of(rows, "serve_load"), rows_of(rows, "serve_bench")
    check(len(load) == 1 and len(bench) == 1, "serve rows missing")
    load, bench = load[0], bench[0]
    check(len(load["buckets"]) == n_buckets, f"buckets {load['buckets']}")
    check(
        load["compiles"] == n_buckets,
        f"{load['compiles']} compiles after warm-up, {n_buckets} buckets",
    )
    check(
        bench["compiles"] == n_buckets,
        f"{bench['compiles']} compiles after traffic, {n_buckets} buckets",
    )
    check(bench["errors"] == 0, f"{bench['errors']} request errors")
    check(bench["shed_frac"] == 0, f"shed {bench['shed_by_cause']}")
    check(bench["outstanding"] == 0, "requests left unanswered")
    check(
        bench["requests"] >= 0.9 * qps * SERVE_SECONDS,
        f"only {bench['requests']} requests answered",
    )
    out.update({
        "parity_rows": PARITY_ROWS,
        "parity_max_err": float(f"{err:.3g}"),
        "requests": bench["requests"],
        "errors": bench["errors"],
        "buckets": load["buckets"],
        "compiles_after_warm": load["compiles"],
        "compiles_after_traffic": bench["compiles"],
    })


def phase_mesh(
    geom: dict, paths: dict, ll_one: list, meter: CompileMeter, out: dict
) -> None:
    """Phase 1's configuration for one epoch over four devices, really
    spread: row-sharded tables, batch split on its first axis, the same
    trajectory as one chip.  (A multi-device mesh rides the compact
    wire, not the dictionary wire — parallel/step.py dict_ok.)"""
    import jax

    from xflow_tpu import train

    n = 4
    if jax.local_device_count() < n:
        out["skipped"] = f"{jax.local_device_count()} device"
        print(f"chip_smoke: phase mesh skipped: {out['skipped']}",
              file=sys.stderr)
        return
    ckpt = os.path.join(WORK, "ckpt4")
    os.makedirs(ckpt)
    shutil.copy(paths["remap"], ckpt)
    probe = StepProbe(meter)
    with probe.installed():
        rc = train.main([
            *train_argv(geom, paths, n),
            "--epochs", "1", "--checkpoint-dir", ckpt, "--skip-eval",
        ])
    check(rc == 0, f"train.main --num-devices {n} returned {rc}")
    t_rows = 1 << geom["table_size_log2"]
    for tname, entry in probe.state["tables"].items():
        for aname, arr in entry.items():
            shards = arr.addressable_shards
            check(
                len({s.device for s in shards}) == n
                and all(s.data.shape[0] == t_rows // n for s in shards),
                f"table {tname}.{aname} not row-sharded over {n} devices: "
                f"{[(str(s.device), s.data.shape) for s in shards]}",
            )
    b = geom["batch_size"]
    split = 0
    for name, arr in probe.first_arrays.items():
        if arr.ndim and arr.shape[0] == b:
            shards = arr.addressable_shards
            check(
                len({s.device for s in shards}) == n
                and all(s.data.shape[0] == b // n for s in shards),
                f"batch plane {name} not split on its first axis",
            )
            split += 1
    check(split > 0, "no batch plane carries the batch axis")
    ll = probe.loglosses()
    check(
        abs(ll[0] - ll_one[0]) < 1e-5,
        f"first-step logloss {ll[0]} vs one chip {ll_one[0]}",
    )
    # same batches, same arithmetic up to summation order across shards
    band = max(abs(a - b) for a, b in zip(ll, ll_one))
    check(band < 1e-4, f"trajectory off one chip's by {band}: {ll}")
    out.update({
        "devices": n,
        "steps": len(ll),
        "wire": probe.step.wire_format,
        "first_logloss": round(ll[0], 6),
        "last_logloss": round(ll[-1], 6),
        "max_logloss_gap_vs_one_chip": float(f"{band:.3g}"),
        "rows_per_device": t_rows // n,
        "fm": mesh_fm_leg(geom, n),
    })


def mesh_fm_leg(geom: dict, n: int) -> dict:
    """Three steps of FM (v_dim=10: the D>1 rows of the exchange) on
    the n-device mesh against the same three on ONE device of the same
    backend: logloss and every row of both tables.  (Not against the
    CPU backend: over 10^7 entries one can sit on FTRL's "no gradient
    yet" branch on one backend and off it on the other — seen on the
    v5e, one entry, PR 27 — which says nothing about the mesh.)"""
    import jax
    import numpy as np

    from xflow_tpu.config import Config

    toy = geom["table_size_log2"] <= 16
    cfg = Config(
        model="fm", optimizer="ftrl", table_size_log2=14 if toy else 20,
        batch_size=1024 if toy else 16384, max_nnz=8,
        hot_size_log2=geom["hot_size_log2"], hot_nnz=32, seed=SEED,
    )
    batches = synthetic_batches(
        cfg, np.random.default_rng(SEED), 3, head_share=0.8
    )
    _, ll_m, rows_m = run_steps(cfg, jax.devices()[:n], batches)
    _, ll_1, rows_1 = run_steps(cfg, jax.devices()[:1], batches)
    ll_err = max(abs(a - b) for a, b in zip(ll_m, ll_1))
    check(ll_err < 1e-5, f"FM logloss mesh vs one device: {ll_m} vs {ll_1}")
    rows_err = 0.0
    for name, want in rows_1.items():
        err = float(np.abs(rows_m[name] - want).max() / np.abs(want).max())
        check(err < 1e-5, f"FM table {name} mesh vs one device: relative {err}")
        rows_err = max(rows_err, err)
    return {
        "steps": len(ll_m),
        "logloss_err": float(f"{ll_err:.3g}"),
        "rows_rel_err": float(f"{rows_err:.3g}"),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="toy size on whatever backend JAX finds: runs every "
        "phase's code, prints 'rehearsal', never 'ok'",
    )
    args = ap.parse_args(argv)
    geom = TOY if args.rehearsal else FLAGSHIP

    from xflow_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    t_start = time.perf_counter()
    device = phase_device(args.rehearsal)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    phases: dict = {}
    with meter.phase(phases, "build") as out:
        phase_build(out)
    with meter.phase(phases, "train") as out:
        paths = make_data(geom, out)
        ll_one = phase_train(
            geom, paths, device, args.rehearsal, meter, out
        )
    with meter.phase(phases, "parity") as out:
        phase_parity(geom, args.rehearsal, out)
    with meter.phase(phases, "serve") as out:
        phase_serve(paths, args.rehearsal, out)
    with meter.phase(phases, "mesh") as out:
        phase_mesh(geom, paths, ll_one, meter, out)
    shutil.rmtree(WORK, ignore_errors=True)
    report = {
        "geometry": geom,
        "compile_cache_dir": cache_dir,
        "seconds": round(time.perf_counter() - t_start, 2),
        "phases": phases,
    }
    verdict = {"rehearsal" if args.rehearsal else "ok": True, "device": device}
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

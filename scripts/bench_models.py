"""Per-model training-step throughput (docs/PERF.md model-zoo table).

Runs the fused train step for every model family at bench-scale shapes
and prints one JSON line per model:
    {"model": ..., "examples_per_sec": N, "batch_size": B, ...}

Usage:  python scripts/bench_models.py [--cpu] [--batch-log2 N]

Needs an accelerator and fails without one; ``--cpu`` asks for the CPU
explicitly (rows then say ``"backend": "cpu"``).  A model that raises
fails its child, and the parent exits non-zero after the last model.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")  # repo root

from bench import (  # noqa: E402
    build,
    make_batches,
    prepare_real_data,
    real_batches,
    run,
)


def model_cfgs(base_b: int, accel: bool):
    """(name, Config) per family, enumerated from the MODEL REGISTRY
    (models/__init__.py) — every registered family MUST have a bench
    geometry below, so a new family is throughput-tracked (and gated
    by check_bench_regress.py) from the day it registers, or this
    script fails loudly instead of silently skipping it.

    FM/MVM: v_dim=10 (ftrl.h:16).  FFM: per-field latent D=4.
    max_fields=39 everywhere — the bench data is Criteo-shaped with
    fgids 0..38 (gen_synth.FIELDS); a smaller cap would silently mask
    fields out of the field-aware models.  Sizes shrink under --cpu
    to keep runtime bounded.

    Hot geometries are the measured per-model optima (docs/PERF.md
    round-4 sweeps).  The wide-row models (FM/MVM, D=10) profit from a
    LARGER head than LR: their cold scatter costs ~106 ns/slice (any
    D>1 hits XLA's slow multi-lane scatter path, scripts/probe_fm2.py)
    vs ~15 ns for LR's scalars, so hiding more mass behind the MXU hot
    path is worth the extra one-hot traffic.

    FFM's table rows are max_fields*v_dim = 156 floats wide — at
    T=2^24 the (param, n, z) triple would be ~31 GB; its natural
    single-chip scale is T=2^21 (3.9 GB).  No hot table: h2*D = 9984
    lanes would force tiny scan chunks through ops/hot.py.

    two_tower/dcn (the cascade families, docs/SERVING.md): the same
    embedding-tower geometry as wide_deep (E=8 over 39 fields) so
    their rows read against its trajectory; two_tower splits the 39
    fields 20 user / 19 item."""
    from xflow_tpu.config import Config
    from xflow_tpu.models import model_names

    t = 24 if accel else 20
    b = base_b if accel else min(base_b, 16384)
    common = dict(
        optimizer="ftrl", table_size_log2=t, batch_size=b, num_devices=1,
        max_fields=39,
    )
    hot = dict(max_nnz=12, hot_size_log2=14, hot_nnz=32)
    geometries = {
        # flagship geometry (docs/PERF.md round-4 sweep)
        "lr": [
            ("lr", Config(model="lr", max_nnz=16, hot_size_log2=12,
                          hot_nnz=32, **common)),
            ("lr_nohot", Config(model="lr", max_nnz=40, **common)),
        ],
        "fm": [
            ("fm", Config(model="fm", v_dim=10, **hot, **common)),
            ("fm_nohot", Config(model="fm", max_nnz=40, v_dim=10,
                                **common)),
        ],
        "mvm": [
            ("mvm", Config(model="mvm", v_dim=10, **hot, **common)),
            ("mvm_nohot", Config(model="mvm", max_nnz=40, v_dim=10,
                                 **common)),
        ],
        # microbatch=4: FFM's [B/s, K, F*D] pair tensors are the live
        # memory; gradient accumulation runs full-size batches at 1/4
        # the intermediates (and measures FASTER than B=32768 whole)
        "ffm": [
            ("ffm", Config(model="ffm", max_nnz=40, ffm_v_dim=4,
                           microbatch=4,
                           **{**common,
                              "table_size_log2": 21 if accel else 18})),
        ],
        "wide_deep": [
            ("wide_deep", Config(model="wide_deep", emb_dim=8,
                                 hidden_dim=64, **hot, **common)),
            ("wide_deep_nohot", Config(model="wide_deep", max_nnz=40,
                                       emb_dim=8, hidden_dim=64,
                                       **common)),
        ],
        "two_tower": [
            ("two_tower", Config(model="two_tower", max_nnz=40, emb_dim=8,
                                 hidden_dim=64, tower_dim=16,
                                 tower_split_field=20, **common)),
        ],
        "dcn": [
            ("dcn", Config(model="dcn", max_nnz=40, emb_dim=8,
                           hidden_dim=64, cross_layers=2, **common)),
        ],
        # the same tower again under a small CIN (2 layers of 16 maps)
        "xdeepfm": [
            ("xdeepfm", Config(model="xdeepfm", max_nnz=40, emb_dim=8,
                               hidden_dim=64, cross_layers=2, cin_maps=16,
                               **common)),
        ],
    }
    missing = [n for n in model_names() if n not in geometries]
    if missing:
        raise SystemExit(
            f"bench_models: registered famil{'ies' if len(missing) > 1 else 'y'} "
            f"{missing} have no bench geometry — add one above so "
            "check_bench_regress.py tracks them from day one"
        )
    stale = [n for n in geometries if n not in model_names()]
    if stale:
        # the reverse direction: a geometry whose family was renamed
        # or removed must fail as loudly as a missing one, not rot as
        # silently-unbenched dead code
        raise SystemExit(
            f"bench_models: geometry entr{'ies' if len(stale) > 1 else 'y'} "
            f"{stale} match no registered family — rename or delete"
        )
    return [row for name in model_names() for row in geometries[name]]


def run_one(name: str, args) -> None:
    """Bench a single model in THIS process (child mode)."""
    import jax

    from xflow_tpu.utils.compile_cache import enable_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    devices = jax.devices()
    backend = devices[0].platform
    accel = backend != "cpu"
    if not accel and not args.cpu:
        raise SystemExit(
            "bench_models: JAX found no accelerator (platform 'cpu'); "
            "pass --cpu to ask for the CPU explicitly"
        )
    iters = args.iters if accel else max(2, args.iters // 3)

    cfg = dict(model_cfgs(1 << args.batch_log2, accel))[name]
    # geometry overrides for hot-head scaling sweeps (find each D>1
    # model's mass-vs-h2*D-traffic optimum)
    over = {}
    if args.hot_log2 is not None:
        over["hot_size_log2"] = args.hot_log2
    if args.hot_nnz is not None:
        over["hot_nnz"] = args.hot_nnz
    if args.cold_nnz is not None:
        over["max_nnz"] = args.cold_nnz
    if args.microbatch is not None:
        over["microbatch"] = args.microbatch
    if over:
        cfg = cfg.replace(**over)
    step, state = build(devices, cfg)
    if args.synthetic:
        source = "synthetic"
        batches, _ = make_batches(cfg, 2)
    else:
        source = "zipf-cache"
        _, csr, remap, _ = prepare_real_data(
            cfg, 2_000_000 if accel else 200_000
        )
        batches, _ = real_batches(
            cfg, csr, remap if cfg.hot_size else None, 2
        )
    t0 = time.time()
    _, eps = run(step, state, batches, iters=iters, warmup=2)
    print(json.dumps({
        "model": name,
        "examples_per_sec": round(eps, 1),
        "batch_size": cfg.batch_size,
        "table_size_log2": cfg.table_size_log2,
        "hot": f"2^{cfg.hot_size_log2}x{cfg.hot_nnz}+cold{cfg.max_nnz}"
        if cfg.hot_size else "off",
        "backend": backend,
        "device_kind": devices[0].device_kind,
        "batch_source": source,
        "wall_s": round(time.time() - t0, 1),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--batch-log2", type=int, default=16)  # 65536
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--synthetic", action="store_true",
        help="use synthetic batches instead of the zipf CSR cache",
    )
    ap.add_argument(
        "--model", default=None,
        help="bench ONE model inline (child mode); default: all models, "
        "each in its own subprocess",
    )
    ap.add_argument("--hot-log2", type=int, default=None,
                    help="override hot_size_log2 (0 = hot off)")
    ap.add_argument("--hot-nnz", type=int, default=None)
    ap.add_argument("--cold-nnz", type=int, default=None,
                    help="override max_nnz (cold capacity)")
    ap.add_argument("--microbatch", type=int, default=None)
    args = ap.parse_args()

    if args.model is not None:
        run_one(args.model, args)
        return

    if any(
        v is not None
        for v in (args.hot_log2, args.hot_nnz, args.cold_nnz,
                  args.microbatch)
    ):
        # geometry overrides are per-model sweep knobs; applied fleet-
        # wide they'd also rewrite the *_nohot control rows (making the
        # hot-vs-nohot comparison hot-vs-hot) and hand FFM a hot table
        # its 156-wide rows can't ride (model_cfgs docstring)
        ap.error("geometry overrides require --model (child mode)")

    # Parent mode: one subprocess per model.  Isolation matters — a
    # model whose tables cannot fit (or that trips an OOM) must not
    # poison the device heap/jit caches of the models after it, which
    # is exactly what happened when all models shared one process
    # (round-4 log: FFM's 31 GB table OOM'd, then wide_deep — fine in
    # isolation — reported RESOURCE_EXHAUSTED too).
    #
    # A chip belongs to one process at a time, so this parent must
    # never call jax.devices() or build an array: a parent that holds
    # the chip makes every child fail or hang.  Importing bench and
    # calling model_cfgs() initializes no backend — keep it so.
    import subprocess

    names = [n for n, _ in model_cfgs(1 << args.batch_log2, True)]
    passthrough = []
    if args.cpu:
        passthrough.append("--cpu")
    if args.synthetic:
        passthrough.append("--synthetic")
    passthrough += ["--batch-log2", str(args.batch_log2),
                    "--iters", str(args.iters)]
    failed = []
    for name in names:
        proc = subprocess.run(
            [sys.executable, __file__, "--model", name, *passthrough],
            stdout=subprocess.PIPE, text=True,
        )
        out = proc.stdout.strip()
        if out:
            print(out, flush=True)
        if proc.returncode != 0:
            failed.append(f"{name} (exit {proc.returncode})")
    if failed:
        raise SystemExit("bench_models: failed: " + ", ".join(failed))


if __name__ == "__main__":
    main()

"""Wall-clock-to-AUC: the north-star measurement (BASELINE.md "≥5×
wall-clock to convergence vs the CPU baseline").

Composes the two halves the repo previously measured separately:

* QUALITY — the proven B=512 FTRL convergence protocol
  (docs/CONVERGENCE.md: LR reaches test AUC 0.7401 in 6 epochs,
  1071 s on the 1-core CPU host).  Batch size is an optimizer
  hyperparameter under the reference's mean-over-batch gradients
  (lr_worker.cc:116-118), so the demo must keep the EFFECTIVE batch
  at 512.
* THROUGHPUT — device-rate dispatch.  update_mode="sequential"
  (parallel/step.py::_train_sequential) applies the optimizer once per
  512-example slice inside a scanned dispatch of `--batch-size`
  examples: B_eff stays 512 while the host dispatches B=131072.

The dataset is staged into device HBM ONCE as compact-wire planes
(~1.6 GB for 10 M examples at 40 keys/row — int32 keys + u8
labels/weights), so the training loop reads device-resident windows
instead of paying a host→device transfer every step.  The
clock starts BEFORE staging: uploads are enqueued as per-window async
transfers and epoch-0 compute overlaps the transfer stream, so
wall-to-target (secs_to_target_auc) pays the upload honestly without
serializing on it.  Compile time is reported separately AND added
into total_wall_secs / the headline speedup (a persistent XLA
compilation cache makes it ~1 s on repeat runs of a geometry).

Usage (full protocol, after gen_synth + binary conversion — see
scripts/convergence_baseline.py header for the dataset recipe):

    python scripts/time_to_auc.py                      # LR, 6 epochs
    python scripts/time_to_auc.py --platform cpu \
        --examples 200000 --test-examples 50000        # smoke test

Writes docs/artifacts/time_to_auc_<model>.json with per-epoch rows and
the wall-clock at which the target AUC was crossed.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRAIN = "/tmp/xflow_conv/bin.train"
TEST = "/tmp/xflow_conv/bin.test"
CPU_BASELINE = {  # docs/CONVERGENCE.md wall column (1-core CPU host)
    "lr": 1071.0,
    "fm": 1673.0,
    "mvm": 1719.0,
    "wide_deep": 1876.0,
}
TARGET_AUC = {  # each model's OWN final test AUC (docs/CONVERGENCE.md)
    "lr": 0.7401,
    "fm": 0.7530,
    "mvm": 0.7596,
    "wide_deep": 0.7414,
}


def stage_planes(trainer, path, cache_tag, limit=0):
    """Parse the shard(s) once through the production ShardLoader —
    using the TRAINER's loader so the hot remap (when on) is the one
    sampled from the training data, shared by both splits — into
    concatenated compact-wire planes, memoized to .npz beside the
    data."""
    from xflow_tpu.parallel.step import compact_wire_np
    from xflow_tpu.trainer import find_shards

    cache = f"{path}.{cache_tag}{'-n%d' % limit if limit else ''}.npz"
    if os.path.exists(cache):
        with np.load(cache) as z:
            return {k: z[k] for k in z.files}
    planes: dict[str, list] = {}
    seen = 0
    for shard in find_shards(path):
        for batch, _ in trainer._loader(shard).iter_batches():
            wire = compact_wire_np(
                batch,
                ship_slots=trainer.step._ship_slots,
                hot_u16=trainer.step._hot_u16,
            )
            for k, v in wire.items():
                planes.setdefault(k, []).append(v)
            seen += int(batch.weights.sum())
            if limit and seen >= limit:
                break
        if limit and seen >= limit:
            break
    out = {k: np.concatenate(v) for k, v in planes.items()}
    np.savez(cache, **out)
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="lr")
    p.add_argument("--train", default=TRAIN)
    p.add_argument("--test", default=TEST)
    p.add_argument(
        "--target-auc", type=float, default=None,
        help="default: the model's OWN docs/CONVERGENCE.md final AUC — "
        "the CPU baseline's wall time is to that target, so comparing "
        "against an easier one would inflate the speedup",
    )
    p.add_argument("--max-epochs", type=int, default=6)
    p.add_argument("--batch-size", type=int, default=131072,
                   help="dispatch window (examples per device call)")
    p.add_argument("--eff-batch", type=int, default=512,
                   help="effective optimizer batch (slice size)")
    p.add_argument("--table-size-log2", type=int, default=24)
    p.add_argument("--max-nnz", type=int, default=40)
    p.add_argument("--hot-size-log2", type=int, default=0)
    p.add_argument("--hot-nnz", type=int, default=32)
    p.add_argument(
        "--sequential-inner", default="dense",
        choices=["dense", "sparse", "hot"],
        help="sparse = touched-rows-only per slice (T=2^28 scale); "
        "hot = hot-fine/cold-coarse (needs --hot-size-log2)",
    )
    p.add_argument("--examples", type=int, default=0,
                   help="cap train examples (0 = all; smoke tests)")
    p.add_argument("--test-examples", type=int, default=0)
    p.add_argument("--platform", help="force JAX backend (cpu for smoke)")
    p.add_argument("--out", default="")
    p.add_argument(
        "--stage-only", action="store_true",
        help="build/refresh the .npz plane caches and exit (lets a CPU "
        "session pre-pay host prep so the TPU session starts hot)",
    )
    args = p.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax
    import jax.numpy as jnp

    # persistent XLA compilation cache: repeat runs of the same
    # geometry skip the trace+compile (reported separately either way,
    # so the artifact shows which case it was)
    from xflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from xflow_tpu.config import Config
    from xflow_tpu.trainer import Trainer
    from xflow_tpu.utils.metrics import AucAccumulator

    assert args.batch_size % args.eff_batch == 0
    cfg = Config(
        model=args.model,
        train_path=args.train,
        test_path=args.test,
        batch_size=args.batch_size,
        table_size_log2=args.table_size_log2,
        max_nnz=args.max_nnz,
        max_fields=39,
        num_devices=1,
        update_mode="sequential",
        sequential_inner=args.sequential_inner,
        microbatch=args.batch_size // args.eff_batch,
        hot_size_log2=args.hot_size_log2,
        hot_nnz=args.hot_nnz,
        # the remap (when hot is on) samples key frequencies from the
        # training data exactly as production does
        freq_sample_mib=64,
        checkpoint_dir="",
    )
    if args.target_auc is None:
        if args.model not in TARGET_AUC:
            p.error(f"--target-auc required for model {args.model!r}")
        args.target_auc = TARGET_AUC[args.model]
    trainer = Trainer(cfg, log=lambda s: print(s, file=sys.stderr))
    # the cache key carries everything that shapes the planes: table
    # size, hot geometry, cold capacity, batch padding, and whether a
    # slots plane is shipped (slot models on a slot-free cache would
    # silently train every feature in field 0)
    tag = "ttauc-t{}-h{}-hn{}-c{}-b{}-s{}{}".format(
        args.table_size_log2,
        args.hot_size_log2 if args.hot_size_log2 else 0,
        args.hot_nnz if args.hot_size_log2 else 0,
        args.max_nnz,
        args.batch_size,
        int(trainer.step._ship_slots),
        "-w2" if trainer.step._hot_u16 else "",
    )
    t_setup0 = time.time()
    train_planes = stage_planes(trainer, args.train, tag, args.examples)
    test_planes = stage_planes(trainer, args.test, tag, args.test_examples)
    host_prep_secs = time.time() - t_setup0
    if args.stage_only:
        print(
            json.dumps(
                {
                    "staged": True,
                    "n_train": len(train_planes["labels_u8"]),
                    "n_test": len(test_planes["labels_u8"]),
                    "host_prep_secs": round(host_prep_secs, 2),
                }
            )
        )
        return

    B = args.batch_size

    def pad_planes(planes, multiple):
        n = len(planes["labels_u8"])
        pad = (-n) % multiple
        if pad == 0:
            return planes, n
        out = {}
        for k, v in planes.items():
            if k.endswith("ckeys_u16"):
                fill_val = 0xFFFF  # the u16 pad sentinel
            elif k.endswith("ckeys"):
                fill_val = -1
            else:
                fill_val = 0
            fill = np.full((pad,) + v.shape[1:], fill_val, v.dtype)
            out[k] = np.concatenate([v, fill])
        # padding examples carry weight 0 -> no gradient, no metric
        return out, n

    train_planes, n_train = pad_planes(train_planes, B)
    test_planes, n_test = pad_planes(test_planes, B)
    n_padded = len(train_planes["labels_u8"])
    n_windows = n_padded // B
    bytes_staged = sum(
        v.nbytes for v in list(train_planes.values()) + list(test_planes.values())
    )

    step = trainer.step

    run_chunk = jax.jit(
        lambda state, window: step._train_impl(state, window),
        donate_argnums=0,
    )
    predict_chunk = jax.jit(
        lambda state, window: step._predict_impl(state, window)
    )

    def window_of(planes, i):
        return {k: v[i * B : (i + 1) * B] for k, v in planes.items()}

    def evaluate(state, test_dev):
        acc = AucAccumulator()
        for i, win in enumerate(test_dev):
            pctr = np.asarray(jax.device_get(predict_chunk(state, win)))
            sl = slice(i * B, (i + 1) * B)
            acc.add(
                test_planes["labels_u8"][sl].astype(np.float32),
                pctr,
                test_planes["weights_u8"][sl].astype(np.float32),
            )
        ll, auc = acc.compute()
        return ll, auc

    # compile on a zero-filled dummy window BEFORE any real data is
    # staged (one-time, reported separately; persistent-cache hits
    # make this ~1 s on repeat runs of the same geometry)
    t_c0 = time.time()
    dummy = {
        k: jnp.zeros((B,) + v.shape[1:], v.dtype)
        for k, v in train_planes.items()
    }
    state = trainer.state
    state, m = run_chunk(state, dummy)
    jax.device_get(m["logloss"])
    jax.device_get(predict_chunk(state, dummy)[:1])
    compile_secs = time.time() - t_c0
    # rebuild pristine state (the compile probe trained one window)
    from xflow_tpu.parallel.step import init_state

    state = init_state(trainer.model, trainer.optimizer, cfg, trainer.mesh)

    result = {
        "model": args.model,
        # v2 = overlapped staging inside the timed region, headline
        # speedup = baseline / (secs_to_target + compile); v1
        # artifacts (no accounting key) timed staging separately and
        # divided by total+stage+compile
        "accounting": "v2-overlapped-staging",
        "protocol": "docs/CONVERGENCE.md (B_eff=%d, ftrl.h:17-20 "
        "hyperparameters, T=2^%d)" % (args.eff_batch, args.table_size_log2),
        "backend": jax.devices()[0].platform,
        "batch_size": B,
        "eff_batch": args.eff_batch,
        "microbatch": cfg.microbatch,
        "sequential_inner": cfg.sequential_inner,
        "hot_size_log2": args.hot_size_log2,
        "n_train": n_train,
        "n_test": n_test,
        "host_prep_secs": round(host_prep_secs, 2),
        "bytes_staged": bytes_staged,
        "compile_secs": round(compile_secs, 2),
        "target_auc": args.target_auc,
        "cpu_baseline_secs": CPU_BASELINE.get(args.model),
        "epochs": [],
    }

    # The clock starts BEFORE device staging: wall-to-target pays the
    # full host→device upload honestly.  Uploads are enqueued as
    # per-window async transfers (jnp.asarray returns before the copy
    # lands), so epoch-0 compute overlaps the tail of the transfer
    # stream instead of waiting for all of it.  Staging is therefore
    # NOT a separable wall-clock term: upload_enqueue_secs is the host
    # dispatch cost alone; uploads_verified_by_wall_secs the wall
    # offset by which every transfer was VERIFIED landed (upper bound
    # — the check runs after epoch-0 compute).
    t0 = time.time()
    train_dev = [
        {k: jnp.asarray(v) for k, v in window_of(train_planes, i).items()}
        for i in range(n_windows)
    ]
    test_dev = [
        {k: jnp.asarray(v) for k, v in window_of(test_planes, i).items()}
        for i in range(len(test_planes["labels_u8"]) // B)
    ]
    result["upload_enqueue_secs"] = round(time.time() - t0, 2)
    reached = None
    for epoch in range(args.max_epochs):
        t_ep = time.time()
        ll_sum = cnt = 0.0
        metrics = []
        for win in train_dev:
            state, m = run_chunk(state, win)
            metrics.append(m)
        for m in jax.device_get(metrics):
            ll_sum += float(m["logloss"]) * float(m["count"])
            cnt += float(m["count"])
        train_secs = time.time() - t_ep
        if epoch == 0:
            # verify every transfer landed: transfers were enqueued
            # in order on one stream, but touch one element of every
            # test window rather than assume ordering.  UPPER BOUND:
            # checked after epoch-0 compute, so this records "landed
            # by here", not the landing instant.
            for w in test_dev:
                jax.device_get(w["labels_u8"][:1])
            result["uploads_verified_by_wall_secs"] = round(
                time.time() - t0, 2
            )
        ev_ll, ev_auc = evaluate(state, test_dev)
        wall = time.time() - t0
        row = {
            "epoch": epoch,
            "train_logloss": round(ll_sum / max(cnt, 1.0), 6),
            "test_logloss": round(ev_ll, 6),
            "test_auc": round(ev_auc, 6),
            "epoch_train_secs": round(train_secs, 2),
            "wall_secs": round(wall, 2),
            "examples_per_sec": round(cnt / max(train_secs, 1e-9), 0),
        }
        result["epochs"].append(row)
        print(json.dumps(row), flush=True)
        if reached is None and ev_auc >= args.target_auc:
            reached = wall
            result["secs_to_target_auc"] = round(wall, 2)
            break

    total = time.time() - t0
    # timed region = staging + train + eval (staging overlaps epoch 0
    # and is not separable); compile is added back for the headline
    result["stage_train_eval_wall_secs"] = round(total, 2)
    result["total_wall_secs"] = round(total + compile_secs, 2)
    if reached is not None and result["cpu_baseline_secs"]:
        result["speedup_vs_cpu_baseline"] = round(
            result["cpu_baseline_secs"] / (reached + compile_secs), 2
        )
        result["speedup_excl_compile"] = round(
            result["cpu_baseline_secs"] / reached, 2
        )
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs",
        "artifacts",
        f"time_to_auc_{args.model}.json",
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps({k: v for k, v in result.items() if k != "epochs"}))


if __name__ == "__main__":
    main()

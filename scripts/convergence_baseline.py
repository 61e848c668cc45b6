"""Convergence baseline (SURVEY §6: "a first task of the new repo"):
train any model family (lr / fm / mvm / ffm / wide_deep) to
convergence with the reference's exact FTRL hyperparameters
(/root/reference/src/optimizer/ftrl.h:17-20 — α=5e-2, β=1, λ1=5e-5,
λ2=10, v_dim=10) on a Criteo-shaped synthetic dataset with planted
logistic signal (scripts/gen_synth.py; real Criteo is not available in
this environment — documented proxy), and record per-epoch test
logloss/AUC curves against the generator's Bayes-optimal floor.

The recorded docs/CONVERGENCE.md rows used: `--models lr --epochs 6`,
`--models fm mvm --epochs 6`, `--models wide_deep --epochs 6`, and
`--models ffm --epochs 2` (FFM's CPU step is ~10× the others').

Dataset: 10M train / 1M test, 39 fields, zipf(1.2) ids, vocab 3.9M —
generate with:
    python scripts/gen_synth.py /tmp/xflow_conv/c10m 10000000 \
        --num-test 1000000 --train-shards 4
    python -m xflow_tpu.io.binary --train /tmp/xflow_conv/c10m.train \
        --out /tmp/xflow_conv/bin.train --block-mib 8   (and .test)

Run: python scripts/convergence_baseline.py [--models lr fm mvm]
Writes /tmp/xflow_conv/convergence.json and prints per-epoch JSON lines
— paste the summary into BASELINE.md.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

from xflow_tpu.config import Config
from xflow_tpu.trainer import Trainer

TRAIN = "/tmp/xflow_conv/bin.train"
TEST = "/tmp/xflow_conv/bin.test"
BAYES_LOGLOSS = 0.5106  # gen_synth.bayes_optimal_logloss(seed=7)
BAYES_AUC = 0.7883


def run_model(
    model: str, epochs: int, batch_size: int, table_size_log2: int = 24
) -> dict:
    cfg = Config(
        model=model,
        train_path=TRAIN,
        test_path=TEST,
        epochs=epochs,
        batch_size=batch_size,
        table_size_log2=table_size_log2,
        max_nnz=40,
        max_fields=39,
        num_devices=1,
        # Gradients are mean-over-batch (reference lr_worker.cc:116-118
        # parity), so the batch size IS an optimizer hyperparameter:
        # per-key updates scale as 1/B.  The reference's effective batch
        # is a per-thread slice of a 2 MiB block — a few hundred rows —
        # so convergence runs use a comparable small batch (measured:
        # B=8192 reaches AUC 0.53 where B=512 reaches 0.65 on the same
        # 500k examples).  Sparse update mode keeps small-batch steps
        # O(B*nnz) instead of O(table).
        update_mode="sparse",
        # optimizer defaults ARE the reference's ftrl.h:17-20 values
    )
    t = Trainer(cfg)
    curve = []
    for epoch in range(epochs):
        t.epoch = epoch
        stats = t.train_epoch()
        ev = t.evaluate()
        row = {
            "model": model,
            "epoch": epoch,
            "train_logloss": round(stats["train_logloss"], 6),
            "test_logloss": round(ev["logloss"], 6),
            "test_auc": round(ev["auc"], 6),
            "examples_per_sec": round(stats["examples_per_sec"], 0),
        }
        curve.append(row)
        print(json.dumps(row), flush=True)
    return {
        "model": model,
        "epochs": epochs,
        "batch_size": batch_size,
        "table_size_log2": table_size_log2,
        "final_test_logloss": curve[-1]["test_logloss"],
        "final_test_auc": curve[-1]["test_auc"],
        "curve": curve,
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--models", nargs="*", default=["lr", "fm", "mvm"])
    p.add_argument("--epochs", type=int, default=6)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument(
        "--table-size-log2", type=int, default=24,
        help="2^24 carries ~12%% occurrence collisions on this dataset, "
        "2^28 ~1%% (docs/PERF.md) — vary to quantify the collision cost "
        "the reference's exact-key store doesn't pay",
    )
    p.add_argument("--out", default="/tmp/xflow_conv/convergence.json")
    p.add_argument(
        "--platform",
        help="force the JAX backend, like JAX_PLATFORMS (e.g. cpu — "
        "convergence results are device-independent)",
    )
    args = p.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    results = {
        "dataset": "synthetic Criteo-shaped, 10M train / 1M test, "
        "39 fields, zipf(1.2), planted logistic signal (gen_synth "
        "seed=7)",
        "ftrl": "alpha=5e-2 beta=1 lambda1=5e-5 lambda2=10 (ftrl.h:17-20)",
        "bayes_optimal": {"logloss": BAYES_LOGLOSS, "auc": BAYES_AUC},
        "models": [],
    }
    for m in args.models:
        t0 = time.time()
        r = run_model(
            m, args.epochs, args.batch_size, args.table_size_log2
        )
        r["wall_secs"] = round(time.time() - t0, 1)
        results["models"].append(r)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    print(json.dumps({k: v for k, v in results.items() if k != "models"}))
    for r in results["models"]:
        print(
            json.dumps(
                {
                    "model": r["model"],
                    "final_test_logloss": r["final_test_logloss"],
                    "final_test_auc": r["final_test_auc"],
                    "wall_secs": r["wall_secs"],
                }
            )
        )


if __name__ == "__main__":
    main()

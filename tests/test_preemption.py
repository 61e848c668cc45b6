"""Graceful preemption: SIGTERM during training checkpoints (weights +
optimizer state + data cursor) and exits cleanly; --resume continues.

The reference's only recovery story is ``pkill -9`` and a full restart
(scripts/stop.sh:1, SURVEY §5 failure-detection row); this is the
capability gap filled.  Crash forensics (ISSUE 4) ride the same exit
paths: an exception or preemption mid-epoch must leave a fully-flushed
schema-valid metrics file AND a parseable flight dump naming the phase
that was active.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest


@pytest.fixture(scope="module")
def big_dataset(tmp_path_factory):
    from tests.gen_data import generate_dataset

    root = tmp_path_factory.mktemp("preempt")
    return generate_dataset(
        str(root),
        num_train_shards=2,
        lines_per_shard=2000,
        num_fields=10,
        vocab_per_field=32,
        seed=3,
    )


def test_sigterm_checkpoints_and_resume_completes(big_dataset, tmp_path):
    ck = tmp_path / "ck"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    cmd = [
        sys.executable, "-m", "xflow_tpu.train",
        "--model", "lr",
        "--train", big_dataset.train_prefix,
        "--test", big_dataset.test_prefix,
        "--epochs", "500",  # far more than fits before the signal
        "--batch-size", "64",
        "--table-size-log2", "14",
        "--max-nnz", "16",
        "--num-devices", "1",
        "--checkpoint-dir", str(ck),
        "--checkpoint-every-steps", "5",
    ]
    proc = subprocess.Popen(
        cmd, env=env, stderr=subprocess.PIPE, text=True, cwd=os.getcwd()
    )
    # wait until training demonstrably progresses (first checkpoint lands)
    deadline = time.time() + 180
    while time.time() < deadline and not (ck / "LATEST").exists():
        if proc.poll() is not None:
            pytest.fail(f"trainer exited early: {proc.communicate()[1]}")
        time.sleep(0.5)
    assert (ck / "LATEST").exists(), "no checkpoint appeared within deadline"

    proc.send_signal(signal.SIGTERM)
    try:
        _, err = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        pytest.fail("trainer did not exit after SIGTERM")
    assert proc.returncode == 0, err
    assert "preempted: checkpoint saved" in err

    # resume: must pick up the cursor and run to completion (small epoch
    # count now) without error
    resume_cmd = [c for c in cmd]
    resume_cmd[resume_cmd.index("--epochs") + 1] = "1"
    resume_cmd.append("--resume")
    out = subprocess.run(
        resume_cmd, env=env, stderr=subprocess.PIPE, text=True,
        cwd=os.getcwd(), timeout=300,
    )
    assert out.returncode == 0, out.stderr
    assert "resumed at" in out.stderr
    assert "auc" in out.stderr  # evaluation ran after completed training


def test_midepoch_crash_flushes_metrics_and_flight_dump(
    big_dataset, tmp_path, monkeypatch
):
    """ISSUE 4 satellite: an exception raised mid-epoch still yields
    (a) a schema-valid, fully-flushed metrics file — including the
    flight_dump pointer row — and (b) a parseable flight dump naming
    the phase that was active when the run died."""
    from xflow_tpu.config import Config
    from xflow_tpu.obs.flight import load_dump
    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.trainer import Trainer

    out = tmp_path / "m.jsonl"
    flight = tmp_path / "flight.json"
    cfg = Config(
        train_path=big_dataset.train_prefix,
        model="lr",
        epochs=3,
        batch_size=64,
        table_size_log2=14,
        max_nnz=16,
        num_devices=1,
        metrics_out=str(out),
        obs_flight_out=str(flight),
    )
    orig = Trainer.iter_train_batches

    def dies_midway(self, *a, **kw):
        for i, item in enumerate(orig(self, *a, **kw)):
            if i == 3:
                raise RuntimeError("shard went away mid-epoch")
            yield item

    monkeypatch.setattr(Trainer, "iter_train_batches", dies_midway)
    t = Trainer(cfg)
    with pytest.raises(RuntimeError, match="mid-epoch"):
        t.train()
    # (a) the metrics file is flushed, closed, and schema-valid
    assert t.metrics_logger.closed
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert validate_rows(rows) == []
    dump_rows = [r for r in rows if r["kind"] == "flight_dump"]
    assert len(dump_rows) == 1
    assert dump_rows[0]["reason"] == "exception"
    assert dump_rows[0]["path"] == str(flight)
    # (b) the flight dump parses and names the active phase (the crash
    # surfaced while the loop was pulling from the input iterator)
    doc = load_dump(str(flight))
    assert doc["reason"] == "exception"
    assert doc["active_phase"] == "input_stall"
    assert dump_rows[0]["active_phase"] == "input_stall"
    assert doc["exception"]["type"] == "RuntimeError"
    assert "mid-epoch" in doc["exception"]["message"]
    assert doc["record"]["last_batch"] is not None  # batches were in flight
    assert any(t_["stack"] for t_ in doc["threads"])
    # a second close() must not write a second dump row
    t.close()
    rows2 = [json.loads(l) for l in out.read_text().splitlines()]
    assert rows2 == rows


def test_preemption_mid_checkpoint_resume_auto_roundtrip(
    big_dataset, tmp_path
):
    """ISSUE 11 satellite: a run killed MID-CHECKPOINT (the
    ckpt.finalize failpoint fires between manifest write and rename —
    the worst preemption moment) leaves the previous complete
    generation restorable, and `--resume auto` picks it and runs to
    completion with a schema-valid metrics stream."""
    from xflow_tpu import chaos
    from xflow_tpu.config import Config
    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.trainer import Trainer
    from xflow_tpu.utils.checkpoint import latest_complete

    ck = tmp_path / "ck"
    metrics = tmp_path / "m.jsonl"
    cfg = Config(
        train_path=big_dataset.train_prefix,
        model="lr",
        epochs=1,
        batch_size=64,
        table_size_log2=14,
        max_nnz=16,
        num_devices=1,
        checkpoint_dir=str(ck),
        checkpoint_every_steps=5,
        metrics_out=str(metrics),
    )
    # the 3rd mid-epoch save dies mid-commit: two complete generations
    # exist by then, so the fallback has something to restore
    chaos.arm("ckpt.finalize:nth=3")
    t1 = Trainer(cfg)
    try:
        with pytest.raises(chaos.ChaosError):
            t1.train()
    finally:
        t1.close()
        chaos.disarm()
    survivor = latest_complete(str(ck))
    assert survivor is not None

    t2 = Trainer(cfg)
    try:
        cursor = t2.restore(auto=True)
        assert cursor is not None
        # mid-shard cursor: the save recorded a real resume offset
        assert {"shard", "offset"} <= set(cursor["cursors"][0])
        history = t2.train()
        assert history and not history[-1].get("preempted")
    finally:
        t2.close()
    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert validate_rows(rows) == []
    causes = [r["cause"] for r in rows if r["kind"] == "health"]
    assert "checkpoint_save_failed" in causes
    assert [r["site"] for r in rows if r["kind"] == "chaos"] == [
        "ckpt.finalize"
    ]

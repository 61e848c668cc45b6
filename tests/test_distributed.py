"""True multi-process distributed training on one machine.

The reference proves its whole distributed topology (scheduler + servers
+ workers) as plain local processes (scripts/local.sh, SURVEY §4 item
2).  The equivalent here: two OS processes, `jax.distributed.initialize`
over a localhost coordinator, gloo CPU collectives, each host reading
its own shard subset — the exact `scripts/run_dist.sh` path.

Three train shards across two hosts makes the split UNEQUAL (host 0
gets shards 0 and 2, host 1 gets shard 1), exercising the SPMD
step-count agreement (`Trainer._synced_batches`): host 1 must feed
zero-weight padding batches while host 0 finishes its second shard, or
the pjit collectives deadlock.
"""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch_pair(cmd, env, timeout=600, fail_msg="distributed run deadlocked"):
    """Spawn both ranks of a 2-process job, wait with a deadlock
    timeout (kill all on expiry), return (procs, stderr_texts)."""
    procs = [
        subprocess.Popen(
            cmd + ["--process-id", str(pid)],
            env=env, stderr=subprocess.PIPE, text=True, cwd=os.getcwd(),
        )
        for pid in range(2)
    ]
    errs = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(fail_msg)
        errs.append(err)
    return procs, errs


@pytest.mark.parametrize("hot", [False, "dense", "hot"])
def test_two_process_training(toy_dataset, tmp_path, hot):
    port = _free_port()
    env_base = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    cmd = [
        sys.executable, "-m", "xflow_tpu.train",
        "--model", "lr",
        "--train", toy_dataset.train_prefix,  # 3 shards -> unequal split
        "--test", toy_dataset.test_prefix,
        "--epochs", "3",
        "--batch-size", "64",
        "--table-size-log2", "14",
        "--max-nnz", "24",
        "--num-devices", "2",
        "--platform", "cpu",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2",
    ]
    if hot:
        # compose the hot-table MXU path AND the sequential per-slice
        # update scan with real 2-process collectives — with the dense
        # inner and with the hot-fine/cold-coarse inner (scan-carried
        # head + window-end writeback under GSPMD) (the accumulate
        # scan's sharding is covered by
        # test_dense_sharded_matches_single on the 8-device mesh)
        cmd += ["--hot-size-log2", "8", "--hot-nnz", "8",
                "--freq-sample-mib", "1", "--microbatch", "2",
                "--update-mode", "sequential",
                "--sequential-inner", hot]
    else:
        # cover the multi-host checkpoint path (collective allgather
        # save, rank-0 writes) in one of the parametrizations
        cmd += ["--checkpoint-dir", str(tmp_path / "ck")]

    def run_pair(extra):
        return _launch_pair(
            cmd + extra, env_base,
            fail_msg="distributed training deadlocked (collective mismatch?)",
        )

    procs, errs = run_pair([])
    assert procs[0].returncode == 0, errs[0]
    assert procs[1].returncode == 0, errs[1]
    # rank-0 reports the global eval (allgathered across hosts)
    assert "auc" in errs[0]
    # all 200 test examples counted exactly once despite padding batches
    assert "tp = " in errs[0]

    if not hot:
        assert (tmp_path / "ck" / "LATEST").exists()
        # multi-host restore: sharded tables rebuilt from the rank-0 files
        procs, errs = run_pair(["--resume"])
        assert procs[0].returncode == 0, errs[0]
        assert procs[1].returncode == 0, errs[1]
        assert "resumed at" in errs[0]


def test_two_process_training_packed_shards(toy_dataset, tmp_path):
    """Multi-host training over PACKED-cache shards (io/packed.py): the
    format sniffing, geometry validation, and per-host shard walk must
    compose with the SPMD step-count voting exactly like text shards
    (3 packed shards over 2 hosts = unequal split)."""
    from xflow_tpu.io import packed

    out = str(tmp_path / "pk")
    for i in range(3):
        packed.convert_shard(
            toy_dataset.train_prefix + f"-{i:05d}",
            f"{out}-{i:05d}",
            batch_size=64,
            max_nnz=24,
            table_size=1 << 14,
        )
    port = _free_port()
    env_base = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    cmd = [
        sys.executable, "-m", "xflow_tpu.train",
        "--model", "lr",
        "--train", out,
        "--test", toy_dataset.test_prefix,
        "--epochs", "3",
        "--batch-size", "64",
        "--table-size-log2", "14",
        "--max-nnz", "24",
        "--num-devices", "2",
        "--platform", "cpu",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2",
    ]
    procs, errs = _launch_pair(
        cmd, env_base,
        fail_msg="packed-shard distributed training deadlocked",
    )
    assert procs[0].returncode == 0, errs[0]
    assert procs[1].returncode == 0, errs[1]
    assert "auc" in errs[0]
    assert "tp = " in errs[0]


def test_two_process_ckpt_mkdir_failure_raises_not_hangs(toy_dataset, tmp_path):
    """Round-2 advisor finding: an exception on process 0 BEFORE the
    post-mkdir synchronization point (e.g. os.makedirs failing) used to
    send process 0 into _all_ok's allgather while process 1 sat in a
    bare sync_global_devices — mismatched collectives, multi-host hang.
    With the mkdir outcome itself voted through _all_ok, both processes
    must now exit nonzero promptly instead of deadlocking."""
    port = _free_port()
    env_base = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    blocker = tmp_path / "blocker"
    blocker.write_text("regular file: makedirs(blocker/ck) must fail")
    cmd = [
        sys.executable, "-m", "xflow_tpu.train",
        "--model", "lr",
        "--train", toy_dataset.train_prefix,
        "--test", toy_dataset.test_prefix,
        "--epochs", "1",
        "--batch-size", "64",
        "--table-size-log2", "14",
        "--max-nnz", "24",
        "--num-devices", "2",
        "--platform", "cpu",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2",
        "--checkpoint-dir", str(blocker / "ck"),
        "--skip-eval",
    ]
    procs, errs = _launch_pair(
        cmd, env_base, timeout=300,
        fail_msg="checkpoint mkdir failure deadlocked the job (pre-barrier "
        "exception not voted through _all_ok?)",
    )
    assert procs[0].returncode != 0, "process 0 should fail on mkdir"
    assert procs[1].returncode != 0, "process 1 should learn of the failure"
    assert "NotADirectoryError" in errs[0] or "FileExistsError" in errs[0]
    assert "checkpoint mkdir failed on process 0" in errs[1]


def test_two_process_midepoch_cursor_resume(toy_dataset, tmp_path):
    """Mid-epoch checkpoints record EVERY host's (shard, offset) cursor
    and each host resumes from its own — the round-1 advisor finding:
    rank 0's byte offset must not be applied to other hosts' different
    shard subsets."""
    import json

    port = _free_port()
    env_base = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    ck = tmp_path / "ck"
    cmd = [
        sys.executable, "-m", "xflow_tpu.train",
        "--model", "lr",
        "--train", toy_dataset.train_prefix,  # 3 shards -> unequal split
        "--test", toy_dataset.test_prefix,
        "--epochs", "1",
        "--batch-size", "32",
        "--block-mib", "1",
        "--table-size-log2", "14",
        "--max-nnz", "24",
        "--num-devices", "2",
        "--platform", "cpu",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2",
        "--checkpoint-dir", str(ck),
        "--checkpoint-every-steps", "2",
        "--skip-eval",
    ]

    def run_pair(extra, port):
        cmd2 = list(cmd)
        cmd2[cmd2.index("--coordinator") + 1] = f"localhost:{port}"
        procs, errs = _launch_pair(cmd2 + extra, env_base)
        assert procs[0].returncode == 0, errs[0]
        assert procs[1].returncode == 0, errs[1]
        return errs

    run_pair([], port)
    # every checkpoint (intermediate + final) carries both hosts' cursors
    import glob as _glob

    ckpts = sorted(_glob.glob(str(ck / "ckpt-*")))
    assert len(ckpts) >= 2  # at least one mid-epoch + the final
    manifests = [
        json.load(open(os.path.join(c, "manifest.json"))) for c in ckpts
    ]
    for m in manifests:
        assert m["cursor"]["num_hosts"] == 2
        assert len(m["cursor"]["cursors"]) == 2
    # host 0 owns shards {0,2}, host 1 owns {1}: once host 0 crosses into
    # its second local shard (or host 1 finishes first), the two hosts'
    # cursors MUST diverge in some mid-epoch checkpoint — rank 0's cursor
    # alone could not describe both (the round-1 advisor bug)
    assert any(
        m["cursor"]["cursors"][0] != m["cursor"]["cursors"][1]
        for m in manifests[:-1]
    )

    # resume from the mid-epoch checkpoint: point LATEST at it
    with open(ck / "LATEST", "w") as f:
        f.write(os.path.basename(ckpts[0]))
    errs = run_pair(["--resume"], _free_port())
    assert "resumed at" in errs[0]

"""Test harness: emulate an 8-device pod on CPU.

The reference proves its whole distributed topology as plain processes
on one host (scripts/local.sh, SURVEY §4 item 2); the JAX equivalent is
8 virtual CPU devices via XLA_FLAGS, which every sharding test uses.
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

# -- multiprocess-CPU capability probe ------------------------------------
#
# tests/test_distributed.py needs REAL 2-process collectives on the CPU
# backend (gloo).  Some images ship a jaxlib whose CPU client cannot do
# cross-process computations at all ("Multiprocess computations aren't
# implemented on the CPU backend") — there the 6 distributed tests can
# never pass, and failing every tier-1 run teaches people to ignore
# red.  Probe the capability ONCE per session (two short-lived
# subprocesses running one allgather) and skip-mark the distributed
# tests with the probe's reason when it is absent.

_MP_CPU_PROBE: tuple[bool, str] | None = None


def _multiprocess_cpu_capable(timeout: float = 180.0) -> tuple[bool, str]:
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import jax\n"
        "jax.distributed.initialize("
        f"coordinator_address='localhost:{port}', "
        "num_processes=2, process_id=int(sys.argv[1]))\n"
        "from jax.experimental import multihost_utils\n"
        "out = multihost_utils.process_allgather(np.int32(1))\n"
        "assert int(np.asarray(out).sum()) == 2\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, str(i)],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(2)
    ]
    errs: list[str] = []
    for p in procs:
        try:
            _, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            for q in procs:
                q.communicate()
            return False, "2-process CPU collective probe timed out"
        errs.append(err or "")
    if all(p.returncode == 0 for p in procs):
        return True, ""
    tail = next(
        (
            line.strip()
            for e in errs
            for line in reversed(e.strip().splitlines())
            if line.strip()
        ),
        "unknown failure",
    )
    return False, f"2-process CPU collectives unavailable: {tail[:160]}"


def pytest_collection_modifyitems(config, items):
    dist = [
        item
        for item in items
        if os.path.basename(str(item.fspath)) == "test_distributed.py"
    ]
    if not dist:
        return
    global _MP_CPU_PROBE
    if _MP_CPU_PROBE is None:
        _MP_CPU_PROBE = _multiprocess_cpu_capable()
    capable, reason = _MP_CPU_PROBE
    if capable:
        return
    marker = pytest.mark.skip(
        reason=f"multiprocess-CPU environment limitation: {reason}"
    )
    for item in dist:
        item.add_marker(marker)


@pytest.fixture(scope="session")
def toy_dataset(tmp_path_factory):
    """Synthetic libffm dataset with learnable structure, regenerating the
    shape of the reference's bundled toy data (SURVEY §2 #19: shards
    ``prefix-%05d``, ~18 fields/sample, fid < 10^4, ``label\\tfgid:fid:val``
    lines)."""
    from tests.gen_data import generate_dataset

    root = tmp_path_factory.mktemp("toy")
    return generate_dataset(
        str(root),
        num_train_shards=3,
        lines_per_shard=200,
        num_fields=10,
        vocab_per_field=8,
        seed=7,
        scale=3.0,
    )

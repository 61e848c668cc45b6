"""From a cell's name to a ``Ctx``: what ``run.py`` and ``knee_sweep.py`` do
before they call a driver."""

from __future__ import annotations

import os
import shutil
import sys
import time

from benchmarks.harness import compiles, device, manifest
from benchmarks.harness.context import Ctx


def context(
    doc: dict, workload: str, *, seed: int, seconds: float, trace: bool,
    rehearsal: bool, t0: float, work_name: str | None = None, **extra,
) -> Ctx:
    """Resolve the cell's files, turn the compile cache on, find (or refuse)
    the device and make the run's work directory.  ``t0`` is the clock
    reading log lines count from."""
    from xflow_tpu.utils.compile_cache import enable_compile_cache

    cell = manifest.cell(doc, workload)
    config_doc = manifest.apply_rehearsal(
        manifest.config(doc, cell["config"]), rehearsal
    )
    traffic = manifest.apply_rehearsal(manifest.traffic(cell["traffic"]), rehearsal)
    cache_dir = enable_compile_cache()
    meter = compiles.CompileMeter()
    dev = device.require(cell["chips"], rehearsal)
    work = os.path.join(manifest.ROOT, ".bench_cache", work_name or workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)

    def log(msg: str) -> None:
        print(f"[{time.perf_counter() - t0:7.1f}s] {msg}", file=sys.stderr, flush=True)

    log(f"{workload}: {dev}, compile cache {cache_dir}")
    return Ctx(
        workload=workload, cell=cell, config_doc=config_doc,
        fields={
            k: v for k, v in config_doc.items() if k not in manifest.CONFIG_META
        },
        traffic=traffic, seed=seed, seconds=seconds, trace=trace,
        rehearsal=rehearsal, work=work, device=dev,
        peaks=None if rehearsal else device.peaks(dev["kind"]),
        meter=meter, log=log, **extra,
    )

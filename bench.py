"""Benchmark harness: steady-state LR+FTRL training throughput.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "examples/sec",
     "vs_baseline": N, "backend": ..., ...}

Needs an accelerator: without one it fails (exit != 0, no JSON line),
and so does any leg that raises.  ``XFLOW_BENCH_CPU=1`` asks for the CPU
mode explicitly; its line says ``"backend": "cpu"`` and carries the CPU
rate under a CPU metric name, never under the device metric's.

Baseline: the reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is measured against a CPU proxy — the same sparse
LR+FTRL step compiled for this host's CPU backend, standing in for the
reference's CPU-cluster workers.  value = accelerator examples/sec,
vs_baseline = accelerator/CPU-host throughput ratio.

Shapes model Criteo-style CTR: 39 features/sample, batch 131072
(throughput saturates there on v5e), 2^24-row hashed table.  The step is
slice-count-bound: XLA TPU gather/scatter costs ~8-10ns per slice
regardless of slice width or table size (measured on v5e), so B*nnz
slices set the floor; see docs/PERF.md for the measurement log.

Secondary metrics in the same JSON line:
  - ``hot_truncated_frac``: measured fraction of real feature entries
    dropped by hot/cold steering at the flagship config (claimed <0.5%).
  - ``e2e_examples_per_sec`` / ``parse_mb_per_sec``: end-to-end
    text->parse->pack->device->train throughput over a generated zipf
    libffm dataset, exercising the real ShardLoader + native parser
    (the reference's whole bottleneck was host IO — SURVEY §7c).
  - ``input_stall_frac`` / ``e2e_phase_seconds``: per-phase attribution
    of the e2e loop (input stall vs h2d vs dispatch vs device block) —
    the same accounting the trainer emits per epoch (xflow_tpu/obs,
    docs/OBSERVABILITY.md), so a low e2e number names its bottleneck.
  - ``e2e_packed_examples_per_sec`` / ``packed_read_examples_per_sec``:
    the steady-state path — text parsed ONCE into the packed-batch
    cache (io/packed.py), epochs 2..N stream device-ready batches over
    the compact wire (Config.wire_mode) with transfer-ahead.  The
    read rate is the host-side feed capacity.
"""

from __future__ import annotations

import glob
import json
import os
import time

import numpy as np

def build(platform_devices, cfg):
    from xflow_tpu.models import make_model
    from xflow_tpu.optim import make_optimizer
    from xflow_tpu.parallel.mesh import make_mesh
    from xflow_tpu.parallel.step import TrainStep, init_state

    mesh = make_mesh(1, devices=platform_devices[:1])
    model = make_model(cfg)
    opt = make_optimizer(cfg)
    step = TrainStep(model, opt, cfg, mesh)
    state = init_state(model, opt, cfg, mesh)
    return step, state


def make_batches(cfg, num, seed=0):
    """Synthetic device batches + the measured hot-truncation fraction."""
    from xflow_tpu.io.batch import make_batch

    rng = np.random.default_rng(seed)
    b = cfg.batch_size
    k = cfg.max_nnz + (cfg.hot_nnz if cfg.hot_size else 0)
    batches = []
    entries_in = 0
    entries_kept = 0
    for _ in range(num):
        # ~39 real features/sample, Criteo-style; zipf-ish key reuse (30%
        # of occurrences drawn from a 1000-key head) so consolidation and
        # the hot table see realistic duplicate densities
        nnz = 39
        mask = np.zeros((b, k), np.float32)
        mask[:, :nnz] = 1.0
        keys = rng.integers(0, cfg.table_size, (b, k)).astype(np.int32)
        head = rng.integers(0, 1000, (b, k)).astype(np.int32)
        use_head = rng.random((b, k)) < 0.3
        keys = np.where(use_head, head, keys)
        slots = np.broadcast_to(np.arange(k, dtype=np.int32), (b, k)).copy()
        vals = np.ones((b, k), np.float32)
        labels = rng.integers(0, 2, b).astype(np.float32)
        weights = np.ones(b, np.float32)
        # head keys already live in [0, 1000) ⊂ [0, hot_size) — the
        # identity remap is what io/freq.py would compute here
        batch = make_batch(
            keys, slots, vals, mask, labels, weights,
            cfg.hot_size, cfg.hot_nnz,
        )
        entries_in += int(mask.sum())
        entries_kept += int(batch.mask.sum() + batch.hot_mask.sum())
        batches.append(batch)
    truncated_frac = (entries_in - entries_kept) / max(entries_in, 1)
    return batches, truncated_frac


def prepare_real_data(cfg, n_examples: int):
    """Shared real-data setup: zipf text shard (cached), CSR binary
    cache (cached), frequency counts + hot remap at cfg's geometry.
    Returns (data_path, csr_path, remap, hot_mass|None)."""
    from xflow_tpu.io import binary, freq

    data_path = ensure_synth_data(
        os.path.join(
            os.environ.get("XFLOW_BENCH_CACHE", "/tmp/xflow_bench"),
            f"zipf-{n_examples}.ffm",
        ),
        n_examples,
    )
    csr = data_path + ".xfbc"
    if not os.path.exists(csr):
        binary.convert_shard(data_path, csr, block_mib=8)
    remap = None
    mass = None
    if cfg.hot_size:
        counts = cached_counts(csr, cfg.table_size_log2)
        remap = freq.build_remap(counts, cfg.hot_size)
        mass = freq.hot_mass(counts, remap, cfg.hot_size)
    return data_path, csr, remap, mass


def cached_counts(csr: str, table_size_log2: int):
    """Key-frequency counts over the CSR cache, memoized on disk —
    bench_models.py runs each model in a fresh subprocess and the
    counting pass (~1 min on a 1-core host) must not repeat per model."""
    from xflow_tpu.io import freq

    cache = f"{csr}.counts-t{table_size_log2}.npy"
    # stale if the CSR cache was regenerated after the counts were taken
    if os.path.exists(cache) and (
        os.path.getmtime(cache) >= os.path.getmtime(csr)
    ):
        return np.load(cache)
    counts = freq.count_keys([csr], None, 1 << table_size_log2, 64 << 20)
    tmp = f"{cache}.tmp.{os.getpid()}.npy"
    np.save(tmp, counts)
    os.replace(tmp, cache)
    return counts


def real_batches(cfg, csr_path: str, remap, num: int):
    """Production-loader batches off the CSR cache — the device bench
    measures the step on REAL zipf-distributed keys (synthetic uniform
    keys understate hot-table coverage; the measured head mass is
    ~0.71-0.85, not the old synthetic 30%)."""
    from xflow_tpu.io.loader import ShardLoader

    loader = ShardLoader(
        csr_path,
        batch_size=cfg.batch_size,
        max_nnz=cfg.max_nnz,
        table_size=cfg.table_size,
        hash_seed=cfg.seed,
        remap=remap,
        hot_size=cfg.hot_size,
        hot_nnz=cfg.hot_nnz if cfg.hot_size else 0,
    )
    batches = []
    kept = 0.0
    real = 0
    for batch, _ in loader.iter_batches():
        if batch.num_real() < cfg.batch_size:
            break  # partial tail batch would inflate run()'s eps
        kept += float(batch.mask.sum() + batch.hot_mask.sum())
        real += batch.num_real()
        batches.append(batch)
        if len(batches) == num:
            break
    if len(batches) < num:
        raise ValueError(
            f"{csr_path}: only {len(batches)} full batches of "
            f"{cfg.batch_size} available, need {num}"
        )
    truncated = 1.0 - kept / (real * 39.0)  # generator: 39 features/row
    return batches, truncated


def run(step, state, batches, iters, warmup=3):
    import jax

    device_batches = [step.put_batch(b) for b in batches]

    def sync(st):
        # device_get of one element waits for the whole chain
        first = next(iter(st["tables"].values()))
        jax.device_get(first["param"][:1, 0])

    for i in range(warmup):
        state, m = step.train(state, device_batches[i % len(device_batches)])
    sync(state)
    t0 = time.perf_counter()
    for i in range(iters):
        state, m = step.train(state, device_batches[i % len(device_batches)])
    sync(state)
    dt = time.perf_counter() - t0
    return state, iters * batches[0].batch_size / dt


def bench_e2e(devices, cfg, data_path: str, result: dict, remap=None) -> None:
    """End-to-end: text shard -> BlockReader -> (native) parser -> pack ->
    put_batch -> fused train step, via the production ShardLoader
    prefetch path.  Fills e2e_* fields of ``result`` in place.
    ``remap`` (from prepare_real_data at the same cfg) skips the
    frequency-count setup when the caller already has one."""
    import jax

    from xflow_tpu.io.loader import ShardLoader, make_parse_fn
    from xflow_tpu.native import available as native_available

    step, state = build(devices, cfg)
    parse_fn = make_parse_fn(cfg.table_size, True, cfg.seed)
    if remap is not None and len(remap) != cfg.table_size:
        remap = None  # caller's remap was built for a different table
    if cfg.hot_size and remap is None:
        # production hot-table path: measure key frequencies on a sample
        # and permute the head into rows [0, H) (io/freq.py), exactly as
        # trainer._init_remap does; setup cost is outside the timed loop
        # (one-time, like compilation)
        from xflow_tpu.io import freq

        counts = freq.count_keys(
            [data_path], parse_fn, cfg.table_size, 32 << 20, 8 << 20
        )
        remap = freq.build_remap(counts, cfg.hot_size)
        result["hot_mass"] = round(
            freq.hot_mass(counts, remap, cfg.hot_size), 4
        )
    loader = ShardLoader(
        data_path,
        batch_size=cfg.batch_size,
        max_nnz=cfg.max_nnz,
        table_size=cfg.table_size,
        block_mib=8,
        parse_fn=parse_fn,
        remap=remap,
        hot_size=cfg.hot_size,
        hot_nnz=cfg.hot_nnz,
    )
    workers = max(1, min(6, (os.cpu_count() or 1) - 1))
    nbytes = os.path.getsize(data_path)
    examples = 0
    # Per-phase attribution of the e2e loop (ISSUE 1): input_stall is
    # time blocked on the prefetch iterator (parse+pack hide behind
    # it), h2d the inline put_batch, dispatch the async train call;
    # device_block the final drain.  input_stall_frac says whether the
    # gap between `value` (pure compute) and e2e_examples_per_sec is
    # the host pipeline or the device path.
    phase = {"input_stall": 0.0, "h2d": 0.0, "dispatch": 0.0}
    it = loader.prefetch(depth=2, parse_workers=workers)
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        try:
            batch, _ = next(it)
        except StopIteration:
            break
        phase["input_stall"] += time.perf_counter() - t
        t = time.perf_counter()
        arrays = step.put_batch(batch)
        phase["h2d"] += time.perf_counter() - t
        t = time.perf_counter()
        state, _ = step.train(state, arrays)
        phase["dispatch"] += time.perf_counter() - t
        examples += batch.num_real()
    t = time.perf_counter()
    jax.device_get(state["tables"]["w"]["param"][:1, 0])
    phase["device_block"] = time.perf_counter() - t
    dt = time.perf_counter() - t0
    result["e2e_examples_per_sec"] = round(examples / dt, 1)
    result["e2e_mb_per_sec"] = round(nbytes / dt / 2**20, 1)
    result["e2e_examples"] = examples
    result["input_stall_frac"] = round(phase["input_stall"] / dt, 4)
    result["e2e_phase_seconds"] = {
        k: round(v, 3) for k, v in phase.items()
    }
    result["native_parser"] = bool(native_available())

    # host-only parse+pack rate (no device work): isolates the host
    # pipeline the e2e number is bound by on low-core hosts
    t0 = time.perf_counter()
    parsed = 0
    for batch, _ in loader.prefetch(depth=2, parse_workers=workers):
        parsed += batch.num_real()
    dt = time.perf_counter() - t0
    result["parse_mb_per_sec"] = round(nbytes / dt / 2**20, 1)
    result["parse_examples_per_sec"] = round(parsed / dt, 1)

    # -- packed-batch cache path (io/packed.py): the steady-state story.
    # Text parses ONCE into device-ready batches; epochs 2..N stream
    # them at memory speed.  Cached on disk keyed by config + remap;
    # the v2 cache stores PRE-COMPACTED records (io/compact.py), so the
    # steady-state feed pays zero per-batch compaction or wire packing.
    from xflow_tpu.io import packed as packed_mod

    digest = (packed_mod.remap_digest(remap) or "none")[:12]
    pk_path = (
        f"{data_path}.pk2-b{cfg.batch_size}-k{cfg.max_nnz}"
        f"-t{cfg.table_size_log2}-h{cfg.hot_size_log2}.{cfg.hot_nnz}"
        f"-s{cfg.seed}-r{digest}"
    )
    if not os.path.exists(pk_path):
        t0 = time.perf_counter()
        packed_mod.convert_shard(
            data_path,
            pk_path,
            batch_size=cfg.batch_size,
            max_nnz=cfg.max_nnz,
            table_size=cfg.table_size,
            hot_size=cfg.hot_size,
            hot_nnz=cfg.hot_nnz if cfg.hot_size else 0,
            hash_mode=True,
            hash_seed=cfg.seed,
            block_mib=8,
            remap=remap,
            parse_fn=parse_fn,
        )
        result["packed_build_secs"] = round(time.perf_counter() - t0, 1)
    pk_loader = ShardLoader(
        pk_path,
        batch_size=cfg.batch_size,
        max_nnz=cfg.max_nnz,
        table_size=cfg.table_size,
        hash_seed=cfg.seed,
        remap=remap,
        hot_size=cfg.hot_size,
        hot_nnz=cfg.hot_nnz if cfg.hot_size else 0,
        emit_compact=step.dict_wire,
    )
    result["wire_format"] = step.wire_format
    # host-only read rate (epoch-2+ feed capacity, no device).  Records
    # are mmap-backed views; to keep the metric honest this loop runs
    # the numpy half of put_batch — by construction exactly the
    # per-batch work the training feed performs
    # (parallel/step.py::host_wire_np).
    t0 = time.perf_counter()
    n = 0
    for batch, _ in pk_loader.iter_batches():
        step.host_wire_np(batch)
        n += batch.num_real()
    dt = time.perf_counter() - t0
    result["packed_read_examples_per_sec"] = round(n / dt, 1)
    # e2e with the input fan-out + staging ring (the trainer's
    # production structure: io/fanout.py ShardStreamPool feeding
    # trainer._transfer_ahead's ring): the packed corpus splits into
    # XFLOW_BENCH_STREAMS contiguous sub-shards (split_shard_v2 — raw
    # record copy) so N reader streams pre-read/compact ahead while the
    # ring stages XFLOW_BENCH_RING_DEPTH batches of h2d.  The first
    # timed pass compiles the full- and tail-batch shape buckets, so
    # run two and report the steady-state (second) pass — that IS the
    # epoch regime.  The second pass must hit the executable cache
    # only: e2e_recompiles counts programs compiled DURING it
    # (acceptance: 0 — the dict wire's plane_cap bucketing keeps steady
    # shapes on one program, and the fan-out's serial-order merge feeds
    # the identical batch sequence).
    from concurrent.futures import ThreadPoolExecutor

    from xflow_tpu.io.fanout import ShardStreamPool
    from xflow_tpu.trainer import _ring_workers

    n_streams = int(os.environ.get("XFLOW_BENCH_STREAMS", "4"))
    ring_depth = int(os.environ.get("XFLOW_BENCH_RING_DEPTH", "4"))
    fan_prefix = f"{pk_path}.fan{n_streams}"
    # a hard-killed prior split can leave `.tmp.<pid>` residue next to
    # the real sub-shards — the tail-safety convention says any name
    # with a .tmp infix is never a shard
    fan_paths = sorted(
        p for p in glob.glob(glob.escape(fan_prefix) + "-*")
        if ".tmp." not in os.path.basename(p)
    )
    if not fan_paths:
        fan_paths = packed_mod.split_shard_v2(
            pk_path, fan_prefix, n_streams
        )
    result["input_streams"] = n_streams
    result["transfer_ahead_depth"] = ring_depth

    def fan_loader(path):
        return ShardLoader(
            path,
            batch_size=cfg.batch_size,
            max_nnz=cfg.max_nnz,
            table_size=cfg.table_size,
            hash_seed=cfg.seed,
            remap=remap,
            hot_size=cfg.hot_size,
            hot_nnz=cfg.hot_nnz if cfg.hot_size else 0,
            emit_compact=step.dict_wire,
        )

    def train_cache_size():
        # private, but the only count of programs a jit holds
        # (present on jax 0.9.0; let it raise if a later one drops it)
        return int(step.train._cache_size())

    best = 0.0
    best_link = 0.0
    wire_bytes_per_batch = None
    compaction_ratio = None
    for pass_i in range(2):
        cache_before = train_cache_size()
        t0 = time.perf_counter()
        n = 0
        sent = 0
        pending = []
        pool = ShardStreamPool(
            fan_paths, fan_loader, num_streams=n_streams, depth=2,
            transform=step.precompact,
        )
        try:
            with ThreadPoolExecutor(_ring_workers(ring_depth)) as ex:
                for batch, _, _ in pool:
                    sent += 1
                    if wire_bytes_per_batch is None:
                        # what actually crosses the link per dispatch
                        # (the bytes x link-MB/s reconciliation,
                        # VERDICT r4 #6)
                        wire, cb = step.host_wire_np(batch)
                        wire_bytes_per_batch = sum(
                            v.nbytes for v in wire.values()
                        )
                        if cb is not None and cb.n_dict:
                            compaction_ratio = round(
                                cb.n_cold / max(cb.cold_touched, 1), 3
                            )
                    pending.append(
                        (ex.submit(step.put_batch, batch), batch.num_real())
                    )
                    if len(pending) > ring_depth:
                        fut, cnt = pending.pop(0)
                        state, _ = step.train(state, fut.result())
                        n += cnt
                for fut, cnt in pending:
                    state, _ = step.train(state, fut.result())
                    n += cnt
        finally:
            pool.close()
        jax.device_get(state["tables"]["w"]["param"][:1, 0])
        dt = time.perf_counter() - t0
        if pass_i == 1:
            result["e2e_recompiles"] = train_cache_size() - cache_before
        eps = n / dt
        if eps > best:
            best = eps
            # actual bytes shipped per second this pass (every
            # dispatched batch ships the same bucketed wire, so
            # count batches, not real examples — a real-example
            # scaling would read low by the tail-batch pad
            # fraction)
            if wire_bytes_per_batch:
                best_link = sent * wire_bytes_per_batch / dt
    result["e2e_packed_examples_per_sec"] = round(best, 1)
    if compaction_ratio is not None:
        result["compaction_ratio"] = compaction_ratio
    if wire_bytes_per_batch:
        result["wire_bytes_per_batch"] = wire_bytes_per_batch
        result["wire_bytes_per_example"] = round(
            wire_bytes_per_batch / cfg.batch_size, 1
        )
        # implied host->device rate IF the link were the only cost
        result["e2e_implied_link_mb_per_sec"] = round(
            best_link / 2**20, 1
        )


def ensure_synth_data(path: str, num_examples: int, seed: int = 7) -> str:
    """Generate (once, cached) a zipf-feature libffm shard for the e2e
    bench; format matches the reference's bundled data
    (/root/reference/data/small_train-00000:1 ``label<TAB>fgid:fid:val``).

    The cache key (filename) embeds the generator version+params so a
    stale shard from older generator settings is never reused; the temp
    name is pid-unique so concurrent benches can't interleave writes.
    """
    import scripts.gen_synth as gen

    base, ext = os.path.splitext(path)
    key = f"g{gen.GEN_VERSION}-s{seed}-f{gen.FIELDS}-v{gen.VOCAB}"
    path = f"{base}-{key}{ext}"
    if not os.path.exists(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        gen.generate_shard(tmp, num_examples, seed=seed)
        os.replace(tmp, path)
    return path


def main() -> None:
    force_cpu = os.environ.get("XFLOW_BENCH_CPU") == "1"

    import jax

    from xflow_tpu.utils.compile_cache import enable_compile_cache

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    devices = jax.devices()
    backend = devices[0].platform
    if backend == "cpu" and not force_cpu:
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform 'cpu'); the "
            "device metrics need a chip.  XFLOW_BENCH_CPU=1 asks for "
            "the CPU mode explicitly."
        )
    accel = [] if force_cpu else devices
    cpu = jax.devices("cpu")

    from xflow_tpu.config import Config

    result: dict = {
        "metric": (
            "lr_ftrl_cpu_proxy_examples_per_sec" if force_cpu
            else "lr_ftrl_train_examples_per_sec"
        ),
        "value": 0.0,
        "unit": "examples/sec",
        "vs_baseline": None,
        "backend": backend,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }

    # Flagship config (docs/PERF.md sweep, round 4): hot head H=2^12
    # captures 71% of real zipf occurrence mass; hot capacity 32 rides
    # the MXU, cold capacity 16 catches the rest on the DMA path — the
    # step is cold-slice-bound, so shrinking the cold section is the
    # whole game.  Truncation at this geometry is measured and reported
    # as hot_truncated_frac (~0.1%).
    cfg = Config(
        model="lr",
        optimizer="ftrl",
        table_size_log2=24,
        batch_size=131072,
        max_nnz=16,
        hot_size_log2=12,
        hot_nnz=32,
        num_devices=1,
    )

    # Real zipf-distributed batches off the CSR cache (production
    # loader + measured remap) — synthetic uniform keys understate the
    # head mass the hot table exists for.  XFLOW_BENCH_E2E_EXAMPLES=0
    # asks for the synthetic batches (and no e2e leg) explicitly.
    n_examples = int(
        os.environ.get(
            "XFLOW_BENCH_E2E_EXAMPLES", "2000000" if accel else "200000"
        )
    )
    data_path = csr = remap = None
    if n_examples > 0:
        data_path, csr, remap, hot_mass = prepare_real_data(cfg, n_examples)
        nb = max(1, min(4, n_examples // cfg.batch_size))
        batches, truncated_frac = real_batches(cfg, csr, remap, nb)
        result["batch_source"] = "zipf-cache"
        if hot_mass is not None:
            result["hot_mass"] = round(hot_mass, 4)
    else:
        result["batch_source"] = "synthetic"
        batches, truncated_frac = make_batches(cfg, 4)
    result["hot_truncated_frac"] = round(truncated_frac, 6)

    if accel:
        step, state = build(accel, cfg)
        _, accel_eps = run(step, state, batches, iters=20)

    # CPU proxy baseline, smaller table/iters to keep runtime bounded.
    # The proxy runs ITS best config (no hot table — one-hot matmuls are
    # an MXU trick, slow on CPU; scatter-add DMA is the CPU-fast path)
    # on the same real data, so vs_baseline compares best-vs-best.
    cpu_cfg = cfg.replace(
        table_size_log2=22, batch_size=16384, max_nnz=40,
        hot_size_log2=0,
    )
    cpu_step, cpu_state = build(cpu, cpu_cfg)
    if csr is not None:
        cpu_batches, _ = real_batches(cpu_cfg, csr, None, 4)
    else:
        cpu_batches, _ = make_batches(cpu_cfg, 4)
    _, cpu_eps = run(cpu_step, cpu_state, cpu_batches, iters=8, warmup=2)
    result["cpu_examples_per_sec"] = round(cpu_eps, 1)

    if accel:
        result["value"] = round(accel_eps, 1)
        result["vs_baseline"] = round(accel_eps / cpu_eps, 3)
    else:
        result["value"] = round(cpu_eps, 1)  # under the CPU metric name

    # -- end-to-end pipeline metric (text -> trained table) ----------------
    if n_examples > 0:
        # the CPU mode shrinks the geometry so the leg stays bounded
        e2e_cfg = cfg if accel else cfg.replace(
            table_size_log2=22, batch_size=16384
        )
        bench_e2e(accel or cpu, e2e_cfg, data_path, result, remap=remap)

    if accel:
        _persist_artifact(result)
    print(json.dumps(result))


def _persist_artifact(result: dict) -> None:
    """Keep an accelerator run's full JSON under
    docs/artifacts/bench_tpu_*.json, so the number of record is a
    citable file rather than prose."""
    art_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "docs", "artifacts"
    )
    os.makedirs(art_dir, exist_ok=True)
    name = "bench_tpu_{}.json".format(
        time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    )
    with open(os.path.join(art_dir, name), "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    result["artifact"] = os.path.join("docs", "artifacts", name)


if __name__ == "__main__":
    main()

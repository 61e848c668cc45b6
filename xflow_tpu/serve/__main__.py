"""CLI: ``python -m xflow_tpu.serve <serve|index|cascade|loadgen|bench|score>``

    index   ARTIFACT --input FILE     build the serve-time item index
                                      beside a retrieval artifact from
                                      libffm item rows (item-side
                                      features deduplicated into the
                                      catalog, embedded through the
                                      item tower — serve/artifact.py::
                                      export_item_index)

    cascade RETRIEVAL RANKING --port P
                                      retrieval→ranking cascade tier
                                      (serve/cascade.py): a top-k fleet
                                      over the retrieval artifact's
                                      item index feeding a point-score
                                      fleet over the ranking artifact,
                                      behind one HTTP front end
                                      (/v1/recommend, /v1/topk,
                                      /v1/score; rollout endpoints
                                      take "stage": "retrieval"|
                                      "ranking"); emits `cascade`
                                      JSONL stats windows


    score   ARTIFACT --input FILE     pctr per libffm line (stdout/--out)
    bench   ARTIFACT [--requests N]   closed-loop concurrent load through
                                      one MicroBatcher; prints a JSON
                                      summary with queue/featurize/
                                      device/e2e p50+p99 and logs
                                      serve_load/serve_stats/serve_bench
                                      JSONL rows (--metrics-out) that
                                      ``python -m xflow_tpu.obs
                                      validate`` checks like any other
                                      metrics file
    serve   ARTIFACT --port P         production tier: HTTP front end
                                      (serve/server.py) over a replica
                                      fleet (--replicas) with admission
                                      control and staged rollout
                                      (--canary-frac default); prints
                                      one JSON line with the bound
                                      address, then serves until
                                      SIGTERM/SIGINT — which drain
                                      gracefully through the tier/fleet
                                      close() path (every accepted
                                      request scores, final stats rows
                                      flush)
    loadgen ARTIFACT --qps Q          open-loop zipf traffic generator
                                      (serve/loadgen.py) against an
                                      in-process fleet or --url of a
                                      running tier; logs the serve_bench
                                      SLO row scripts/check_serve_slo.py
                                      gates on

Serving docs: docs/SERVING.md (the "Production tier" section covers
serve/loadgen, rollout states, and the shed policy).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np

from xflow_tpu.obs import startup
from xflow_tpu.utils.compile_cache import enable_compile_cache


def _buckets(text: str | None) -> tuple[int, ...] | None:
    if not text:
        return None
    return tuple(int(b) for b in text.split(","))


def _percentile(vals: list[float], p: float) -> float:
    # one percentile definition repo-wide: obs.registry.Histogram
    from xflow_tpu.obs.registry import Histogram

    h = Histogram(capacity=max(len(vals), 1))
    for v in vals:
        h.observe(v)
    return round(h.percentile(p), 6)


def cmd_score(args) -> int:
    from xflow_tpu.serve.engine import PredictEngine

    engine = PredictEngine.load(
        args.artifact,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        warm=not args.no_warm,
    )
    src = open(args.input) if args.input else sys.stdin
    try:
        lines = [l for l in src.read().splitlines() if l.strip()]
    finally:
        if args.input:
            src.close()
    pctr = engine.score_text(lines)
    out = open(args.out, "w") if args.out else sys.stdout
    try:
        for p in pctr:
            out.write(f"{p:.6f}\n")
    finally:
        if args.out:
            out.close()
    return 0


def cmd_bench(args) -> int:
    from xflow_tpu.obs.schema import validate_rows
    from xflow_tpu.serve.batcher import MicroBatcher
    from xflow_tpu.serve.engine import PredictEngine

    engine = PredictEngine.load(
        args.artifact,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        warm=True,
    )
    cfg = engine.cfg
    logger = _serve_logger(
        args.metrics_out, engine.digest, cfg.model, "bench"
    )
    if logger is not None:
        logger.log("serve_load", {
            "artifact": args.artifact,
            "config_digest": engine.digest,
            "model": cfg.model,
            "buckets": list(engine.buckets),
            "warm_seconds": round(engine.warm_seconds, 6),
            "compiles": engine.compile_count,
        })
    batcher = MicroBatcher(
        engine, max_wait_ms=args.max_wait_ms, metrics_logger=logger
    )
    rng = np.random.default_rng(args.seed)
    nnz = min(args.nnz, cfg.max_nnz)
    rows = [
        (
            rng.integers(0, cfg.table_size, size=nnz).astype(np.int64),
            np.arange(nnz, dtype=np.int32) % max(cfg.max_fields, 1),
            None,
        )
        for _ in range(args.requests)
    ]
    e2e: list[float] = []
    e2e_lock = threading.Lock()

    def worker(my_rows) -> None:
        for row in my_rows:
            t0 = time.perf_counter()
            fut = batcher.submit(*row)
            fut.result(timeout=600.0)
            dt = time.perf_counter() - t0
            with e2e_lock:
                e2e.append(dt)

    threads = [
        # bounded workload — each worker drains a finite request slice,
        # so the untimed join below ends with it; a wedged engine is
        # the batcher close() join-timeout's job (xf: ignore[XF006])
        threading.Thread(target=worker, args=(rows[i :: args.concurrency],))
        for i in range(args.concurrency)
    ]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    seconds = time.perf_counter() - t_start
    stats = batcher.close()
    summary = {
        "requests": args.requests,
        "concurrency": args.concurrency,
        "seconds": round(seconds, 6),
        "requests_per_sec": round(args.requests / max(seconds, 1e-9), 1),
        "e2e_p50": _percentile(e2e, 50),
        "e2e_p99": _percentile(e2e, 99),
        "queue_p50": stats["queue_p50"],
        "queue_p99": stats["queue_p99"],
        "featurize_p50": stats["featurize_p50"],
        "featurize_p99": stats["featurize_p99"],
        "device_p50": stats["device_p50"],
        "device_p99": stats["device_p99"],
        "compiles": engine.compile_count,
    }
    if logger is not None:
        logger.log("serve_bench", summary)
        logger.close()
        from xflow_tpu.obs.schema import load_jsonl

        errors = validate_rows(load_jsonl(args.metrics_out))
        if errors:
            for e in errors:
                print(f"schema violation: {e}", file=sys.stderr)
            return 1
    print(json.dumps(
        dict(summary, buckets=list(engine.buckets),
             batch_fill_mean=stats["batch_fill_mean"]),
        sort_keys=True,
    ))
    return 0


def _serve_logger(path: str, digest: str, model: str, tag: str):
    from xflow_tpu.utils.logging import MetricsLogger

    if not path:
        return None
    return MetricsLogger(path, run_header={
        "run_id": f"{int(time.time() * 1000):x}-{tag}",
        "config_digest": digest,
        "rank": 0,
        "num_hosts": 1,
        "model": model,
    })


def _reqtrace_sink(logger, sample: float):
    """Request-scoped trace sink (obs/reqtrace.py) bound to the tier's
    metrics stream; None when metrics are off — tracing without a sink
    to land in would stamp spans nobody can read."""
    from xflow_tpu.obs.reqtrace import ReqTraceSink

    if logger is None:
        return None
    return ReqTraceSink(metrics_logger=logger, sample=sample)


def cmd_serve(args) -> int:
    """The production tier: fleet + HTTP front end + watchdog, alive
    until SIGTERM/SIGINT, then a graceful drain through
    ``ServeTier.close()`` → ``ReplicaFleet.close()`` (every accepted
    request scores; the final serve_stats/serve_shed rows flush)."""
    import signal

    from xflow_tpu.obs.flight import FlightRecorder
    from xflow_tpu.obs.watchdog import Watchdog
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.server import ServeTier

    flight = FlightRecorder()
    fleet = ReplicaFleet.load(
        args.artifact,
        replicas=args.replicas,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        max_wait_ms=args.max_wait_ms,
        deadline_budget_ms=args.deadline_budget_ms,
        depth_budget=args.depth_budget,
        flight=flight,
        cache_capacity=args.cache_capacity,
    )
    logger = _serve_logger(
        args.metrics_out, fleet.digest, fleet.cfg.model, "serve"
    )
    fleet.metrics_logger = logger
    flight.metrics_logger = logger
    fleet.reqtrace = _reqtrace_sink(logger, args.reqtrace_sample)
    fleet.log_load(args.artifact)
    # chaos fabric (docs/ROBUSTNESS.md): the XFLOW_CHAOS env var arms
    # the serve surface too, with chaos rows in this tier's stream
    from xflow_tpu import chaos

    if chaos.arm_from_env() is not None and logger is not None:
        chaos.attach_logger(logger)
    tier = ServeTier(
        fleet,
        host=args.host,
        port=args.port,
        flight=flight,
        default_canary_frac=args.canary_frac,
        score_timeout_s=fleet.cfg.serve_score_timeout_s,
        socket_timeout_s=fleet.cfg.serve_socket_timeout_s,
    )
    wd = Watchdog(
        flight, serve_s=args.watchdog_serve_s, metrics_logger=logger
    )
    wd.set_pending("serve", fleet.pending)
    wd.set_pending("http", lambda: tier.running)
    # live telemetry plane (obs/live.py, obs/export.py): SLO alert
    # rules evaluated over each stats window, host resource rows per
    # tick, and both surfaced on GET /v1/stats next to the watchdog's
    # health state; GET /metrics exposition comes free with the tier
    from xflow_tpu.obs.export import ResourceSampler
    from xflow_tpu.obs.live import AlertEvaluator

    alerts = AlertEvaluator(metrics_logger=logger)
    sampler = ResourceSampler(
        metrics_logger=logger, registry=fleet.registry
    )
    tier.watchdog = wd
    tier.alerts = alerts
    # binary front end (serve/binary.py): the persistent XFB1
    # transport, sharing the SAME fleet (and therefore the same
    # admission control, cache, and stats windows) as the HTTP tier
    btier = None
    if args.binary_port >= 0:
        from xflow_tpu.serve.binary import BinaryTier

        btier = BinaryTier(
            fleet,
            host=args.host,
            port=args.binary_port,
            flight=flight,
            score_timeout_s=fleet.cfg.serve_score_timeout_s,
            socket_timeout_s=fleet.cfg.serve_socket_timeout_s,
        ).start()

    stop = threading.Event()

    def _drain(signum, frame) -> None:
        stop.set()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    tier.start()
    wd.start()
    print(json.dumps({
        "serving": tier.address,
        "binary": btier.address if btier is not None else None,
        "digest": fleet.digest,
        "model": fleet.cfg.model,
        "replicas": fleet.replicas,
        "buckets": list(fleet.engines[0].buckets),
        "admission": fleet.policy.describe(),
        "cache_capacity": (
            fleet.cache.capacity if fleet.cache is not None else 0
        ),
    }, sort_keys=True), flush=True)
    # stats-window loop IS the main thread's job until a drain signal
    while not stop.wait(args.stats_every_s):
        out = fleet.emit_stats()
        sampler.sample()
        alerts.observe_rows([
            dict(out["stats"], kind="serve_stats"),
            dict(out["shed"], kind="serve_shed"),
        ])
    wd.stop()
    # binary front end first: it only submits into the fleet, so the
    # tier/fleet close below still drains whatever it admitted
    if btier is not None:
        btier.close()
    final = tier.close()
    if logger is not None:
        logger.close()
    print(json.dumps({"drained": final}, sort_keys=True), flush=True)
    return 0


def cmd_index(args) -> int:
    """Build the serve-time item index beside a retrieval artifact
    from libffm-format lines: each line's ITEM-side features (fields
    >= the artifact's tower_split_field) become one catalog item,
    deduplicated by feature set, embedded through the item tower, and
    frozen via serve.artifact.export_item_index."""
    from xflow_tpu.io.loader import make_parse_fn
    from xflow_tpu.serve.artifact import (
        export_item_index,
        item_catalog_from_block,
    )
    from xflow_tpu.serve.engine import PredictEngine

    engine = PredictEngine.load(
        args.artifact,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        warm=False,
    )
    cfg = engine.cfg
    parse = make_parse_fn(
        cfg.table_size, cfg.hash_mode, cfg.seed,
        prefer_native=cfg.native_parser,
    )
    src = open(args.input, "rb") if args.input else sys.stdin.buffer
    try:
        block = parse(src.read())
    finally:
        if args.input:
            src.close()
    items = item_catalog_from_block(
        block, cfg.tower_split_field, args.max_items
    )
    if not items:
        print(
            "error: no item-side features found (fields >= "
            f"tower_split_field={cfg.tower_split_field})",
            file=sys.stderr,
        )
        return 1
    meta = export_item_index(engine, args.artifact, items)
    print(json.dumps({
        "artifact": args.artifact,
        "items": meta["count"],
        "dim": meta["dim"],
        "servable": meta["servable"],
    }, sort_keys=True))
    return 0


def cmd_cascade(args) -> int:
    """The cascade tier: retrieval top-k fleet + ranking fleet +
    CascadeEngine behind one HTTP front end, alive until
    SIGTERM/SIGINT, then a graceful drain (retrieval first, then
    ranking — in-flight fan-outs land before the ranking queues
    close)."""
    import signal

    from xflow_tpu.serve.cascade import CascadeEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.server import ServeTier

    retrieval = ReplicaFleet.load(
        args.retrieval,
        replicas=args.replicas,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        max_wait_ms=args.max_wait_ms,
        deadline_budget_ms=args.deadline_budget_ms,
        depth_budget=args.depth_budget,
        topk_k=args.topk_k,
        topk=True,
    )
    ranking = ReplicaFleet.load(
        args.ranking,
        replicas=args.replicas,
        num_devices=args.num_devices,
        buckets=_buckets(args.buckets),
        max_wait_ms=args.max_wait_ms,
        deadline_budget_ms=args.deadline_budget_ms,
        depth_budget=args.depth_budget,
    )
    logger = _serve_logger(
        args.metrics_out, ranking.digest, ranking.cfg.model, "cascade"
    )
    retrieval.metrics_logger = logger
    ranking.metrics_logger = logger
    # ONE sink across both stages: a /recommend request keeps one
    # trace id through retrieval fan-in and the ranking fan-out, so a
    # span tree reads end-to-end (obs/reqtrace.py)
    sink = _reqtrace_sink(logger, args.reqtrace_sample)
    retrieval.reqtrace = sink
    retrieval.reqtrace_stage = "retrieval"
    ranking.reqtrace = sink
    ranking.reqtrace_stage = "ranking"
    cascade = CascadeEngine(
        retrieval, ranking, k=args.k, metrics_logger=logger
    )
    tier = ServeTier(
        ranking,
        host=args.host,
        port=args.port,
        default_canary_frac=args.canary_frac,
        cascade=cascade,
        score_timeout_s=ranking.cfg.serve_score_timeout_s,
        socket_timeout_s=ranking.cfg.serve_socket_timeout_s,
    )
    stop = threading.Event()

    def _drain(signum, frame) -> None:
        stop.set()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    tier.start()
    print(json.dumps({
        "serving": tier.address,
        "retrieval_digest": retrieval.digest,
        "ranking_digest": ranking.digest,
        "k": cascade.k,
        "topk_k": retrieval.engines[0].topk_k,
        "index_items": int(len(retrieval.engines[0].item_index["item_index"])),
        "replicas": args.replicas,
    }, sort_keys=True), flush=True)
    while not stop.wait(args.stats_every_s):
        cascade.emit_stats()
        retrieval.emit_stats()
        ranking.emit_stats()
    final = tier.close()
    if logger is not None:
        logger.close()
    print(json.dumps({"drained": final}, sort_keys=True), flush=True)
    return 0


def _parse_qos_mix(text: str) -> dict | None:
    """"bidding=0.3,normal=0.5,best_effort=0.2" → class fractions."""
    if not text:
        return None
    mix = {}
    for part in text.split(","):
        name, sep, frac = part.partition("=")
        if not sep:
            raise ValueError(
                f"bad --qos-mix entry {part!r} (want class=frac)"
            )
        mix[name.strip()] = float(frac)
    return mix


def cmd_loadgen(args) -> int:
    from xflow_tpu.obs.schema import load_jsonl, validate_rows
    from xflow_tpu.serve.loadgen import (
        BinaryTarget,
        HttpTarget,
        run_loadgen,
    )

    qos_mix = _parse_qos_mix(args.qos_mix)
    remote_target = None
    if args.url or args.binary_addr:
        # remote mode: the artifact supplies only the key space
        from xflow_tpu.config import Config
        from xflow_tpu.serve.artifact import load_manifest

        manifest = load_manifest(args.artifact)
        digest = manifest["config_digest"]
        model = manifest["model"]
        cfg = Config.from_json(manifest["config"])
        table_size = int(cfg.table_size)
        if args.binary_addr:
            host, _, port = args.binary_addr.rpartition(":")
            depth = (
                args.pipeline_depth
                if args.pipeline_depth is not None
                else cfg.serve_pipeline_depth
            )
            remote_target = BinaryTarget(
                host or "127.0.0.1",
                int(port),
                timeout_s=cfg.serve_client_timeout_s,
                pipeline_depth=depth,
                qos=args.qos or None,
            )
        else:
            remote_target = HttpTarget(
                args.url,
                timeout_s=cfg.serve_client_timeout_s,
                qos=args.qos or None,
            )
        target: object = remote_target
        fleet = None
    else:
        from xflow_tpu.serve.fleet import ReplicaFleet

        fleet = ReplicaFleet.load(
            args.artifact,
            replicas=args.replicas,
            num_devices=args.num_devices,
            buckets=_buckets(args.buckets),
            max_wait_ms=args.max_wait_ms,
            deadline_budget_ms=args.deadline_budget_ms,
            depth_budget=args.depth_budget,
            cache_capacity=args.cache_capacity,
            **({"default_qos": args.qos} if args.qos else {}),
        )
        digest, model = fleet.digest, fleet.cfg.model
        table_size = None
        target = fleet
    logger = _serve_logger(args.metrics_out, digest, model, "loadgen")
    if fleet is not None:
        fleet.metrics_logger = logger
        fleet.reqtrace = _reqtrace_sink(logger, args.reqtrace_sample)
        fleet.log_load(args.artifact)
    remote = bool(args.url or args.binary_addr)
    try:
        summary = run_loadgen(
            target,
            offered_qps=args.qps,
            duration_s=args.duration_s,
            concurrency=args.concurrency,
            nnz=args.nnz,
            zipf_a=args.zipf_a,
            table_size=table_size,
            seed=args.seed,
            metrics_logger=logger,
            # remote tier: no local sink to auto-enable on, so the
            # flag itself arms client-side minting over the XFS2 wire
            trace=(args.reqtrace_sample > 0) if remote else None,
            trace_sample=args.reqtrace_sample,
            qos_mix=qos_mix,
        )
    finally:
        if fleet is not None:
            fleet.close()
        if remote_target is not None and hasattr(remote_target, "close"):
            remote_target.close()
        if logger is not None:
            logger.close()
    if args.metrics_out:
        errors = validate_rows(load_jsonl(args.metrics_out))
        if errors:
            for e in errors:
                print(f"schema violation: {e}", file=sys.stderr)
            return 1
    print(json.dumps(summary, sort_keys=True))
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m xflow_tpu.serve",
        description="serving toolchain (docs/SERVING.md)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("artifact", help="artifact dir (serve/artifact.py)")
        sp.add_argument("--num-devices", type=int, default=1)
        sp.add_argument(
            "--buckets", default="",
            help="comma-separated batch-size buckets (default 1,8,64,512)",
        )

    ps = sub.add_parser("score", help="pctr per libffm input line")
    common(ps)
    ps.add_argument("--input", default="", help="libffm file (default stdin)")
    ps.add_argument("--out", default="", help="output file (default stdout)")
    ps.add_argument("--no-warm", action="store_true")

    pb = sub.add_parser("bench", help="concurrent serving latency bench")
    common(pb)
    pb.add_argument("--requests", type=int, default=256)
    pb.add_argument("--concurrency", type=int, default=8)
    pb.add_argument("--max-wait-ms", type=float, default=2.0)
    pb.add_argument("--nnz", type=int, default=16, help="features/request")
    pb.add_argument("--seed", type=int, default=0)
    pb.add_argument("--metrics-out", default="")

    def fleet_args(sp):
        sp.add_argument(
            "--replicas", type=int, default=2,
            help="PredictEngine replicas behind the router (clones of "
            "one loaded artifact — shared weights + compiles)",
        )
        sp.add_argument("--max-wait-ms", type=float, default=2.0)
        sp.add_argument(
            "--deadline-budget-ms", type=float, default=50.0,
            help="admission control: shed when the oldest queued "
            "request is older than this",
        )
        sp.add_argument(
            "--depth-budget", type=int, default=256,
            help="admission control: shed when a replica backlog "
            "reaches this depth",
        )
        sp.add_argument(
            "--reqtrace-sample", type=float, default=0.01,
            help="head-sampling rate for request-scoped traces in "
            "[0, 1]; errors, sheds, and the window's slowest-k are "
            "always kept regardless (obs/reqtrace.py)",
        )
        sp.add_argument("--metrics-out", default="")

    pv = sub.add_parser(
        "serve", help="HTTP serving tier (fleet + admission + rollout)"
    )
    common(pv)
    fleet_args(pv)
    pv.add_argument("--host", default="127.0.0.1")
    pv.add_argument("--port", type=int, default=8000)
    pv.add_argument(
        "--binary-port", type=int, default=-1,
        help="also serve the persistent XFB1 binary transport on this "
        "port (0 = ephemeral, -1 = off; serve/binary.py) — same "
        "fleet, admission control, and cache as the HTTP tier",
    )
    pv.add_argument(
        "--cache-capacity", type=int, default=None,
        help="hot-key score cache entries (serve/scache.py; 0 = off; "
        "default = the artifact config's serve_cache_capacity)",
    )
    pv.add_argument(
        "--canary-frac", type=float, default=0.1,
        help="default canary traffic fraction for POST /v1/rollout",
    )
    pv.add_argument(
        "--stats-every-s", type=float, default=10.0,
        help="serve_stats/serve_shed window flush period",
    )
    pv.add_argument("--watchdog-serve-s", type=float, default=10.0)

    pi = sub.add_parser(
        "index",
        help="build the serve-time item index beside a retrieval "
        "artifact from libffm item rows (docs/SERVING.md)",
    )
    common(pi)
    pi.add_argument(
        "--input", default="",
        help="libffm file of item rows (default stdin); item-side "
        "features (fields >= the artifact's tower_split_field) are "
        "deduplicated into the catalog",
    )
    pi.add_argument(
        "--max-items", type=int, default=0,
        help="cap the catalog size (0 = no cap)",
    )

    pc = sub.add_parser(
        "cascade",
        help="retrieval→ranking cascade tier (docs/SERVING.md)",
    )
    pc.add_argument(
        "retrieval",
        help="retrieval artifact dir (two-tower family with an item "
        "index — serve.artifact.export_item_index)",
    )
    pc.add_argument(
        "ranking", help="ranking artifact dir (any point-score family)"
    )
    pc.add_argument("--num-devices", type=int, default=1)
    pc.add_argument(
        "--buckets", default="",
        help="comma-separated batch-size buckets (default 1,8,64,512)",
    )
    fleet_args(pc)
    pc.add_argument("--host", default="127.0.0.1")
    pc.add_argument("--port", type=int, default=8000)
    pc.add_argument(
        "--k", type=int, default=8,
        help="candidates retrieved and ranked per request",
    )
    pc.add_argument(
        "--topk-k", type=int, default=None,
        help="compiled top-k width on the retrieval engines "
        "(default 16, capped at the index size); per-request k "
        "slices it",
    )
    pc.add_argument("--canary-frac", type=float, default=0.1)
    pc.add_argument("--stats-every-s", type=float, default=10.0)

    pl = sub.add_parser(
        "loadgen", help="open-loop zipf load generator (SLO rows)"
    )
    common(pl)
    fleet_args(pl)
    pl.add_argument(
        "--url", default="",
        help="target a RUNNING tier instead of an in-process fleet "
        "(the artifact then only supplies the key space)",
    )
    pl.add_argument(
        "--binary-addr", default="",
        help="target a RUNNING binary tier at HOST:PORT over the "
        "pipelined XFB1 transport (serve/loadgen.py::BinaryTarget) "
        "instead of HTTP",
    )
    pl.add_argument(
        "--pipeline-depth", type=int, default=None,
        help="max in-flight XFB1 frames per connection (binary "
        "transport; default = the artifact config's "
        "serve_pipeline_depth)",
    )
    pl.add_argument(
        "--qos", default="",
        help="QoS admission class for ALL offered traffic "
        "(bidding|normal|best_effort; default = the tier default)",
    )
    pl.add_argument(
        "--qos-mix", default="",
        help="mixed-class traffic, e.g. "
        "'bidding=0.3,normal=0.5,best_effort=0.2' — classes "
        "interleave at these fractions; the summary carries "
        "qos_offered/qos_shed per class",
    )
    pl.add_argument(
        "--cache-capacity", type=int, default=None,
        help="hot-key score cache entries for the in-process fleet "
        "(0 = off; default = the artifact config knob)",
    )
    pl.add_argument("--qps", type=float, default=500.0)
    pl.add_argument("--duration-s", type=float, default=10.0)
    pl.add_argument("--concurrency", type=int, default=8)
    pl.add_argument("--nnz", type=int, default=8)
    pl.add_argument("--zipf-a", type=float, default=1.3)
    pl.add_argument("--seed", type=int, default=0)

    args = p.parse_args(argv)
    enable_compile_cache()
    remote = args.cmd == "loadgen" and (args.url or args.binary_addr)
    if not remote:  # a remote load generator never touches the backend
        startup.init_backend()

    if args.cmd == "score":
        return cmd_score(args)
    if args.cmd == "serve":
        return cmd_serve(args)
    if args.cmd == "index":
        return cmd_index(args)
    if args.cmd == "cascade":
        return cmd_cascade(args)
    if args.cmd == "loadgen":
        return cmd_loadgen(args)
    return cmd_bench(args)


if __name__ == "__main__":
    sys.exit(main())

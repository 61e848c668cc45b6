"""Binary serve transport, QoS-classed admission, and the hot-key
score cache (ISSUE 20): XFB1 codec refusals, pipelined e2e scoring
parity, shed ordering under mixed-class overload (+ the extended
check_serve_slo.py gates), and cache correctness across rollouts."""

import json
import os
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from xflow_tpu.config import Config
from xflow_tpu.io.loader import ShardLoader
from xflow_tpu.trainer import Trainer


def _cfg(toy_dataset, **overrides):
    base = dict(
        train_path=toy_dataset.train_prefix,
        test_path=toy_dataset.test_prefix,
        model="lr",
        epochs=2,
        batch_size=64,
        table_size_log2=14,
        max_nnz=24,
        num_devices=1,
    )
    base.update(overrides)
    return Config(**base)


@pytest.fixture(scope="module")
def lr_served(toy_dataset, tmp_path_factory):
    """One trained lr model + exported artifact shared by the module
    (same shape as tests/test_serve.py's fixture)."""
    from xflow_tpu.serve.artifact import export_artifact

    trainer = Trainer(_cfg(toy_dataset))
    trainer.train()
    art = str(tmp_path_factory.mktemp("serve_bin") / "artifact")
    export_artifact(trainer, art)
    return {"trainer": trainer, "artifact": art}


def _slowed(engine, delay_s):
    import time as _time

    orig = engine.predict_prepared
    engine.predict_prepared = lambda b: (_time.sleep(delay_s), orig(b))[1]
    return engine


def _rows(cfg, n, nnz=6, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, cfg.table_size, size=nnz) for _ in range(n)
    ]


def _trained_row(trainer, shard=None):
    """One row of TRAINED keys (an untrained random row scores the
    all-zero-weights 0.5 on every artifact — useless for telling two
    model versions apart)."""
    loader = ShardLoader(
        shard or trainer.cfg.test_path + "-00000",
        batch_size=trainer.cfg.batch_size,
        max_nnz=trainer.cfg.max_nnz,
        table_size=trainer.cfg.table_size,
        parse_fn=trainer._parse_fn(),
    )
    batch = next(b for b, _ in loader.iter_batches())
    return batch.keys[0][batch.mask[0] > 0]


# -- frame codec --------------------------------------------------------------


def test_xfb1_codec_roundtrip_and_typed_refusals():
    """The codec contract the wirefuzz target drives: encode→decode
    round-trips; truncation, trailing bytes, magic confusion, length
    inflation, and unknown QoS bytes all refuse with typed errors."""
    from xflow_tpu.serve.binary import (
        FRAME_MAGIC,
        MAX_FRAME_BYTES,
        STATUS_OK,
        decode_frame,
        decode_request_stream,
        decode_response_frame,
        encode_frame,
        encode_response_frame,
    )
    from xflow_tpu.serve.server import (
        decode_packed_response,
        encode_packed_request,
        encode_packed_response,
    )

    body = encode_packed_request([(np.asarray([3, 99, 2048]), None, None)])
    frame = encode_frame(7, "bidding", body)
    assert frame.startswith(FRAME_MAGIC)
    rid, qos, got = decode_frame(frame)
    assert (rid, qos, got) == (7, "bidding", body)

    # pipelined stream: every frame decodes, ids/classes preserved
    stream = (
        encode_frame(1, "normal", body)
        + encode_frame((1 << 64) - 1, "best_effort", body)
    )
    decoded = decode_request_stream(stream)
    assert [(r, q) for r, q, _, _ in decoded] == [
        (1, "normal"), ((1 << 64) - 1, "best_effort"),
    ]

    # response frame round-trip
    rbody = encode_packed_response([0.25, 0.5])
    rframe = encode_response_frame(9, STATUS_OK, rbody)
    rid, status, rgot = decode_response_frame(rframe)
    assert (rid, status) == (9, STATUS_OK)
    np.testing.assert_allclose(
        decode_packed_response(rgot), [0.25, 0.5], atol=1e-7
    )

    # truncation: every strict prefix refuses
    for cut in (1, 4, 7, 8, 12, len(frame) - 1):
        with pytest.raises(ValueError, match="truncat|magic|length"):
            decode_frame(frame[:cut])
    with pytest.raises(ValueError, match="truncated frame at offset"):
        decode_request_stream(stream[:-3])

    # trailing garbage after a complete frame
    with pytest.raises(ValueError, match="trailing"):
        decode_frame(frame + b"\x00")

    # magic confusion: an XFS1 body alone is not a frame
    with pytest.raises(ValueError, match="magic"):
        decode_frame(body)

    # length inflation refuses BEFORE buffering toward the claimed size
    inflated = bytearray(frame)
    struct.pack_into("<I", inflated, 4, MAX_FRAME_BYTES + 1)
    with pytest.raises(ValueError, match="length"):
        decode_frame(bytes(inflated))

    # unknown QoS byte (offset 16 = magic + len + u64 rid)
    bad_qos = bytearray(frame)
    bad_qos[16] = 9
    with pytest.raises(ValueError, match="QoS byte"):
        decode_frame(bytes(bad_qos))
    with pytest.raises(ValueError, match="QoS class"):
        encode_frame(1, "platinum", body)
    with pytest.raises(ValueError, match="u64"):
        encode_frame(1 << 64, "normal", body)
    with pytest.raises(ValueError, match="status"):
        encode_response_frame(1, 17, b"")


# -- binary tier e2e ----------------------------------------------------------


def _recv_response(sock, timeout=30.0):
    """Read exactly one response frame off a raw socket."""
    from xflow_tpu.serve.binary import decode_response_frame

    sock.settimeout(timeout)
    buf = b""
    while len(buf) < 8:
        buf += sock.recv(4096)
    (length,) = struct.unpack_from("<I", buf, 4)
    while len(buf) < 8 + length:
        buf += sock.recv(4096)
    return decode_response_frame(buf[:8 + length])


def test_binary_tier_pipelined_scores_match_engine(lr_served):
    """E2E over the wire: a pipelined BinaryTarget against a live
    BinaryTier scores bit-for-bit with direct engine predict; framed
    garbage gets a typed STATUS_ERROR on a SURVIVING connection;
    unframeable garbage drops the connection."""
    from xflow_tpu.serve.binary import (
        STATUS_ERROR,
        STATUS_OK,
        BinaryTier,
        encode_frame,
    )
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.loadgen import BinaryTarget
    from xflow_tpu.serve.server import encode_packed_request

    engine = PredictEngine.load(
        lr_served["artifact"], buckets=(8, 64), warm=True
    )
    fleet = ReplicaFleet(engine, replicas=2, max_wait_ms=1.0)
    tier = BinaryTier(fleet, port=0, poll_s=0.02).start()
    rows = _rows(engine.cfg, 40, seed=5)
    try:
        with BinaryTarget(
            "127.0.0.1", tier.port, pipeline_depth=16
        ) as target:
            futs = [target.submit(r, qos="bidding") for r in rows]
            got = np.asarray([f.result(timeout=60) for f in futs])
        want = engine.predict(engine.featurize_raw(rows))
        np.testing.assert_allclose(got, want, atol=1e-6)
        live = fleet.stats()
        assert live["shed"]["by_class"]["bidding"]["admitted"] == 40
        assert "bidding" in live["qos"]

        # raw socket: framed-but-garbage body → typed STATUS_ERROR,
        # and the SAME connection still scores afterwards
        sock = socket.create_connection(("127.0.0.1", tier.port), 10)
        try:
            sock.sendall(encode_frame(50, "normal", b"not a request"))
            rid, status, body = _recv_response(sock)
            assert (rid, status) == (50, STATUS_ERROR)
            assert "error" in json.loads(body.decode())
            good = encode_packed_request([(rows[0], None, None)])
            sock.sendall(encode_frame(51, "normal", good))
            rid, status, body = _recv_response(sock)
            assert (rid, status) == (51, STATUS_OK)
            # unknown QoS byte with good framing: typed error frame
            bad = bytearray(encode_frame(52, "normal", good))
            bad[16] = 7
            sock.sendall(bytes(bad))
            rid, status, _ = _recv_response(sock)
            assert (rid, status) == (52, STATUS_ERROR)
            # unframeable garbage: the stream cannot resync — dropped
            sock.sendall(b"GET / HTTP/1.1\r\n\r\n")
            assert sock.recv(4096) == b""
        finally:
            sock.close()
    finally:
        tier.close()
        assert not tier.running
        fleet.close()  # the tier never closes the shared fleet


def test_binary_tier_shed_and_timeout_status(lr_served):
    """The wire's 429 and 504: an overloaded fleet answers
    STATUS_SHED (surfacing as a typed ShedError with its QoS class
    through BinaryTarget futures); a scoring future outliving
    score_timeout_s answers STATUS_TIMEOUT via the deadline sweep."""
    from xflow_tpu.serve.binary import BinaryTier
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet, ShedError
    from xflow_tpu.serve.loadgen import BinaryTarget

    engine = _slowed(
        PredictEngine.load(lr_served["artifact"], buckets=(8,), warm=True),
        0.3,
    )
    fleet = ReplicaFleet(
        engine, replicas=1, max_wait_ms=0.0,
        deadline_budget_ms=15.0, depth_budget=2,
    )
    tier = BinaryTier(
        fleet, port=0, poll_s=0.02, score_timeout_s=0.1,
    ).start()
    row = _rows(engine.cfg, 1, seed=6)[0]
    try:
        with BinaryTarget(
            "127.0.0.1", tier.port, pipeline_depth=32, qos="best_effort"
        ) as target:
            futs = [target.submit(row) for _ in range(16)]
            sheds, timeouts, ok = [], 0, 0
            for f in futs:
                try:
                    f.result(timeout=60)
                    ok += 1
                except ShedError as e:
                    assert e.qos == "best_effort"
                    assert e.cause in ("queue_depth", "queue_age")
                    sheds.append(e)
                except TimeoutError:
                    timeouts += 1
            assert sheds, "a 0.3s device call never backed the queue up?"
            # with a 0.1s score budget over a 0.3s device call, every
            # admitted request times out on the wire
            assert timeouts >= 1
            assert ok + timeouts + len(sheds) == 16
    finally:
        tier.close()
        fleet.close()


# -- QoS ordering under overload + the extended SLO gate ----------------------


def test_qos_overload_ordering_and_slo_gate(lr_served, tmp_path):
    """Acceptance: under a mixed-class zipf overload the bidding shed
    fraction stays 0 while best_effort absorbs the shedding; the
    serve_bench row carries the per-class split and
    check_serve_slo.py --qos-ordering gates it (and refuses an
    inverted or classless row)."""
    from xflow_tpu.obs.schema import load_jsonl, validate_rows
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.loadgen import run_loadgen
    from xflow_tpu.utils.logging import MetricsLogger

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gate = os.path.join(repo, "scripts", "check_serve_slo.py")

    engine = _slowed(
        PredictEngine.load(lr_served["artifact"], buckets=(8, 64), warm=True),
        0.03,
    )
    metrics = tmp_path / "qos.jsonl"
    logger = MetricsLogger(metrics, run_header={
        "run_id": "t", "config_digest": engine.digest,
        "rank": 0, "num_hosts": 1,
    })
    # budgets make the ordering DEMONSTRABLE, not just configured:
    # bidding's (full) budget is far above anything a 1.2s run can
    # reach, best_effort's scaled copy sits under the slowed device
    # call, so pressure lands on best_effort only — the invariant the
    # gate and `obs doctor` qos_inversion both watch
    fleet = ReplicaFleet(
        engine, replicas=1, max_wait_ms=1.0,
        deadline_budget_ms=10_000.0, depth_budget=10_000,
        qos_normal_frac=0.5, qos_best_effort_frac=0.002,
        metrics_logger=logger,
    )
    try:
        summary = run_loadgen(
            fleet, offered_qps=300, duration_s=1.2, concurrency=4,
            nnz=6, seed=7, drain_timeout_s=60.0,
            metrics_logger=logger,
            qos_mix={"bidding": 0.2, "normal": 0.5, "best_effort": 0.3},
        )
    finally:
        fleet.close()
        logger.close()
    assert validate_rows(load_jsonl(str(metrics))) == []
    assert summary["errors"] == 0
    offered = summary["qos_offered"]
    shed = summary["qos_shed"]
    assert offered["bidding"] > 0 and offered["best_effort"] > 0
    assert shed.get("bidding", 0) == 0, summary
    assert shed.get("normal", 0) == 0, summary
    assert shed.get("best_effort", 0) > 0, (
        "the overload never pressured the best_effort budget"
    )

    proc = subprocess.run(
        [
            sys.executable, gate, str(metrics),
            "--qos-ordering", "--max-shed-frac", "0.9",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "qos_bidding_shed" in proc.stdout

    # an inverted row (bidding shed, best_effort clean) must FAIL
    rows = [json.loads(l) for l in open(metrics) if l.strip()]
    bench = next(r for r in rows if r.get("kind") == "serve_bench")
    bench["qos_shed"] = {"bidding": 3, "normal": 0, "best_effort": 0}
    inverted = tmp_path / "inverted.jsonl"
    inverted.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    proc = subprocess.run(
        [
            sys.executable, gate, str(inverted),
            "--qos-ordering", "--max-shed-frac", "0.9",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "qos_bidding_shed" in proc.stdout

    # a classless row cannot vacuously pass the ordering gate
    bench.pop("qos_shed")
    classless = tmp_path / "classless.jsonl"
    classless.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    proc = subprocess.run(
        [
            sys.executable, gate, str(classless),
            "--qos-ordering", "--max-shed-frac", "0.9",
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "qos_shed" in proc.stderr


def test_compare_transports_gate_two_legs(lr_served, tmp_path):
    """Acceptance (CI wiring): one fleet serves both wires; an HTTP
    leg and a pipelined binary leg log transport-tagged serve_bench
    rows, and check_serve_slo.py --compare-transports requires the
    binary leg to beat HTTP on achieved QPS with a p99 no worse.  A
    file missing a leg is a usage error, not a pass.

    The gate's verdicts are decided on the two legs' rows with their
    ``achieved_qps`` and ``e2e_p99`` WRITTEN, once with the binary leg
    ahead and once behind: which transport wins a one-second race on
    a CPU shared with the other test workers says nothing about the
    wiring (the speed statement is a chip cell's, PERF.md section 7:
    ``lr_tb.serve_xfb1``)."""
    from xflow_tpu.obs.schema import load_jsonl, validate_rows
    from xflow_tpu.serve.binary import BinaryTier
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.loadgen import (
        BinaryTarget,
        HttpTarget,
        run_loadgen,
    )
    from xflow_tpu.serve.server import ServeTier
    from xflow_tpu.utils.logging import MetricsLogger

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gate = os.path.join(repo, "scripts", "check_serve_slo.py")

    engine = PredictEngine.load(
        lr_served["artifact"], buckets=(8, 64), warm=True
    )
    metrics = tmp_path / "twoleg.jsonl"
    logger = MetricsLogger(metrics, run_header={
        "run_id": "t", "config_digest": engine.digest,
        "rank": 0, "num_hosts": 1,
    })
    fleet = ReplicaFleet(engine, replicas=2, max_wait_ms=1.0)
    tier = ServeTier(fleet, port=0, poll_s=0.05).start()
    btier = BinaryTier(fleet, port=0, poll_s=0.02).start()
    table = int(engine.cfg.table_size)
    kw = dict(
        offered_qps=1200, duration_s=1.0, concurrency=4, nnz=6,
        seed=11, drain_timeout_s=60.0, table_size=table,
        metrics_logger=logger,
    )
    try:
        http = HttpTarget(tier.address, max_retries=0)
        http_sum = run_loadgen(http, **kw)
        with BinaryTarget(
            "127.0.0.1", btier.port, pipeline_depth=32
        ) as bt:
            bin_sum = run_loadgen(bt, **kw)
    finally:
        btier.close()
        tier.close()
        fleet.close()
        logger.close()
    assert validate_rows(load_jsonl(str(metrics))) == []
    assert http_sum["transport"] == "http"
    assert bin_sum["transport"] == "binary"
    assert bin_sum["errors"] == 0 and bin_sum["outstanding"] == 0

    rows = [json.loads(l) for l in open(metrics) if l.strip()]

    def verdict(name, written):
        """The gate's run over the live rows with each leg's
        serve_bench row carrying ``written[transport]``."""
        path = tmp_path / f"{name}.jsonl"
        path.write_text("".join(
            json.dumps(
                {**r, **written[r["transport"]]}
                if r.get("kind") == "serve_bench" else r
            ) + "\n"
            for r in rows
        ))
        return subprocess.run(
            [
                sys.executable, gate, str(path),
                "--compare-transports", "--max-shed-frac", "0.5",
            ],
            capture_output=True, text=True, timeout=120,
        )

    fast = {"achieved_qps": 1100.0, "e2e_p99": 0.004}
    slow = {"achieved_qps": 400.0, "e2e_p99": 0.020}
    proc = verdict("binary_ahead", {"binary": fast, "http": slow})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "transport_qps" in proc.stdout
    assert "transport_p99" in proc.stdout
    proc = verdict("binary_behind", {"binary": slow, "http": fast})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "FAIL transport_qps" in proc.stdout
    assert "FAIL transport_p99" in proc.stdout

    # one-leg file: usage error (exit 2), never a vacuous pass
    solo = [
        r for r in rows
        if not (
            r.get("kind") == "serve_bench"
            and r.get("transport") == "http"
        )
    ]
    oneleg = tmp_path / "oneleg.jsonl"
    oneleg.write_text("\n".join(json.dumps(r) for r in solo) + "\n")
    proc = subprocess.run(
        [sys.executable, gate, str(oneleg), "--compare-transports"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "http" in proc.stderr


# -- score cache --------------------------------------------------------------


def test_scache_lru_bound_across_two_generations():
    """Unit contract: the LRU bound holds within a digest generation;
    a generation swap evicts wholesale and the straggler guard drops
    inserts carrying the previous digest."""
    from xflow_tpu.serve.scache import ScoreCache

    cache = ScoreCache(capacity=4)
    cache.set_current("gen-a")
    for i in range(10):
        assert cache.insert("gen-a", np.asarray([i]), None, None, i / 10)
    assert len(cache) == 4
    row = cache.stats_row(reset=False)
    assert row["cache_evictions"] == 6
    assert row["cache_bytes"] > 0

    evicted = cache.set_current("gen-b")
    assert evicted == 4 and len(cache) == 0
    # straggler insert under the OLD digest is dropped, not mis-keyed
    assert not cache.insert("gen-a", np.asarray([1]), None, None, 0.5)
    assert cache.lookup("gen-a", np.asarray([9]), None, None) is None
    for i in range(10):
        cache.insert("gen-b", np.asarray([i]), None, None, i / 10)
    assert len(cache) == 4
    assert cache.lookup("gen-b", np.asarray([9]), None, None) == 0.9
    row = cache.stats_row(reset=False)
    assert row["cache_inserts_dropped"] == 1
    assert row["cache_invalidations"] == 1  # the a→b swap (init pin aside)


def test_cache_hits_bitwise_and_rollout_commit(toy_dataset, tmp_path):
    """Acceptance: a cached score is BITWISE the engine's own score;
    across a staged rollout commit the cache never returns the old
    artifact's score — post-commit traffic matches the new engine
    exactly, and lookups are suspended while the rollout is open so
    the canary gate still sees traffic."""
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.scache import ScoreCache

    trainer = Trainer(_cfg(toy_dataset, epochs=1))
    trainer.train()
    art_a = str(tmp_path / "a")
    export_artifact(trainer, art_a)
    trainer.train_epoch()
    art_b = str(tmp_path / "b")
    export_artifact(trainer, art_b)

    ea = PredictEngine.load(art_a, buckets=(8,), warm=True)
    eb = PredictEngine.load(art_b, buckets=(8,), warm=True)
    row = _trained_row(trainer)
    pa = float(ea.predict(ea.featurize_raw([row]))[0])
    pb = float(eb.predict(eb.featurize_raw([row]))[0])
    assert pa != pb

    cache = ScoreCache(capacity=128)
    fleet = ReplicaFleet(ea, replicas=2, max_wait_ms=1.0, cache=cache)
    try:
        assert fleet.score(row, timeout=60) == pa  # miss → device
        assert fleet.score(row, timeout=60) == pa  # hit → cache
        stats = cache.stats_row(reset=False)
        assert stats["cache_hits"] == 1
        assert len(cache) >= 1

        fleet.begin_rollout(eb, canary_frac=0.5, min_canary_requests=6)
        # open rollout: lookups suspended — the canary stripe must see
        # live traffic or the health gate never accumulates
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            got = fleet.score(row, timeout=60)
            # scored by an ENGINE (canary or incumbent), never cached
            assert min(abs(got - pa), abs(got - pb)) < 1e-6
            state = fleet.rollout_state()
            if state["healthy"]:
                break
        assert fleet.rollout_state()["healthy"]
        hits_before = cache.stats_row(reset=False)["cache_hits"]
        fleet.commit_rollout()
        # committed swap evicted generation A atomically with the pin
        assert fleet.score(row, timeout=60) == pb  # miss on fresh gen
        assert fleet.score(row, timeout=60) == pb  # hit, new digest
        assert (
            cache.stats_row(reset=False)["cache_hits"] == hits_before + 1
        )
    finally:
        final = fleet.close()
        trainer.close()
    # the serve_stats window carries the cache fields
    assert "cache_hits" in final["stats"]


def test_cache_rollout_delta_refresh_bitwise(toy_dataset, tmp_path):
    """The zero-recompile delta refresh path: a cached score from the
    base servable is evicted by rollout_delta's commit, and post-
    commit scores match the delta-applied engine bitwise (the
    servable digest advanced even though the config digest did not)."""
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.engine import PredictEngine
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.stream.delta import TouchedLedger, export_delta

    import jax

    trainer = Trainer(_cfg(toy_dataset, epochs=1))
    trainer.train()
    base = str(tmp_path / "base")
    export_artifact(trainer, base)
    base_step = int(jax.device_get(trainer.state["step"]))

    ledger = TouchedLedger()
    shard = trainer.cfg.train_path + "-00000"

    def feed(n):
        taken = 0
        while taken < n:
            for batch, _ in trainer._loader(shard).iter_batches():
                if taken >= n:
                    return
                ledger.mark(batch)
                taken += 1
                yield batch, None

    for _ in trainer.train_stream(feed(3)):
        pass
    delta = str(tmp_path / "delta")
    export_delta(trainer, delta, ledger, base_step)

    inc = PredictEngine.load(base, buckets=(8,), warm=True)
    ref = PredictEngine.load(base, buckets=(8,), warm=False).apply_delta(
        delta
    )
    # a row the DELTA actually touched (the stream fed this shard)
    row = _trained_row(trainer, shard=shard)
    p_base = float(inc.predict(inc.featurize_raw([row]))[0])
    p_delta = float(ref.predict(ref.featurize_raw([row]))[0])
    assert p_base != p_delta
    assert ref.servable_digest != inc.servable_digest

    fleet = ReplicaFleet.load(
        base, replicas=2, buckets=(8,), cache_capacity=64,
    )
    try:
        assert fleet.score(row, timeout=60) == p_base
        assert fleet.score(row, timeout=60) == p_base  # cached
        fleet.rollout_delta(delta, canary_frac=0.5, min_canary_requests=6)
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            fleet.score(row, timeout=60)
            if fleet.rollout_state()["healthy"]:
                break
        fleet.commit_rollout()
        assert fleet.servable == ref.servable_digest
        assert fleet.score(row, timeout=60) == p_delta  # fresh gen
        assert fleet.score(row, timeout=60) == p_delta  # cached hit
        assert fleet.cache.stats_row(reset=False)["cache_hits"] >= 2
    finally:
        fleet.close()
        trainer.close()


# -- observability: schema back-compat, doctor, summarize ---------------------


def test_serve_shed_by_class_schema_backcompat():
    """Pinned: by_class (serve_shed) and the cache_* fields
    (serve_stats) are additive-OPTIONAL — a pre-QoS metrics stream
    without them still validates, and typed violations still catch a
    wrong-typed by_class."""
    from xflow_tpu.obs.schema import validate_rows

    header = {
        "t": 0.0, "kind": "run_start", "run_id": "r0",
        "config_digest": "abc", "rank": 0, "num_hosts": 1,
        "time_unix": 1000.0, "hostname": "h", "pid": 1,
    }
    old_shed = {
        "t": 1.0, "kind": "serve_shed", "admitted": 10,
        "shed_total": 2, "shed_frac": 0.1667,
        "by_cause": {"queue_age": 2}, "errors": 0,
        "depth": 3, "queue_age_s": 0.05,
    }
    old_stats = {
        "t": 1.0, "kind": "serve_stats", "requests": 10, "batches": 2,
        "swaps": 0, "batch_fill_mean": 5.0, "queue_p50": 0.001,
        "queue_p99": 0.002, "featurize_p50": 0.001,
        "featurize_p99": 0.002, "device_p50": 0.001,
        "device_p99": 0.002,
    }
    assert validate_rows([header, old_shed, old_stats]) == []
    new_shed = dict(old_shed, by_class={
        c: {"admitted": 3, "shed": 0}
        for c in ("bidding", "normal", "best_effort")
    })
    new_stats = dict(
        old_stats, cache_hits=5, cache_misses=5, cache_hit_rate=0.5,
        cache_entries=5, cache_bytes=300, cache_evictions=0,
        cache_invalidations=0, cache_inserts_dropped=0,
    )
    assert validate_rows([header, new_shed, new_stats]) == []
    bad = dict(old_shed, by_class="bidding")
    assert any(
        "by_class" in v for v in validate_rows([header, bad])
    )


def test_doctor_qos_inversion_and_scache_thrash(tmp_path, capsys):
    """`obs doctor`: an inverted shed window (bidding shed while a
    traffic-carrying best_effort shed nothing) reads as
    qos_inversion; a post-warmup cache window stuck under a 10% hit
    rate reads as scache_thrash; healthy windows stay clean.  `obs
    summarize` prints the per-class shed and cache hit-rate lines."""
    from xflow_tpu.obs.__main__ import main

    header = {
        "t": 0.0, "kind": "run_start", "run_id": "r0",
        "config_digest": "abc", "rank": 0, "num_hosts": 1,
        "time_unix": 1000.0, "hostname": "h", "pid": 1,
    }

    def shed_row(bid_shed, be_shed, be_adm):
        return {
            "t": 2.0, "kind": "serve_shed", "admitted": 40,
            "shed_total": bid_shed + be_shed,
            "shed_frac": (bid_shed + be_shed) / 40,
            "by_cause": {"queue_age": bid_shed + be_shed}, "errors": 0,
            "depth": 3, "queue_age_s": 0.05,
            "by_class": {
                "bidding": {"admitted": 10, "shed": bid_shed},
                "normal": {"admitted": 20, "shed": 0},
                "best_effort": {"admitted": be_adm, "shed": be_shed},
            },
        }

    def stats_row(t, hits, misses):
        total = hits + misses
        return {
            "t": t, "kind": "serve_stats", "requests": total,
            "batches": 4, "swaps": 0, "batch_fill_mean": 8.0,
            "queue_p50": 0.001, "queue_p99": 0.002,
            "featurize_p50": 0.001, "featurize_p99": 0.002,
            "device_p50": 0.001, "device_p99": 0.002,
            "cache_hits": hits, "cache_misses": misses,
            "cache_hit_rate": hits / total if total else 0.0,
            "cache_entries": 64, "cache_bytes": 4096,
            "cache_evictions": 10, "cache_invalidations": 0,
            "cache_inserts_dropped": 0,
        }

    sick = tmp_path / "sick.jsonl"
    sick.write_text("\n".join(json.dumps(r) for r in [
        header,
        shed_row(bid_shed=4, be_shed=0, be_adm=10),
        stats_row(1.0, hits=0, misses=200),   # warmup window: exempt
        stats_row(2.0, hits=5, misses=195),   # post-warmup: thrash
    ]) + "\n")
    rc = main(["doctor", str(sick)])
    text = capsys.readouterr().out
    assert rc == 1
    assert "qos_inversion" in text
    assert "scache_thrash" in text

    healthy = tmp_path / "healthy.jsonl"
    healthy.write_text("\n".join(json.dumps(r) for r in [
        header,
        shed_row(bid_shed=0, be_shed=6, be_adm=4),
        stats_row(1.0, hits=0, misses=200),
        stats_row(2.0, hits=150, misses=50),
    ]) + "\n")
    assert main(["doctor", str(healthy)]) == 0
    text = capsys.readouterr().out
    assert "qos_inversion:" not in text
    assert "scache_thrash:" not in text

    # summarize: per-class shed + cache hit-rate lines
    assert main(["summarize", str(healthy)]) == 0
    text = capsys.readouterr().out
    assert "serve shed:" in text
    assert "best_effort" in text
    assert "score cache:" in text
    assert "hit rate" in text

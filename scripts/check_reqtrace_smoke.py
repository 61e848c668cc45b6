"""Request-trace smoke lint: train a toy ranker, serve it on a
2-replica fleet with request-scoped tracing armed, drive zipf traffic,
and validate everything the tracing spine promises
(docs/OBSERVABILITY.md "Tracing a request"):

* **0 errors, 0 recompiles** — tracing adds span stamps, never
  compiles or failures: the loadgen run answers everything and the
  fleet's compile count is unchanged from warm;
* **complete span trees** — every sampled request's ``reqtrace`` row
  has the full phase vocabulary, its phases sum to its e2e exactly
  (chain-fill), and its batch reference resolves to a batch span that
  fans the trace id in;
* **client/server agreement** — the ``serve_bench`` row's
  ``slowest_exemplars`` carry server-side phase breakdowns whose sum
  is within 10% (plus a 2 ms scheduler-noise floor) of the
  client-observed e2e;
* **tail sampling contract** — at ``sample=0.0`` a window still keeps
  the slowest-k exemplars, and error/shed spans are always kept;
* **front-door propagation** — a trace id sent on the XFS2 packed
  wire and as an ``X-XFlow-Trace`` header comes back on the response;
* **doctor attribution** — ``obs doctor`` stays clean on the healthy
  stream and raises ``reqtrace_tail`` naming the **device** phase on a
  run with an injected device-side slowdown.  The slowdown is injected
  by wrapping ``predict_prepared`` with a sleeping delegator rather
  than the ``serve.replica_score`` failpoint: the chaos fabric's
  failpoints RAISE (error path — covered by the sampling contract
  above), and tail attribution needs slow-but-successful requests.
* **schema** — both metrics streams (``reqtrace`` rows included) pass
  obs/schema.py strictly.

Two of these read the host's clock and not the program: whether the
healthy leg HAS a tail (one request that the scheduler held for 150 ms
under six test workers failed the driver's tier-1 runs of PRs 33 and 40)
and how long after the server's span the client's thread woke.  Each has
an order that must hold on any host, and that is checked as a value: a
server span lies inside its client's (server sum <= client e2e), and a
tail that the doctor reports is one the client saw too (the server's
slowest three are not slower than the client's slowest three).  Past the
order, a healthy leg that only read as slow is run again on a new fleet,
up to ``HEALTHY_ATTEMPTS`` times; schema, span trees, errors, recompiles,
a tail the client did not see and every other value fail at once.

Run from the repo root:

    JAX_PLATFORMS=cpu python scripts/check_reqtrace_smoke.py

Wired into tier-1 via tests/test_reqtrace.py::test_check_reqtrace_smoke_script,
like check_serve_smoke.py / check_cascade_smoke.py.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BUCKETS = (8, 64)
SLOW_SLEEP_S = 0.08  # injected device-side stall, every 8th batch
PHASE_SUM_TOL = 1e-4  # rounding slack: phases round to 1e-6 s each
HEALTHY_ATTEMPTS = 5  # healthy legs run before a slow host is believed


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import http.client

    import numpy as np

    from tests.gen_data import generate_dataset
    from xflow_tpu.config import Config
    from xflow_tpu.obs.doctor import (
        REQTRACE_SLOW_K,
        REQTRACE_TAIL_MIN_EXCESS_S,
        diagnose,
    )
    from xflow_tpu.obs.reqtrace import PHASES, ReqTraceSink
    from xflow_tpu.obs.schema import load_jsonl, validate_rows
    from xflow_tpu.serve.artifact import export_artifact
    from xflow_tpu.serve.fleet import ReplicaFleet
    from xflow_tpu.serve.loadgen import run_loadgen, zipf_rows
    from xflow_tpu.serve.server import (
        ServeTier,
        decode_packed_response,
        encode_packed_request,
    )
    from xflow_tpu.trainer import Trainer
    from xflow_tpu.utils.logging import MetricsLogger

    errors: list[str] = []
    with tempfile.TemporaryDirectory() as root:
        ds = generate_dataset(
            os.path.join(root, "data"),
            num_train_shards=2,
            lines_per_shard=150,
            num_fields=10,
            vocab_per_field=8,
            seed=11,
            scale=3.0,
        )
        cfg = Config(
            model="dcn",
            train_path=ds.train_prefix,
            test_path=ds.test_prefix,
            epochs=1,
            batch_size=64,
            table_size_log2=14,
            max_nnz=24,
            max_fields=10,
            num_devices=1,
        )
        tr = Trainer(cfg)
        tr.train()
        art = export_artifact(tr, os.path.join(root, "artifact"))

        # generous admission budgets: CPU toy device calls are tens of
        # ms, so production deadlines would shed healthy traffic — the
        # smoke asserts full service; shed-path sampling is exercised
        # at the sink level below
        admission = dict(deadline_budget_ms=5000.0, depth_budget=1024)

        def request_rows(rows):
            return [
                r for r in rows
                if r.get("kind") == "reqtrace" and r.get("span") == "request"
            ]

        def check_trees(rows, where):
            """Every request span: full phase vocabulary, phases sum
            to e2e, batch reference resolves and fans the id in."""
            batches = {
                r["batch"]: r for r in rows
                if r.get("kind") == "reqtrace" and r.get("span") == "batch"
            }
            reqs = request_rows(rows)
            if not reqs:
                errors.append(f"{where}: no reqtrace request rows")
                return
            for r in reqs:
                if tuple(sorted(r["phases"])) != tuple(sorted(PHASES)):
                    errors.append(
                        f"{where}: trace {r.get('trace_id')} phase keys "
                        f"{sorted(r['phases'])} != {sorted(PHASES)}"
                    )
                    continue
                gap = abs(sum(r["phases"].values()) - r["e2e"])
                if gap > PHASE_SUM_TOL:
                    errors.append(
                        f"{where}: trace {r.get('trace_id')} phases sum "
                        f"off e2e by {gap:.6f}s"
                    )
                if r.get("status") == "ok":
                    b = batches.get(r.get("batch"))
                    if b is None:
                        errors.append(
                            f"{where}: trace {r.get('trace_id')} batch "
                            f"{r.get('batch')!r} has no batch span"
                        )
                    elif r["trace_id"] not in b["trace_ids"]:
                        errors.append(
                            f"{where}: batch {r.get('batch')!r} does not "
                            f"fan in trace {r['trace_id']}"
                        )
            for b in batches.values():
                if len({b["digest"]}) != 1 or not b["digest"]:
                    errors.append(f"{where}: batch {b['batch']} digest odd")

        # ---- healthy leg: loadgen, sample=1.0 (every tree emitted) ----
        def healthy_leg(path):
            """A fresh traced fleet under the loadgen: (fleet, logger,
            client-observed e2e ms of its slowest requests, timing
            complaints).  Wrong values go to ``errors``."""
            logger = MetricsLogger(path, run_header={
                "run_id": "reqtrace-smoke",
                "config_digest": "smoke",
                "rank": 0,
                "num_hosts": 1,
            })
            fleet = ReplicaFleet.load(
                art, replicas=2, buckets=BUCKETS, metrics_logger=logger,
                **admission,
            )
            fleet.reqtrace = ReqTraceSink(metrics_logger=logger, sample=1.0)
            fleet.log_load(art)
            compiles_warm = fleet.engines[0].compile_count
            summary = run_loadgen(
                fleet,
                offered_qps=60.0,
                duration_s=2.0,
                concurrency=4,
                nnz=8,
                zipf_a=1.3,
                seed=5,
                metrics_logger=logger,
            )
            if summary["errors"]:
                errors.append(f"healthy loadgen errors: {summary['errors']}")
            if summary["requests"] < 20:
                errors.append(
                    f"healthy loadgen answered only {summary['requests']} "
                    "requests — too few to judge anything"
                )
            if fleet.engines[0].compile_count != compiles_warm:
                errors.append(
                    "tracing recompiled the fleet: "
                    f"{compiles_warm} -> {fleet.engines[0].compile_count}"
                )
            exemplars = summary.get("slowest_exemplars") or []
            if not exemplars:
                errors.append("serve_bench summary has no slowest_exemplars")
            with_phases = [e for e in exemplars if "phases_ms" in e]
            if not with_phases:
                errors.append(
                    "no slowest exemplar resolved a server-side phase "
                    f"breakdown: {exemplars}"
                )
            timing = []
            for e in with_phases:
                client = e["e2e_ms"]
                server = sum(e["phases_ms"].values())
                if server > client + 1e3 * PHASE_SUM_TOL:
                    # the server's span is opened after the client's
                    # clock starts and closed before the Future resolves
                    errors.append(
                        f"exemplar {e['trace_id']}: server phase sum "
                        f"{server:.3f}ms OVER client e2e {client:.3f}ms"
                    )
                elif client - server > max(0.10 * client, 2.0):
                    timing.append(
                        f"exemplar {e['trace_id']}: client e2e "
                        f"{client:.3f}ms, server phase sum {server:.3f}ms "
                        "(>10% + 2ms later: the client's thread woke late)"
                    )
            return fleet, logger, [e["e2e_ms"] for e in exemplars], timing

        def judge_healthy(path, client_ms, timing):
            """A closed healthy stream: schema and span trees as values,
            then the doctor: clean, or a tail that the client's own clock
            saw as well (the host was slow: timing), or a tail it did not
            see (the doctor is wrong: an error)."""
            rows = load_jsonl(path)
            errors.extend(f"healthy schema: {e}" for e in validate_rows(rows))
            check_trees(rows, "healthy")
            tail = [d for d in diagnose(rows) if d.code == "reqtrace_tail"]
            if not tail:
                return
            top = sorted(client_ms)[-REQTRACE_SLOW_K:]
            text = (
                f"doctor tail-attribution fired on the healthy run: "
                f"{tail[0].message[:160]}"
            )
            if sum(top) / len(top) < 1e3 * REQTRACE_TAIL_MIN_EXCESS_S:
                errors.append(
                    f"{text} — and the client's slowest requests took "
                    f"{top} ms: no such tail was there"
                )
            else:
                timing.append(text)

        healthy = os.path.join(root, "healthy.jsonl")
        fleet, logger, client_ms, timing = healthy_leg(healthy)

        # ---- front door: trace id rides wire + header and echoes ------
        tier = ServeTier(fleet, port=0).start()
        ctx = fleet.reqtrace.mint()
        row = zipf_rows(
            np.random.default_rng(9), 1, table_size=cfg.table_size,
            nnz=8, max_fields=cfg.max_fields,
        )[0]
        conn = http.client.HTTPConnection("127.0.0.1", tier.port,
                                          timeout=30)
        t_sent = time.perf_counter()
        conn.request(
            "POST", "/v1/score_packed",
            body=encode_packed_request([row], trace=ctx),
            headers={"Content-Type": "application/octet-stream"},
        )
        resp = conn.getresponse()
        payload = resp.read()
        client_ms.append(1e3 * (time.perf_counter() - t_sent))
        echoed = resp.getheader("X-XFlow-Trace") or ""
        if resp.status != 200:
            errors.append(f"packed trace request HTTP {resp.status}")
        else:
            decode_packed_response(payload)
        if not echoed.startswith(f"{ctx.trace_id:016x}-"):
            errors.append(
                f"packed wire trace not echoed: {echoed!r} vs "
                f"{ctx.trace_id:016x}"
            )
        ctx2 = fleet.reqtrace.mint()
        t_sent = time.perf_counter()
        conn.request(
            "POST", "/v1/score",
            body=json.dumps({
                "keys": [int(k) for k in row[0]],
                "slots": [int(s) for s in row[1]],
            }).encode(),
            headers={
                "Content-Type": "application/json",
                "X-XFlow-Trace":
                    f"{ctx2.trace_id:016x}-0000000000000000-1",
            },
        )
        resp = conn.getresponse()
        resp.read()
        client_ms.append(1e3 * (time.perf_counter() - t_sent))
        echoed = resp.getheader("X-XFlow-Trace") or ""
        if not echoed.startswith(f"{ctx2.trace_id:016x}-"):
            errors.append(
                f"header trace not echoed: {echoed!r} vs "
                f"{ctx2.trace_id:016x}"
            )
        conn.close()
        fleet.emit_stats()  # flush the front-door spans into the stream

        # ---- sampling contract: sample=0 keeps slowest-k + errors -----
        sink0 = ReqTraceSink(sample=0.0, slow_k=3)
        fleet.reqtrace = sink0
        rows30 = zipf_rows(
            np.random.default_rng(13), 30, table_size=cfg.table_size,
            nnz=8, max_fields=cfg.max_fields,
        )
        for r in rows30:
            fleet.submit(*r).result(timeout=60)
        err_span = sink0.start(None, "score")
        sink0.complete(err_span, "error", detail="injected")
        shed_span = sink0.start(None, "score")
        sink0.complete(shed_span, "shed", detail="deadline_budget")
        kept = sink0.flush()
        kept_reqs = [r for r in kept if r["span"] == "request"]
        by_keep: dict[str, int] = {}
        for r in kept_reqs:
            by_keep[r["keep"]] = by_keep.get(r["keep"], 0) + 1
        if by_keep.get("slow", 0) != 3:
            errors.append(
                f"sample=0 window kept {by_keep.get('slow', 0)} slow "
                f"exemplars, want 3 (keeps: {by_keep})"
            )
        if by_keep.get("error", 0) != 1 or by_keep.get("shed", 0) != 1:
            errors.append(
                f"sample=0 window dropped error/shed spans: {by_keep}"
            )
        if by_keep.get("head", 0):
            errors.append(f"sample=0 window head-kept spans: {by_keep}")

        # ---- healthy stream: schema + trees + doctor stays clean ------
        tier.close()  # drains and closes the fleet
        logger.close()
        judge_healthy(healthy, client_ms, timing)
        for n in range(2, HEALTHY_ATTEMPTS + 1):
            if errors or not timing:
                break
            print(
                f"healthy leg {n - 1} of {HEALTHY_ATTEMPTS} read the host's "
                f"clock, not the program: {timing}", file=sys.stderr,
            )
            again = os.path.join(root, f"healthy{n}.jsonl")
            fleet, logger, client_ms, timing = healthy_leg(again)
            fleet.close()
            logger.close()
            judge_healthy(again, client_ms, timing)
        if not errors:  # then ``timing`` is the last of that many legs
            errors.extend(
                f"on each of {HEALTHY_ATTEMPTS} healthy legs: {t}"
                for t in timing
            )

        # ---- slow leg: injected device stall -> doctor names device ---
        slow = os.path.join(root, "slow.jsonl")
        slogger = MetricsLogger(slow, run_header={
            "run_id": "reqtrace-smoke-slow",
            "config_digest": "smoke",
            "rank": 0,
            "num_hosts": 1,
        })
        fleet2 = ReplicaFleet.load(
            art, replicas=2, buckets=BUCKETS, metrics_logger=slogger,
            **admission,
        )
        fleet2.reqtrace = ReqTraceSink(metrics_logger=slogger, sample=1.0)
        calls = itertools.count()
        for eng in fleet2.engines:
            orig = eng.predict_prepared

            def slow_call(batch, _orig=orig):
                if next(calls) % 8 == 0:
                    time.sleep(SLOW_SLEEP_S)
                return _orig(batch)

            eng.predict_prepared = slow_call
        rows40 = zipf_rows(
            np.random.default_rng(17), 40, table_size=cfg.table_size,
            nnz=8, max_fields=cfg.max_fields,
        )
        for r in rows40:  # sequential: one batch per request
            fleet2.submit(*r).result(timeout=60)
        fleet2.emit_stats()
        fleet2.close()
        slogger.close()
        srows = load_jsonl(slow)
        errors.extend(f"slow schema: {e}" for e in validate_rows(srows))
        check_trees(srows, "slow")
        stail = [d for d in diagnose(srows) if d.code == "reqtrace_tail"]
        if not stail:
            errors.append(
                "doctor missed the injected device stall: no "
                "reqtrace_tail finding on the slow stream"
            )
        elif "device phase" not in stail[0].message:
            errors.append(
                "doctor misattributed the injected device stall: "
                f"{stail[0].message[:200]}"
            )

    if errors:
        print("check_reqtrace_smoke: FAIL", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    print(
        "check_reqtrace_smoke: OK (0 errors, 0 recompiles with tracing "
        "on, complete span trees with phase sums matching e2e, "
        "client/server exemplar agreement, slowest-k + error/shed kept "
        "at sample=0, wire+header trace echo, doctor clean on healthy "
        "and device-attributed on the injected stall)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

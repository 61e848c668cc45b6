"""``obs merge`` + ``obs doctor`` — first-responder forensics.

``merge`` combines per-host metrics JSONL files into one rank-tagged,
time-aligned stream: every row gains ``rank`` (from its file's
``run_start`` header) and ``time_unix`` (the header's wall-clock epoch
plus the row's relative ``t``), and rows sort globally by that clock.
Extra fields are schema-compatible (validators check required fields
only), so a merged file still passes ``obs validate``.

``doctor`` reads one run stream (merged or single-host) and optionally a
flight dump (obs/flight.py), and prints a RANKED diagnosis instead of
raw JSONL:

* watchdog ``health`` rows → dominant stall cause with trip counts and
  worst silence;
* SLO ``alert`` rows (obs/live.py burn-rate evaluator) → rules still
  firing at end of stream (warn) vs fired-and-resolved (info);
* flight dump → why the run died and what every thread was doing;
* phase accounting → the dominant wall-clock phase, with an
  input-bound callout when stalls dominate;
* per-rank step-time skew → straggler host callout (merged streams);
* per-stream input fan-out skew (``stream`` rows, io/fanout.py) →
  stream-straggler callout, same 1.3x rule on active throughput;
* step-time shape → bimodality (p99 ≫ p50 while p90 stays near p50)
  as recompile suspicion;
* serving tier → shed-storm windows (``serve_shed`` rows where
  admission control rejected most offered traffic — blamed on
  capacity, explicitly NOT on the queue) and canary-stuck rollouts
  (a ``rollout`` stream that ends on ``begin``/``canary``);
* retrieval→ranking cascade → candidate starvation (``cascade`` rows
  where the retrieval stage answered with fewer than the requested k)
  and per-stage p99 attribution (a slow cascade blames the right
  fleet by name);
* continuous training → servable-stale streams (``freshness`` rows,
  docs/CONTINUOUS.md): last newest-event-age over its SLO, rollouts
  repeatedly aborting, or begins that never commit;
* chaos fabric → ``chaos`` rows correlated with the self-healing
  ``health`` causes: fault storm vs isolated recovery, with
  ``quarantine_budget_exceeded`` (data corruption, not an input
  stall) and unrevived ``replica_evicted`` blamed by name
  (docs/ROBUSTNESS.md).

Severity ranks ``crit`` > ``warn`` > ``info``; the CLI exits 0 only
when nothing at ``warn`` or above surfaced — "run one command, get a
verdict" (scripts/check_doctor_smoke.py gates the healthy-run path).
"""

from __future__ import annotations

import json
from dataclasses import dataclass

from xflow_tpu.obs.schema import load_jsonl, load_jsonl_tolerant
from xflow_tpu.obs.summary import split_runs

# straggler: slowest rank's mean step-time p50 vs the fleet median
STRAGGLER_RATIO = 1.3
# input-bound: TIME-WEIGHTED input_stall fraction of total wall-clock
# (0.44 steady-state stall is normal for a CPU toy run; 0.5+ of the
# whole run means the device mostly waited)
INPUT_BOUND_FRAC = 0.5
# bimodality: p99 >= BIMODAL_P99 * p50 while p90 <= BIMODAL_P90 * p50
# (a fat smooth tail raises p90 too; a recompile spike does not).
# Each run's FIRST epoch row is exempt — it legitimately contains the
# process's one-time XLA compile, which IS a giant outlier step.
BIMODAL_P99 = 3.0
BIMODAL_P90 = 1.5
# ... and the spike must be material in ABSOLUTE terms: on
# millisecond-scale toy steps, OS scheduler noise on a loaded CI box
# alone produces 3x-p50 tails (observed flaking the tier-1 doctor
# smoke at a 10ms floor too — a 5.5ms-p50 toy run under full-suite
# load showed a 19.9ms p99, pure scheduler noise), while a real XLA
# recompile costs tens of ms at minimum.
BIMODAL_MIN_EXCESS_S = 0.025
# store-thrash: a tiered run (``store`` rows, docs/STORE.md) whose hot
# tier still serves under this occurrence share AFTER the warmup epoch
# while promotions/demotions keep churning — the working set does not
# fit the configured hot capacity.
STORE_THRASH_HIT_RATE = 0.5
# shed storm: a ``serve_shed`` window (serve/fleet.py admission
# control) where rejections dominate offered traffic.  The floor on
# absolute sheds keeps a 3-request toy window from reading as a storm.
SHED_STORM_FRAC = 0.5
SHED_STORM_MIN_TOTAL = 20

# tail attribution over reqtrace request spans (obs/reqtrace.py): the
# slowest-k exemplars must be BOTH a multiple of the all-request p50
# and a hard absolute excess above it before the tail is called
# anomalous — the ratio alone trips on millisecond scheduler noise in
# toy runs, the floor alone trips on any genuinely slow tier.  The
# row floor keeps a handful of warmup requests from electing a
# dominant phase.
REQTRACE_MIN_REQUESTS = 20
REQTRACE_TAIL_RATIO = 3.0
REQTRACE_TAIL_MIN_EXCESS_S = 0.05
REQTRACE_SLOW_K = 3

# health causes owned by the self-healing fabric (xflow_tpu/chaos/,
# docs/ROBUSTNESS.md): routed to _check_chaos for a named diagnosis —
# _check_health must NOT read them as watchdog stall trips (a
# quarantine abort is data corruption, not an input stall; an evicted
# replica is a capacity event, not a queue bug).
_SELF_HEAL_CAUSES = {
    "record_quarantined",
    "quarantine_budget_exceeded",
    "checkpoint_fallback",
    "checkpoint_save_failed",
    "replica_evicted",
    "replica_revived",
    "replica_revive_failed",
    "store_promote_restarted",
    "store_promote_dead",
}
# the subset that means "the fault was absorbed and service recovered"
_SELF_HEAL_RECOVERIES = {
    "replica_revived",
    "store_promote_restarted",
    "checkpoint_fallback",
}
# fault storm: this many injected/absorbed faults in one stream stops
# reading as "isolated recovery"
CHAOS_STORM_MIN = 10

_SEV_ORDER = {"crit": 0, "warn": 1, "info": 2}


@dataclass
class Diagnosis:
    severity: str  # "crit" | "warn" | "info"
    code: str  # short machine-greppable tag
    message: str


# -- merge ------------------------------------------------------------------


def merge_rows(paths: list[str]) -> list[dict]:
    """Rank-tagged, time-aligned union of per-host metrics files."""
    return merge_rows_tolerant(paths)[0]


def merge_rows_tolerant(paths: list[str]) -> tuple[list[dict], int]:
    """``merge_rows`` plus the count of torn final lines skipped: a
    file that is still being APPENDED to legitimately ends mid-line,
    and merging live files is exactly what `obs live` and a mid-run
    `obs merge` do.  Torn middles still raise (corruption)."""
    merged: list[dict] = []
    skipped = 0
    for path in paths:
        rows, torn = load_jsonl_tolerant(path)
        skipped += torn
        for run in split_runs(rows):
            header = run.header or {}
            rank = int(header.get("rank", 0))
            t0 = float(header.get("time_unix", 0.0))
            run_id = str(header.get("run_id", ""))
            rows = ([header] if run.header else []) + run.rows
            for row in rows:
                out = dict(row)
                out.setdefault("rank", rank)
                # run_id tag: time-sorting interleaves the per-host
                # streams, so split_runs no longer recovers run
                # membership — the explicit tag does
                out.setdefault("run_id", run_id)
                out.setdefault(
                    "time_unix", round(t0 + float(row.get("t", 0.0)), 3)
                )
                merged.append(out)
    merged.sort(key=lambda r: r.get("time_unix", 0.0))
    return merged, skipped


def write_jsonl(rows: list[dict], f) -> None:
    for row in rows:
        f.write(json.dumps(row, sort_keys=True) + "\n")


# -- doctor checks ----------------------------------------------------------


def _rank_of(row: dict, header_rank: int) -> int:
    return int(row.get("rank", header_rank))


def _epoch_rows(rows: list[dict]) -> list[tuple[int, dict]]:
    """(rank, train_epoch row) pairs across every run in the stream."""
    out = []
    for run in split_runs(rows):
        hr = int((run.header or {}).get("rank", 0))
        for e in run.epochs:
            out.append((_rank_of(e, hr), e))
    return out


def _warm_epoch_rows(rows: list[dict]) -> list[dict]:
    """train_epoch rows EXCLUDING each (rank, run)'s first one: every
    fresh process (initial or resumed — a resume is a new run_start)
    pays the one-time XLA compile in its first epoch, which is a
    legitimate giant step-time outlier, not a recompile bug.

    Grouping is by the rows' rank/run_id tags when present (merged
    streams interleave hosts by wall-clock, so split_runs alone puts
    every row in the LAST header's run and would exempt only one
    host's warmup); unmerged files fall back to split_runs order."""
    groups: dict = {}
    for i, run in enumerate(split_runs(rows)):
        header = run.header or {}
        hr = header.get("rank", 0)
        hid = header.get("run_id", i)
        for e in run.epochs:
            key = (e.get("rank", hr), e.get("run_id", hid))
            groups.setdefault(key, []).append(e)
    out = []
    for epochs in groups.values():
        # stream order is time order (merge sorts; single files append)
        out.extend(epochs[1:])
    return out


def _check_health(rows: list[dict]) -> list[Diagnosis]:
    trips: dict[str, list[dict]] = {}
    recovered: dict[str, float] = {}
    for r in rows:
        if r.get("kind") != "health":
            continue
        cause = r.get("cause", "?")
        if cause in _SELF_HEAL_CAUSES:
            continue  # _check_chaos owns the named diagnosis
        if cause.startswith("recovered:"):
            orig = cause.split(":", 1)[1]
            recovered[orig] = max(
                recovered.get(orig, 0.0), float(r.get("silence_seconds", 0))
            )
        else:
            trips.setdefault(cause, []).append(r)
    out = []
    for cause, events in sorted(
        trips.items(), key=lambda kv: -len(kv[1])
    ):
        worst = max(
            [float(e.get("silence_seconds", 0)) for e in events]
            + [recovered.get(cause, 0.0)]
        )
        ranks = sorted({_rank_of(e, 0) for e in events})
        out.append(Diagnosis(
            "crit",
            cause,
            f"watchdog tripped {len(events)}x: {cause} on channel "
            f"{events[-1].get('channel', '?')!r} (worst silence "
            f"{worst:.1f}s over threshold "
            f"{events[-1].get('threshold_seconds', 0)}s, rank(s) "
            f"{ranks})",
        ))
    dumps = [r for r in rows if r.get("kind") == "flight_dump"]
    for d in dumps:
        out.append(Diagnosis(
            "info",
            "flight_dump_row",
            f"flight dump recorded at {d.get('path', '?')} (reason "
            f"{d.get('reason', '?')!r}, active phase "
            f"{d.get('active_phase', '?')!r}) — pass it via --flight "
            "for thread stacks",
        ))
    return out


def _check_alerts(rows: list[dict]) -> list[Diagnosis]:
    """``alert`` rows (obs/live.py AlertEvaluator) as first-class
    evidence: a rule whose LAST transition is still ``firing`` is an
    open problem; a fire→resolve pair is context worth naming (the
    SLO was breached mid-run even though it recovered)."""
    last: dict[str, dict] = {}
    fired: dict[str, int] = {}
    for r in rows:
        if r.get("kind") != "alert":
            continue
        rule = str(r.get("rule", "?"))
        last[rule] = r
        if r.get("state") == "firing":
            fired[rule] = fired.get(rule, 0) + 1
    out = []
    for rule, r in sorted(last.items()):
        n = fired.get(rule, 0)
        if r.get("state") == "firing":
            out.append(Diagnosis(
                "warn",
                "alert_firing",
                f"alert {rule} is FIRING (fired {n}x, last value "
                f"{r.get('value')} vs threshold {r.get('threshold')} "
                f"over {r.get('short_s')}s/{r.get('long_s')}s "
                f"windows): {r.get('detail', '')}",
            ))
        elif n:
            out.append(Diagnosis(
                "info",
                "alert_resolved",
                f"alert {rule} fired {n}x and resolved (last value "
                f"{r.get('value')} vs threshold "
                f"{r.get('threshold')}) — the SLO was breached "
                "mid-run even though it recovered",
            ))
    return out


def _check_phases(rows: list[dict]) -> list[Diagnosis]:
    epochs = [e for _, e in _epoch_rows(rows)]
    if not epochs:
        return []
    totals: dict[str, float] = {}
    wall = 0.0
    for e in epochs:
        wall += float(e.get("seconds", 0.0))
        for k, v in (e.get("phases") or {}).items():
            totals[k] = totals.get(k, 0.0) + float(v)
    if not totals or wall <= 0:
        return []
    name, secs = max(totals.items(), key=lambda kv: kv[1])
    out = [Diagnosis(
        "info",
        "dominant_phase",
        f"dominant phase: {name} ({secs:.2f}s, {100 * secs / wall:.0f}% "
        f"of {wall:.2f}s wall over {len(epochs)} epoch row(s))",
    )]
    stall = totals.get("input_stall", 0.0) / wall  # time-weighted
    if stall >= INPUT_BOUND_FRAC:
        out.append(Diagnosis(
            "warn",
            "input_bound",
            f"input-bound: input_stall is {100 * stall:.0f}% of total "
            "wall-clock — the device is waiting on data (check loader "
            "throughput in the shard rows, parse workers, prefetch "
            "depth)",
        ))
    return out


def _check_stragglers(rows: list[dict]) -> list[Diagnosis]:
    per_rank: dict[int, list[float]] = {}
    for rank, e in _epoch_rows(rows):
        p50 = float(e.get("step_time_p50", 0.0))
        if p50 > 0:
            per_rank.setdefault(rank, []).append(p50)
    if len(per_rank) < 2:
        return []
    means = {
        rank: sum(v) / len(v) for rank, v in per_rank.items()
    }
    # lower-middle median: with an even rank count (2 hosts being the
    # common case) the candidate straggler must compare against the
    # FASTER half, not against itself
    ordered = sorted(means.values())
    median = ordered[(len(ordered) - 1) // 2]
    if median <= 0:
        return []
    worst_rank, worst = max(means.items(), key=lambda kv: kv[1])
    if worst < median * STRAGGLER_RATIO:
        return [Diagnosis(
            "info",
            "rank_skew",
            f"step-time skew across {len(means)} ranks is "
            f"{worst / median:.2f}x (max/median) — within the "
            f"{STRAGGLER_RATIO}x straggler threshold",
        )]
    return [Diagnosis(
        "warn",
        "straggler",
        f"straggler: rank {worst_rank} mean step-time p50 "
        f"{1e3 * worst:.2f}ms is {worst / median:.2f}x the fleet "
        f"median ({1e3 * median:.2f}ms) across {len(means)} ranks — "
        "every synced step waits for it (slow host, shard skew, or "
        "thermal throttling)",
    )]


def _check_streams(rows: list[dict]) -> list[Diagnosis]:
    """Input fan-out stream skew (``stream`` rows, io/fanout.py) —
    the per-rank straggler rule applied to reader streams: a stream
    whose ACTIVE throughput (examples over its measured
    read+parse+compact seconds — a stream parked behind a saturated
    consumer is not slow) lags the stream median by the straggler
    ratio holds the whole serial-order merge back, because every
    later shard it owns gates the consumer."""
    per_stream: dict[int, list[float]] = {}
    for r in rows:
        if r.get("kind") != "stream":
            continue
        eps = float(r.get("examples_per_sec", 0.0))
        if eps > 0:
            per_stream.setdefault(int(r.get("stream", 0)), []).append(eps)
    if len(per_stream) < 2:
        return []
    means = {s: sum(v) / len(v) for s, v in per_stream.items()}
    # upper-middle median: the candidate straggler (SLOWEST stream)
    # must compare against the faster half, mirroring _check_stragglers
    ordered = sorted(means.values())
    median = ordered[len(ordered) // 2]
    worst_stream, worst = min(means.items(), key=lambda kv: kv[1])
    if worst <= 0 or median <= 0:
        return []
    ratio = median / worst
    if ratio < STRAGGLER_RATIO:
        return [Diagnosis(
            "info",
            "stream_skew",
            f"input-stream throughput skew across {len(means)} streams "
            f"is {ratio:.2f}x (median/min) — within the "
            f"{STRAGGLER_RATIO}x straggler threshold",
        )]
    return [Diagnosis(
        "warn",
        "stream_straggler",
        f"input-stream straggler: stream {worst_stream} mean "
        f"{worst:.0f} ex/s is {ratio:.2f}x slower than the stream "
        f"median ({median:.0f} ex/s) across {len(means)} streams — "
        "the serial-order merge waits on every shard it owns (shard "
        "size skew, a slow disk, or parse contention; stall_seconds "
        "in its stream rows says whether it was actually consumer-"
        "bound)",
    )]


def _check_bimodality(rows: list[dict]) -> list[Diagnosis]:
    suspect = []
    for e in _warm_epoch_rows(rows):
        p50 = float(e.get("step_time_p50", 0.0))
        p90 = float(e.get("step_time_p90", 0.0))
        p99 = float(e.get("step_time_p99", 0.0))
        if (
            p50 > 0
            and p99 >= BIMODAL_P99 * p50
            and p90 <= BIMODAL_P90 * p50
            and p99 - p50 >= BIMODAL_MIN_EXCESS_S
        ):
            suspect.append(e)
    if not suspect:
        return []
    e = suspect[-1]
    return [Diagnosis(
        "warn",
        "recompile_suspicion",
        f"step-time bimodality in {len(suspect)} epoch row(s): p99 "
        f"{1e3 * float(e['step_time_p99']):.1f}ms is "
        f"{float(e['step_time_p99']) / float(e['step_time_p50']):.1f}x "
        f"p50 while p90 stays near p50 — a few steps are wildly slower "
        "than the rest, the signature of silent recompiles (new batch "
        "shape?) or periodic interference; check XF001 and the span "
        "trace around the slow steps",
    )]


def _check_store(rows: list[dict]) -> list[Diagnosis]:
    """Tiered-store health from the ``store`` epoch rows.  Each run's
    FIRST store row is exempt: a cold start legitimately misses on
    everything while promotion fills the tier — thrash is a LOW hit
    rate that persists while the tier keeps churning."""
    warm: list[dict] = []
    for run in split_runs(rows):
        srows = [r for r in run.rows if r.get("kind") == "store"]
        warm.extend(srows[1:])
    bad = [
        r for r in warm
        if float(r.get("hot_hit_rate", 1.0)) < STORE_THRASH_HIT_RATE
        and (
            (int(r.get("promotions", 0)) + int(r.get("demotions", 0)))
            > 0
            # a SATURATED tier serving a too-large working set may
            # show zero churn (swap hysteresis blocks near-tie
            # evictions) — that is still the raise-hot-capacity
            # condition, not health
            or float(r.get("hot_occupancy", 0.0)) >= 0.99
        )
    ]
    if not bad:
        return []
    r = bad[-1]
    return [Diagnosis(
        "warn",
        "store_thrash",
        f"store-thrash in {len(bad)} epoch row(s): hot_hit_rate "
        f"{float(r['hot_hit_rate']):.2f} stayed below "
        f"{STORE_THRASH_HIT_RATE} after warmup while the tier churned "
        f"({r.get('promotions')} promotions / {r.get('demotions')} "
        f"demotions, occupancy {float(r.get('hot_occupancy', 0)):.2f} "
        f"in epoch {r.get('epoch')}) — the working set exceeds the hot "
        "tier; raise --hot-capacity-log2 or accept cold-fetch latency "
        "(docs/STORE.md)",
    )]


def _check_serve(
    rows: list[dict], queue_stall_tripped: bool = False
) -> list[Diagnosis]:
    """Serving-tier health from the fleet's ``serve_shed`` and
    ``rollout`` rows (serve/fleet.py, docs/SERVING.md).

    * **shed_storm** — a stats window where admission control rejected
      the majority of offered traffic: the tier is past capacity and
      the deadline budget is being defended at the door.  When the
      watchdog ALSO tripped serve_queue_stall in the same stream, the
      storm is named as the primary cause — the backlog is past its
      deadline budget *because* offered load exceeds capacity, so the
      fix is fleet size / offered QPS, not the queue.
    * **canary_stuck** — a run whose LAST ``rollout`` row is ``begin``
      or ``canary`` (the open-rollout heartbeat): the rollout never
      resolved to commit/abort — the process died or wedged
      mid-canary, and a fraction of traffic is still pinned to an
      uncommitted artifact."""
    out = []
    storms = [
        r for r in rows
        if r.get("kind") == "serve_shed"
        and float(r.get("shed_frac", 0.0)) >= SHED_STORM_FRAC
        and int(r.get("shed_total", 0)) >= SHED_STORM_MIN_TOTAL
    ]
    if storms:
        r = storms[-1]
        causes = ", ".join(
            f"{k}={v}" for k, v in sorted(
                (r.get("by_cause") or {}).items()
            )
        ) or "?"
        msg = (
            f"shed storm in {len(storms)} stats window(s): admission "
            f"control rejected {100 * float(r['shed_frac']):.0f}% of "
            f"offered traffic ({r.get('shed_total')} sheds vs "
            f"{r.get('admitted')} admitted; {causes}) — the tier is "
            "past capacity and defended the deadline budget at the "
            "door; add replicas or lower offered QPS (docs/SERVING.md)"
        )
        if queue_stall_tripped:
            msg += (
                "; the serve_queue_stall trip(s) above are this same "
                "capacity condition, not an independent queue bug"
            )
        out.append(Diagnosis("warn", "shed_storm", msg))
    for run in split_runs(rows):
        rrows = [r for r in run.rows if r.get("kind") == "rollout"]
        if rrows and rrows[-1].get("event") in ("begin", "canary"):
            r = rrows[-1]
            out.append(Diagnosis(
                "warn",
                "canary_stuck",
                f"canary-stuck rollout: the stream's last rollout row "
                f"is {r.get('event')!r} ({r.get('from_digest')} → "
                f"{r.get('to_digest')}, canary_frac "
                f"{r.get('canary_frac')}, {r.get('canary_requests')} "
                f"canary request(s), {r.get('canary_errors')} "
                "error(s)) with no commit/abort after it — the run "
                "ended mid-rollout; commit, abort, or redeploy so "
                "traffic converges on one artifact",
            ))
    return out


def _check_qos(rows: list[dict]) -> list[Diagnosis]:
    """QoS admission ordering from ``serve_shed`` rows that carry the
    per-class ``by_class`` split (serve/fleet.py QOS_CLASSES).

    * **qos_inversion** — a window where the BIDDING class shed
      traffic while best_effort shed nothing: the per-class budgets
      are supposed to make best_effort absorb pressure first and the
      bidding path shed last, so this ordering is inverted — the
      class budget fractions are misconfigured (best_effort's budget
      is not strictly tighter) or requests are mislabeled."""
    inverted = []
    for r in rows:
        if r.get("kind") != "serve_shed":
            continue
        by_class = r.get("by_class") or {}
        bid = by_class.get("bidding") or {}
        be = by_class.get("best_effort") or {}
        if (
            int(bid.get("shed", 0)) > 0
            and int(be.get("shed", 0)) == 0
            # only meaningful when best_effort traffic was offered at
            # all: an all-bidding workload shedding is plain overload
            and int(be.get("admitted", 0)) + int(be.get("shed", 0)) > 0
        ):
            inverted.append(r)
    if not inverted:
        return []
    r = inverted[-1]
    bid = (r.get("by_class") or {}).get("bidding") or {}
    return [Diagnosis(
        "warn",
        "qos_inversion",
        f"QoS inversion in {len(inverted)} stats window(s): the "
        f"bidding class shed {bid.get('shed')} request(s) while "
        "best_effort shed none despite carrying traffic — class "
        "shedding order is inverted (best_effort must absorb pressure "
        "first, bidding last); check serve_qos_best_effort_frac < "
        "serve_qos_normal_frac and client class labels "
        "(docs/SERVING.md)",
    )]


# scache_thrash gates (serve/scache.py windows in serve_stats rows)
SCACHE_THRASH_HIT_RATE = 0.1
SCACHE_MIN_TRAFFIC = 100
SCACHE_INVALIDATION_WINDOWS = 3


def _check_scache(rows: list[dict]) -> list[Diagnosis]:
    """Hot-key score-cache health from the cache_* fields the fleet
    folds into ``serve_stats`` windows (serve/scache.py).  Each run's
    FIRST cache window is exempt (a cold cache legitimately misses on
    everything — same warmup discipline as ``_check_store``).

    * **scache_thrash** — the hit rate stayed under
      ``SCACHE_THRASH_HIT_RATE`` with non-trivial traffic after
      warmup (the working set exceeds capacity, or traffic is not
      zipf-shaped enough to cache), or invalidations landed in
      ``SCACHE_INVALIDATION_WINDOWS``+ windows (rollouts churn the
      cache faster than it can warm) — either way the cache is
      costing memory without returning throughput."""
    warm: list[dict] = []
    invalidating = 0
    for run in split_runs(rows):
        crows = [
            r for r in run.rows
            if r.get("kind") == "serve_stats" and "cache_hits" in r
        ]
        warm.extend(crows[1:])
        invalidating += sum(
            1 for r in crows
            if int(r.get("cache_invalidations", 0)) > 0
        )
    cold = [
        r for r in warm
        if float(r.get("cache_hit_rate", 1.0)) < SCACHE_THRASH_HIT_RATE
        and (
            int(r.get("cache_hits", 0)) + int(r.get("cache_misses", 0))
        ) >= SCACHE_MIN_TRAFFIC
    ]
    out = []
    if cold:
        r = cold[-1]
        out.append(Diagnosis(
            "warn",
            "scache_thrash",
            f"score-cache thrash in {len(cold)} stats window(s): hit "
            f"rate {float(r.get('cache_hit_rate', 0.0)):.2f} stayed "
            f"below {SCACHE_THRASH_HIT_RATE} after warmup over "
            f"{int(r.get('cache_hits', 0)) + int(r.get('cache_misses', 0))} "
            f"lookups ({r.get('cache_entries')} entries, "
            f"{r.get('cache_evictions')} evictions) — the hot set "
            "exceeds serve_cache_capacity or the traffic is not "
            "skewed enough to cache; raise capacity or disable the "
            "cache (docs/SERVING.md)",
        ))
    elif invalidating >= SCACHE_INVALIDATION_WINDOWS:
        out.append(Diagnosis(
            "warn",
            "scache_thrash",
            f"score-cache churn: cache invalidations landed in "
            f"{invalidating} stats window(s) — rollouts are evicting "
            "the cache faster than it can warm, so it costs memory "
            "without returning throughput; batch the rollouts or "
            "disable the cache (docs/SERVING.md)",
        ))
    return out


def _median(vals: list[float]) -> float:
    s = sorted(vals)
    return s[len(s) // 2] if s else 0.0


def _check_reqtrace(
    rows: list[dict],
    shed_storm: bool = False,
    queue_stall: bool = False,
) -> list[Diagnosis]:
    """Tail-latency attribution from ``reqtrace`` request spans
    (obs/reqtrace.py, docs/OBSERVABILITY.md).

    * **reqtrace_tail** — the slowest-k requests' mean e2e sits far
      above the all-request p50 AND one phase explains the excess:
      per-phase, take the slow-k mean minus the all-request median
      (clamped at zero) and name the argmax.  The dominant phase is
      cross-checked against the capacity findings already made from
      serve_shed/watchdog rows: a queue-side phase (admission_wait,
      coalesce_wait) dominating alongside a shed storm or queue stall
      is the same capacity condition seen from inside a request; a
      device-dominated tail alongside those findings means the queue
      symptoms are downstream of a slow device call, so fixing
      admission or fleet size would treat the symptom.
    * **reqtrace_tail_ok** (info) — enough traced requests and the
      tail is within normal spread of the median: decomposition
      reported, nothing to fix."""
    reqs = [
        r for r in rows
        if r.get("kind") == "reqtrace" and r.get("span") == "request"
        and isinstance(r.get("phases"), dict) and "e2e" in r
    ]
    if len(reqs) < REQTRACE_MIN_REQUESTS:
        return []
    e2es = [float(r["e2e"]) for r in reqs]
    p50 = _median(e2es)
    slow = sorted(reqs, key=lambda r: float(r["e2e"]), reverse=True)
    slow = slow[:REQTRACE_SLOW_K]
    slow_mean = sum(float(r["e2e"]) for r in slow) / len(slow)
    phases = sorted({p for r in reqs for p in r["phases"]})
    med = {
        p: _median([float(r["phases"].get(p, 0.0)) for r in reqs])
        for p in phases
    }
    excess = {
        p: max(
            0.0,
            sum(float(r["phases"].get(p, 0.0)) for r in slow)
            / len(slow) - med[p],
        )
        for p in phases
    }
    if (
        slow_mean < REQTRACE_TAIL_RATIO * p50
        or slow_mean - p50 < REQTRACE_TAIL_MIN_EXCESS_S
        or not any(excess.values())
    ):
        return [Diagnosis(
            "info", "reqtrace_tail_ok",
            f"reqtrace: {len(reqs)} request span(s), p50 "
            f"{1e3 * p50:.1f}ms, slowest-{len(slow)} mean "
            f"{1e3 * slow_mean:.1f}ms — tail within normal spread; "
            "no phase attribution needed",
        )]
    dominant = max(excess, key=lambda p: excess[p])
    ids = ", ".join(r.get("trace_id", "?") for r in slow)
    decomp = ", ".join(
        f"{p}+{1e3 * excess[p]:.1f}ms" for p in phases if excess[p]
    )
    msg = (
        f"tail attribution: slowest-{len(slow)} requests average "
        f"{1e3 * slow_mean:.1f}ms vs p50 {1e3 * p50:.1f}ms over "
        f"{len(reqs)} traced request(s); the excess is dominated by "
        f"the {dominant} phase ({decomp}; exemplar trace(s) {ids})"
    )
    if dominant in ("admission_wait", "coalesce_wait"):
        if shed_storm or queue_stall:
            msg += (
                " — consistent with the shed/queue findings above: "
                "the tier is past capacity and requests pay for it "
                "in queue time; add replicas or lower offered QPS"
            )
        else:
            msg += (
                " — requests queue before reaching a device; raise "
                "fleet size or max_batch before blaming the model"
            )
    elif dominant == "device":
        if shed_storm or queue_stall:
            msg += (
                " — the shed/queue findings above are a symptom, not "
                "the cause: the device call itself slowed and the "
                "backlog followed; profile the engine, not admission"
            )
        else:
            msg += (
                " — the device call itself is slow for these "
                "requests; check bucket sizes and engine digests "
                "(docs/SERVING.md)"
            )
    elif dominant == "swap_stall":
        msg += (
            " — batches stalled waiting on the rollout swap lock; "
            "an artifact swap ran during the window (docs/SERVING.md)"
        )
    return [Diagnosis("warn", "reqtrace_tail", msg)]


def _check_cascade(rows: list[dict]) -> list[Diagnosis]:
    """Retrieval→ranking cascade health from the ``cascade`` stats
    windows (serve/cascade.py; docs/SERVING.md):

    * **candidate_starvation** — the retrieval stage answered requests
      with fewer candidates than the requested k (an index smaller
      than k, or a retrieval rollout that shrank it): the ranker is
      scoring a thinner slate than the caller asked for.
    * **cascade_errors** — requests failed inside a stage (warn; the
      per-fleet serve rows name the replica).
    * **cascade_stage_p99** — per-stage p99 attribution (info): which
      stage dominates the e2e tail, so a slow cascade blames the
      right fleet instead of "serving is slow"."""
    crows = [
        r for r in rows
        if r.get("kind") == "cascade" and int(r.get("requests", 0)) > 0
    ]
    if not crows:
        return []
    out: list[Diagnosis] = []
    starved = sum(int(r.get("starved", 0)) for r in crows)
    if starved:
        r = next(r for r in crows if int(r.get("starved", 0)))
        out.append(Diagnosis(
            "warn",
            "candidate_starvation",
            f"candidate starvation: {starved} request(s) got fewer "
            f"candidates than requested (k={r.get('k')}, mean "
            f"returned {r.get('k_returned_mean')}) — the retrieval "
            "index holds fewer items than k (or a rollout shrank "
            "it); re-export the item index or lower the cascade k "
            "(docs/SERVING.md)",
        ))
    errors = sum(int(r.get("errors", 0)) for r in crows)
    if errors:
        out.append(Diagnosis(
            "warn",
            "cascade_errors",
            f"{errors} cascade request(s) failed inside a stage — "
            "check the per-fleet serve_shed/health rows to see which "
            "stage's replicas raised",
        ))
    last = crows[-1]
    rp99 = float(last.get("retrieval_p99", 0.0))
    kp99 = float(last.get("rank_p99", 0.0))
    e2e = float(last.get("e2e_p99", 0.0))
    if e2e > 0:
        stage, worst = (
            ("retrieval", rp99) if rp99 >= kp99 else ("ranking", kp99)
        )
        # per-stage and e2e percentiles come from different request
        # populations (a stage-2 shed books retrieval but not e2e), so
        # the share is capped at 100% rather than reported as an
        # impossible 200%
        share = min(100.0, 100 * worst / e2e)
        out.append(Diagnosis(
            "info",
            "cascade_stage_p99",
            f"cascade p99 attribution: e2e {1e3 * e2e:.1f}ms ≈ "
            f"retrieval {1e3 * rp99:.1f}ms + ranking "
            f"{1e3 * kp99:.1f}ms — the {stage} stage dominates "
            f"({share:.0f}%); scale THAT fleet first",
        ))
    return out


def _check_freshness(rows: list[dict]) -> list[Diagnosis]:
    """Continuous-training freshness (stream/driver.py ``freshness``
    rows; docs/CONTINUOUS.md).  A stream run must not read as clean
    when its servable is stale:

    * the LAST freshness row's newest-event-age exceeds its SLO — the
      fleet is serving a model older than the decay budget;
    * rollouts repeatedly abort (>= 2 aborts after the last commit) —
      exports keep failing the canary gate, so freshness can only
      decay from here;
    * a rollout BEGAN and never committed in a stream run (the
      begin-with-no-commit case): the run produced servables it never
      shipped — _check_serve's canary_stuck names the wedged rollout,
      this names the freshness consequence."""
    out: list[Diagnosis] = []
    for run in split_runs(rows):
        fresh = [r for r in run.rows if r.get("kind") == "freshness"]
        if not fresh:
            continue
        last = fresh[-1]
        age = float(last.get("newest_event_age_s", 0.0))
        slo = float(last.get("slo_s", 0.0))
        if slo > 0 and age > slo:
            out.append(Diagnosis(
                "warn",
                "servable_stale",
                f"stale servable: the stream's last freshness row "
                f"({last.get('event')!r} at step {last.get('step')}) "
                f"reports newest-event-age {age:.1f}s over the "
                f"{slo:.0f}s SLO — ingested events are not reaching "
                "the serving fleet; check rollout aborts and export "
                "cadence (docs/CONTINUOUS.md)",
            ))
        aborts_since_commit = 0
        for r in fresh:
            if r.get("event") == "commit":
                aborts_since_commit = 0
            elif r.get("event") == "abort":
                aborts_since_commit += 1
        if aborts_since_commit >= 2:
            out.append(Diagnosis(
                "warn",
                "servable_stale",
                f"rollouts repeatedly aborting: "
                f"{aborts_since_commit} consecutive abort(s) since "
                "the last committed swap — every refresh is failing "
                "the canary health gate, so the serving fleet keeps "
                "aging; inspect the rollout rows' gate verdicts "
                "(docs/CONTINUOUS.md)",
            ))
        rrows = [r for r in run.rows if r.get("kind") == "rollout"]
        began = any(r.get("event") == "begin" for r in rrows)
        committed = any(r.get("event") == "commit" for r in rrows)
        if began and not committed:
            out.append(Diagnosis(
                "warn",
                "servable_stale",
                "stream run began rollout(s) but never committed one: "
                "exports were cut and canaried but no swap ever "
                "landed — the fleet still serves the original base "
                "while the model trains ahead (see the canary_stuck "
                "finding for the wedged rollout itself)",
            ))
    return out


def _check_chaos(rows: list[dict]) -> list[Diagnosis]:
    """Chaos-fabric forensics (xflow_tpu/chaos/, docs/ROBUSTNESS.md):
    correlate ``chaos`` rows (injected faults) with the self-healing
    ``health`` causes and rank what the run absorbed vs what stuck.

    * **fault storm vs isolated recovery** — a handful of injected
      faults all matched by recoveries is the chaos gate's healthy
      shape (info); many faults, or faults without recoveries, rank as
      a storm (warn).
    * **quarantine_budget_exceeded** — named crit: the stream was
      corrupt past the skip budget and the run aborted deliberately.
      This is DATA corruption, not an input stall — without this
      check its silence would misread as input_bound/input_stall.
    * **replica_evicted** — evictions matched by revivals are absorbed
      capacity events (info); unrevived evictions mean the fleet is
      still running short (warn)."""
    chaos_rows = [r for r in rows if r.get("kind") == "chaos"]
    causes: dict[str, int] = {}
    for r in rows:
        if r.get("kind") == "health":
            c = r.get("cause", "?")
            causes[c] = causes.get(c, 0) + 1
    out: list[Diagnosis] = []
    if causes.get("quarantine_budget_exceeded"):
        out.append(Diagnosis(
            "crit",
            "quarantine_budget_exceeded",
            f"input corruption exceeded the quarantine budget "
            f"({causes.get('record_quarantined', 0)} quarantined "
            "block(s)/record(s) before the abort): the run stopped "
            "deliberately rather than train on a corrupt stream — "
            "this is data corruption, NOT an input stall; check the "
            "shard files and the loader retry health rows "
            "(docs/ROBUSTNESS.md)",
        ))
    quarantined = causes.get("record_quarantined", 0)
    if quarantined and not causes.get("quarantine_budget_exceeded"):
        out.append(Diagnosis(
            "warn",
            "record_quarantined",
            f"{quarantined} block(s)/record(s) quarantined (under the "
            "abort budget): input corruption is being skipped — those "
            "samples never reached the model; check the shard files "
            "and the loader retry health rows (docs/ROBUSTNESS.md)",
        ))
    fallbacks = causes.get("checkpoint_fallback", 0)
    saves_failed = causes.get("checkpoint_save_failed", 0)
    if fallbacks and not saves_failed:
        out.append(Diagnosis(
            "warn",
            "checkpoint_fallback",
            f"restore fell back past {fallbacks} unusable "
            "generation(s) to an older complete one: training REWOUND "
            "— deliberate under `--resume auto`, but inspect the "
            "skipped generations (external corruption?) before the "
            "keep-last-N GC ages the survivors out "
            "(docs/ROBUSTNESS.md)",
        ))
    if saves_failed:
        out.append(Diagnosis(
            "warn",
            "checkpoint_save_failed",
            f"{saves_failed} checkpoint save(s) FAILED "
            f"({causes.get('checkpoint_fallback', 0)} restore "
            "fallback(s) seen): the run remains restorable from the "
            "newest complete generation (`--resume auto`), but fix "
            "the storage path before the retained generations age out "
            "(docs/ROBUSTNESS.md)",
        ))
    evicted = causes.get("replica_evicted", 0)
    revived = causes.get("replica_revived", 0)
    if evicted:
        if revived >= evicted and not causes.get("replica_revive_failed"):
            out.append(Diagnosis(
                "info",
                "replica_evicted",
                f"{evicted} replica eviction(s), all revived from the "
                "shared artifact — scoring errors were absorbed as "
                "capacity events (sheds during the gap are admission "
                "control doing its job, not a queue bug)",
            ))
        else:
            out.append(Diagnosis(
                "warn",
                "replica_evicted",
                f"replica(s) evicted and NOT fully revived "
                f"({evicted} evicted, {revived} revived, "
                f"{causes.get('replica_revive_failed', 0)} revive "
                "failure(s)) — the fleet is serving at reduced "
                "capacity; expect sheds until replicas return "
                "(docs/ROBUSTNESS.md)",
            ))
    if causes.get("store_promote_dead"):
        out.append(Diagnosis(
            "warn",
            "store_promote_dead",
            "the promotion worker died twice (one restart spent): "
            "tier placement is frozen — training stays correct but "
            "every new key rides the cold miss path; expect the hot "
            "hit rate to decay (docs/ROBUSTNESS.md, docs/STORE.md)",
        ))
    if chaos_rows:
        sites: dict[str, int] = {}
        for r in chaos_rows:
            s = r.get("site", "?")
            sites[s] = sites.get(s, 0) + 1
        n = len(chaos_rows)
        recoveries = sum(causes.get(c, 0) for c in _SELF_HEAL_RECOVERIES)
        recoveries += causes.get("recovered:io_retry", 0)
        per_site = ", ".join(
            f"{s}={c}" for s, c in sorted(sites.items())
        )
        unrecovered = any(d.severity in ("crit", "warn") for d in out)
        if n >= CHAOS_STORM_MIN or unrecovered:
            out.append(Diagnosis(
                "warn",
                "fault_storm",
                f"fault storm: {n} injected fault(s) across "
                f"{len(sites)} site(s) ({per_site}) with "
                f"{recoveries} recovery row(s) — the findings above "
                "name what did not heal",
            ))
        else:
            out.append(Diagnosis(
                "info",
                "chaos_absorbed",
                f"chaos fabric armed: {n} injected fault(s) "
                f"({per_site}) absorbed by self-healing "
                f"({recoveries} recovery row(s)) — isolated "
                "recovery, not a storm",
            ))
    return out


def _check_flight(flight: dict) -> list[Diagnosis]:
    reason = flight.get("reason", "?")
    phase = flight.get("active_phase", "")
    threads = flight.get("threads", [])
    record = flight.get("record", {})
    sev = "crit" if reason in ("exception", "watchdog") else "warn"
    msg = (
        f"flight dump: run ended by {reason!r} while in phase "
        f"{phase or '?'} at step {record.get('last_step', '?')} "
        f"(last checkpoint step: {record.get('last_checkpoint_step')}, "
        f"{len(threads)} thread stacks captured)"
    )
    exc = flight.get("exception")
    if exc:
        msg += f"; exception {exc.get('type')}: {exc.get('message')}"
    out = [Diagnosis(sev, f"flight_{reason}", msg)]
    chans = record.get("channels", {})
    if chans:
        ages = ", ".join(
            f"{ch} {info.get('detail', '?')!r} {info.get('age_seconds', 0):.1f}s ago"
            for ch, info in sorted(chans.items())
        )
        out.append(Diagnosis(
            "info", "flight_channels", f"last heartbeats at dump: {ages}"
        ))
    return out


def diagnose(
    rows: list[dict], flight: dict | None = None
) -> list[Diagnosis]:
    """Every check, ranked most-severe-first (stable within rank)."""
    findings: list[Diagnosis] = []
    findings.extend(_check_health(rows))
    findings.extend(_check_alerts(rows))
    findings.extend(_check_chaos(rows))
    findings.extend(_check_serve(
        rows,
        queue_stall_tripped=any(
            d.code == "serve_queue_stall" for d in findings
        ),
    ))
    findings.extend(_check_qos(rows))
    findings.extend(_check_scache(rows))
    findings.extend(_check_reqtrace(
        rows,
        shed_storm=any(d.code == "shed_storm" for d in findings),
        queue_stall=any(
            d.code == "serve_queue_stall" for d in findings
        ),
    ))
    findings.extend(_check_cascade(rows))
    findings.extend(_check_freshness(rows))
    if flight is not None:
        findings.extend(_check_flight(flight))
    findings.extend(_check_phases(rows))
    findings.extend(_check_stragglers(rows))
    findings.extend(_check_streams(rows))
    findings.extend(_check_bimodality(rows))
    findings.extend(_check_store(rows))
    preempted = sum(
        1 for _, e in _epoch_rows(rows) if e.get("preempted")
    )
    if preempted:
        findings.append(Diagnosis(
            "info", "preempted",
            f"{preempted} epoch row(s) ended by graceful preemption "
            "(resume with --resume)",
        ))
    findings.sort(key=lambda d: _SEV_ORDER.get(d.severity, 3))
    return findings


def format_diagnosis(
    path: str, rows: list[dict], findings: list[Diagnosis]
) -> str:
    ranks = sorted({
        int(r.get("rank", h.get("rank", 0)))
        for run in split_runs(rows)
        for h in [run.header or {}]
        for r in ([run.header] if run.header else []) + run.rows
    })
    out = [
        f"obs doctor — {path}: {len(rows)} rows, "
        f"{len(split_runs(rows))} run(s), rank(s) {ranks}"
    ]
    for d in findings:
        out.append(f"  [{d.severity.upper():4s}] {d.code}: {d.message}")
    problems = sum(1 for d in findings if d.severity in ("crit", "warn"))
    out.append(
        "diagnosis: clean (no crit/warn findings)"
        if not problems
        else f"diagnosis: {problems} problem(s) — ranked above"
    )
    return "\n".join(out)


def doctor(path: str, flight_path: str | None = None) -> tuple[str, int]:
    """(report text, exit code): 0 clean, 1 when anything at warn or
    above surfaced."""
    from xflow_tpu.obs.flight import load_dump

    rows = load_jsonl(path)
    flight = load_dump(flight_path) if flight_path else None
    findings = diagnose(rows, flight=flight)
    text = format_diagnosis(path, rows, findings)
    bad = any(d.severity in ("crit", "warn") for d in findings)
    return text, 1 if bad else 0

"""Turn metrics JSONL files into human-readable throughput / stall /
percentile tables (the ``python -m xflow_tpu.obs`` toolchain).

A metrics file may hold several runs appended back to back; each run
starts with its ``run_start`` header row (utils/logging.MetricsLogger),
so runs are never silently merged.  Rows before the first header (files
written by pre-schema versions) form one anonymous run.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from xflow_tpu.obs.schema import load_jsonl, validate_rows


@dataclass
class Run:
    header: dict | None = None
    rows: list = field(default_factory=list)

    def kind(self, kind: str) -> list[dict]:
        return [r for r in self.rows if r.get("kind") == kind]

    @property
    def epochs(self) -> list[dict]:
        return self.kind("train_epoch")

    @property
    def evals(self) -> list[dict]:
        return self.kind("eval")

    @property
    def shards(self) -> list[dict]:
        return self.kind("shard")

    def label(self) -> str:
        if not self.header:
            return "(no run_start header — pre-schema file?)"
        h = self.header
        host = ""
        if h.get("hostname"):
            host = f"  host {h['hostname']}:{h.get('pid', '?')}"
        return (
            f"run {h.get('run_id', '?')}  config {h.get('config_digest', '?')}"
            f"  rank {h.get('rank', '?')}/{h.get('num_hosts', '?')} hosts"
            f"{host}"
        )

    def wall_seconds(self) -> float:
        return sum(e.get("seconds", 0.0) for e in self.epochs)

    def phase_totals(self) -> tuple[dict[str, float], dict[str, float]]:
        """(exclusive main-thread phases, overlapped worker phases)
        summed over the run's epochs."""
        phases: dict[str, float] = {}
        overlapped: dict[str, float] = {}
        for e in self.epochs:
            for k, v in (e.get("phases") or {}).items():
                phases[k] = phases.get(k, 0.0) + float(v)
            for k, v in (e.get("overlapped") or {}).items():
                overlapped[k] = overlapped.get(k, 0.0) + float(v)
        return phases, overlapped

    def throughput(self) -> float:
        """Overall examples/sec over compute time (checkpoint saves
        excluded, matching train_epoch.examples_per_sec semantics)."""
        ex = sum(e.get("examples", 0.0) for e in self.epochs)
        dt = sum(
            max(e.get("seconds", 0.0) - e.get("checkpoint_seconds", 0.0), 0.0)
            for e in self.epochs
        )
        return ex / dt if dt > 0 else 0.0

    def stall_frac(self) -> float:
        wall = self.wall_seconds()
        stall = self.phase_totals()[0].get("input_stall", 0.0)
        return stall / wall if wall > 0 else 0.0


def split_runs(rows: list[dict]) -> list[Run]:
    runs: list[Run] = []
    for row in rows:
        if row.get("kind") == "run_start" or not runs:
            if row.get("kind") == "run_start":
                runs.append(Run(header=row))
                continue
            runs.append(Run())
        runs[-1].rows.append(row)
    return runs


def load_runs(path: str) -> list[Run]:
    return split_runs(load_jsonl(path))


def _fmt_row(cols: list, widths: list[int]) -> str:
    return "  ".join(str(c).rjust(w) for c, w in zip(cols, widths))


def format_run(run: Run) -> str:
    out = [run.label()]
    epochs = run.epochs
    if epochs:
        widths = [5, 10, 11, 10, 7, 8, 8, 7]
        out.append(_fmt_row(
            ["epoch", "examples", "ex/s", "logloss", "stall%",
             "p50ms", "p99ms", "ckpt_s"],
            widths,
        ))
        for e in epochs:
            out.append(_fmt_row(
                [
                    e.get("epoch", "?"),
                    int(e.get("examples", 0)),
                    f"{e.get('examples_per_sec', 0.0):.0f}",
                    f"{e.get('train_logloss', float('nan')):.6f}",
                    f"{100 * e.get('input_stall_frac', 0.0):.1f}",
                    f"{1e3 * e.get('step_time_p50', 0.0):.2f}",
                    f"{1e3 * e.get('step_time_p99', 0.0):.2f}",
                    f"{e.get('checkpoint_seconds', 0.0):.2f}",
                ],
                widths,
            ))
        phases, overlapped = run.phase_totals()
        wall = run.wall_seconds()
        if phases and wall > 0:
            out.append("")
            out.append(_fmt_row(["phase", "seconds", "% wall"], [16, 9, 7]))
            accounted = 0.0
            for name, secs in sorted(
                phases.items(), key=lambda kv: -kv[1]
            ):
                accounted += secs
                out.append(_fmt_row(
                    [name, f"{secs:.3f}", f"{100 * secs / wall:.1f}"],
                    [16, 9, 7],
                ))
            out.append(_fmt_row(
                ["accounted", f"{accounted:.3f}",
                 f"{100 * accounted / wall:.1f}"],
                [16, 9, 7],
            ))
            if overlapped:
                items = ", ".join(
                    f"{k} {v:.3f}s"
                    for k, v in sorted(overlapped.items(), key=lambda kv: -kv[1])
                )
                out.append(f"overlapped (worker threads, not additive): {items}")
    for ev in run.evals:
        out.append(
            f"eval epoch {ev.get('epoch', '?')}: "
            f"logloss={ev.get('logloss', float('nan')):.6f} "
            f"auc={ev.get('auc', float('nan')):.6f} "
            f"examples={ev.get('examples', 0)}"
        )
    wire = run.kind("wire")
    if wire:
        last = wire[-1]
        out.append(
            f"wire: format={last.get('format', '?')} "
            f"{last.get('wire_bytes_per_example', 0.0):.1f} B/example, "
            f"compaction {last.get('compaction_ratio', 1.0):.2f}x "
            "(cold occurrences per table touch; docs/PERF.md "
            "\"Wire format and compaction\")"
        )
    sheds = run.kind("serve_shed")
    if sheds:
        total_shed = sum(int(r.get("shed_total", 0)) for r in sheds)
        total_adm = sum(int(r.get("admitted", 0)) for r in sheds)
        line = (
            f"serve shed: {total_shed} shed vs {total_adm} admitted "
            f"across {len(sheds)} window(s)"
        )
        agg: dict[str, dict[str, int]] = {}
        for r in sheds:
            for c, d in (r.get("by_class") or {}).items():
                a = agg.setdefault(c, {"admitted": 0, "shed": 0})
                a["admitted"] += int(d.get("admitted", 0))
                a["shed"] += int(d.get("shed", 0))
        if agg:
            # protection order, best-protected first (fleet.QOS_CLASSES)
            order = ["bidding", "normal", "best_effort"]
            line += "; per class shed/offered: " + ", ".join(
                f"{c} {agg[c]['shed']}/"
                f"{agg[c]['admitted'] + agg[c]['shed']}"
                for c in order + sorted(set(agg) - set(order))
                if c in agg
            )
        out.append(line)
    cstats = [r for r in run.kind("serve_stats") if "cache_hits" in r]
    if cstats:
        hits = sum(int(r.get("cache_hits", 0)) for r in cstats)
        misses = sum(int(r.get("cache_misses", 0)) for r in cstats)
        inval = sum(
            int(r.get("cache_invalidations", 0)) for r in cstats
        )
        last = cstats[-1]
        rate = hits / (hits + misses) if (hits + misses) else 0.0
        out.append(
            f"score cache: hit rate {rate:.2f} "
            f"({hits} hit(s) / {misses} miss(es)), "
            f"{last.get('cache_entries', 0)} entries "
            f"({float(last.get('cache_bytes', 0)) / 2**20:.2f} MiB), "
            f"{inval} invalidation(s) "
            "(docs/SERVING.md \"Binary transport and QoS\")"
        )
    fresh = run.kind("freshness")
    if fresh:
        commits = sorted(
            float(r.get("newest_event_age_s", 0.0))
            for r in fresh if r.get("event") == "commit"
        )
        aborts = sum(1 for r in fresh if r.get("event") == "abort")
        last = fresh[-1]
        line = (
            f"freshness: {len(commits)} commit(s), {aborts} abort(s)"
        )
        if commits:
            p50 = commits[len(commits) // 2]
            p99 = commits[min(len(commits) - 1,
                              int(0.99 * len(commits)))]
            line += (
                f", newest-event-age p50/p99 = {p50:.1f}/{p99:.1f}s "
                f"(SLO {float(last.get('slo_s', 0.0)):.0f}s)"
            )
        line += (
            f"; last: {last.get('event')} {last.get('export_kind')} "
            f"step {last.get('step')} "
            f"({last.get('delta_bytes', 0)} B, "
            f"{last.get('rows', 0)} row(s))"
        )
        out.append(line)
    alerts = run.kind("alert")
    if alerts:
        state: dict[str, str] = {}
        for a in alerts:
            state[str(a.get("rule", "?"))] = str(a.get("state", "?"))
        open_rules = sorted(r for r, s in state.items() if s == "firing")
        fired = sum(1 for a in alerts if a.get("state") == "firing")
        resolved = sum(1 for a in alerts if a.get("state") == "resolved")
        last = alerts[-1]
        out.append(
            f"alerts: {fired} fired, {resolved} resolved; "
            f"firing at end: {', '.join(open_rules) or 'none'}; "
            f"last: {last.get('rule')} {last.get('state')} "
            f"(value {last.get('value')} vs threshold "
            f"{last.get('threshold')}; docs/OBSERVABILITY.md "
            "\"Operating a live fleet\")"
        )
    res = run.kind("resource")
    if res:
        last = res[-1]
        peak_rss = max(int(r.get("rss_bytes", 0)) for r in res)
        out.append(
            f"resources: {len(res)} sample(s), rss last/peak = "
            f"{float(last.get('rss_bytes', 0)) / 2**20:.1f}/"
            f"{peak_rss / 2**20:.1f} MiB, "
            f"cpu {float(last.get('cpu_seconds', 0.0)):.1f}s, "
            f"{last.get('threads', 0)} thread(s), "
            f"{last.get('open_fds', 0)} open fd(s), "
            f"{last.get('gc_collections', 0)} gc collection(s)"
        )
    traces = [
        r for r in run.kind("reqtrace")
        if r.get("span") == "request"
        and isinstance(r.get("phases"), dict) and "e2e" in r
    ]
    if traces:
        def _pct(vals: list[float], q: float) -> float:
            s = sorted(vals)
            return s[min(len(s) - 1, int(q * len(s)))]
        e2e = [float(r["e2e"]) for r in traces]
        names = sorted({p for r in traces for p in r["phases"]})
        decomp = "  ".join(
            f"{p} {1e3 * _pct(vs, 0.5):.1f}/{1e3 * _pct(vs, 0.99):.1f}"
            for p in names
            for vs in [[float(r["phases"].get(p, 0.0)) for r in traces]]
        )
        kept = {}
        for r in traces:
            kept[r.get("keep", "?")] = kept.get(r.get("keep", "?"), 0) + 1
        out.append(
            f"reqtrace: {len(traces)} request span(s) "
            f"({', '.join(f'{k}={v}' for k, v in sorted(kept.items()))}), "
            f"e2e p50/p99 = {1e3 * _pct(e2e, 0.5):.1f}/"
            f"{1e3 * _pct(e2e, 0.99):.1f}ms; per-phase p50/p99 ms: "
            f"{decomp} (docs/OBSERVABILITY.md \"Tracing a request\")"
        )
    shards = run.shards
    if shards:
        rates = [s.get("examples_per_sec", 0.0) for s in shards]
        out.append(
            f"shards: {len(shards)} finished, loader throughput "
            f"min/mean/max = {min(rates):.0f}/"
            f"{sum(rates) / len(rates):.0f}/{max(rates):.0f} ex/s"
        )
    streams = run.kind("stream")
    if streams:
        # per-stream mean across epochs, so one cold epoch doesn't
        # read as a straggling stream; zero-rate rows (a stream that
        # never finished a shard — preempted epoch) are excluded like
        # doctor._check_streams does, instead of exploding the ratio
        per: dict[int, list[float]] = {}
        stall = 0.0
        for s in streams:
            eps = float(s.get("examples_per_sec", 0.0))
            if eps > 0:
                per.setdefault(int(s.get("stream", 0)), []).append(eps)
            stall += float(s.get("stall_seconds", 0.0))
        if per:
            means = [sum(v) / len(v) for v in per.values()]
            lo, hi = min(means), max(means)
            out.append(
                f"input streams: {len(per)} (fan-out, io/fanout.py), "
                f"throughput min/mean/max = {lo:.0f}/"
                f"{sum(means) / len(means):.0f}/{hi:.0f} ex/s, "
                f"spread max/min = {hi / lo:.2f}x, "
                f"backpressure stall {stall:.1f}s total"
            )
    mem = run.kind("device_mem")
    if mem:
        last = mem[-1].get("devices") or []
        used = [
            d.get("bytes_in_use") for d in last
            if isinstance(d, dict) and d.get("bytes_in_use") is not None
        ]
        if used:
            out.append(
                f"device memory (last epoch): "
                f"{sum(used) / 2**20:.1f} MiB in use across "
                f"{len(used)} device(s)"
            )
    return "\n".join(out)


def summarize(path: str) -> str:
    rows = load_jsonl(path)
    runs = split_runs(rows)
    parts = [f"{path}: {len(rows)} rows, {len(runs)} run(s)"]
    errors = validate_rows(rows)
    if errors:
        parts.append(
            f"WARNING: {len(errors)} schema violation(s), first: {errors[0]}"
        )
    for i, run in enumerate(runs):
        parts.append("")
        parts.append(f"-- run {i + 1} of {len(runs)} --")
        parts.append(format_run(run))
    return "\n".join(parts)


def check_regress(path_a: str, path_b: str, frac: float) -> str | None:
    """Regression verdict comparing B (candidate) against A (baseline):
    an error string when B's throughput fell more than ``frac`` below
    A's, else None.  ``frac`` is a fraction (0.05 = fail on a >5%
    drop)."""
    a = _last_run(path_a).throughput()
    b = _last_run(path_b).throughput()
    if a <= 0:
        return None  # no baseline signal — nothing to gate on
    drop = (a - b) / a
    if drop > frac:
        return (
            f"REGRESS: {path_b} = {b:.0f} examples/sec (last run) is "
            f"{100 * drop:.1f}% below {path_a} = {a:.0f} "
            f"(--fail-on-regress {frac})"
        )
    return None


def _last_run(path: str) -> Run:
    runs = load_runs(path)
    if not runs:
        raise ValueError(f"{path}: no metrics rows to compare")
    return runs[-1]


def compare(path_a: str, path_b: str) -> str:
    """Side-by-side comparison of the LAST run in each file."""
    ra = _last_run(path_a)
    rb = _last_run(path_b)
    out = [f"A: {path_a}  ({ra.label()})", f"B: {path_b}  ({rb.label()})", ""]

    def delta(a: float, b: float) -> str:
        if a == 0:
            return "n/a"
        return f"{100.0 * (b - a) / a:+.1f}%"

    widths = [22, 12, 12, 8]
    out.append(_fmt_row(["metric", "A", "B", "delta"], widths))
    tp_a, tp_b = ra.throughput(), rb.throughput()
    out.append(_fmt_row(
        ["examples/sec", f"{tp_a:.0f}", f"{tp_b:.0f}", delta(tp_a, tp_b)],
        widths,
    ))
    st_a, st_b = ra.stall_frac(), rb.stall_frac()
    out.append(_fmt_row(
        ["input_stall_frac", f"{st_a:.3f}", f"{st_b:.3f}",
         delta(st_a, st_b)],
        widths,
    ))
    wall_a, wall_b = ra.wall_seconds(), rb.wall_seconds()
    out.append(_fmt_row(
        ["wall seconds", f"{wall_a:.2f}", f"{wall_b:.2f}",
         delta(wall_a, wall_b)],
        widths,
    ))
    pa, _ = ra.phase_totals()
    pb, _ = rb.phase_totals()
    for name in sorted(set(pa) | set(pb)):
        a, b = pa.get(name, 0.0), pb.get(name, 0.0)
        out.append(_fmt_row(
            [f"phase.{name} (s)", f"{a:.3f}", f"{b:.3f}", delta(a, b)],
            widths,
        ))
    ll = [
        (r.evals[-1] if r.evals else None) for r in (ra, rb)
    ]
    if ll[0] and ll[1]:
        out.append(_fmt_row(
            ["eval auc", f"{ll[0].get('auc', 0.0):.6f}",
             f"{ll[1].get('auc', 0.0):.6f}",
             delta(ll[0].get("auc", 0.0), ll[1].get("auc", 0.0))],
            widths,
        ))
    return "\n".join(out)

"""Traffic kind ``serve_open_loop``: single-row scoring requests offered on
an open-loop timeline, in process, through ``ReplicaFleet.submit`` over an
exported artifact — admission, micro-batcher, featurize, bucketed device
call — with the trainer nowhere in the path.

Mix parameters: ``rows`` (generator), ``offered_rows_per_s`` (FIXED in the
mix; the sweep that found the knee is ``benchmarks/knee_sweep.py``),
``limit_ms`` (a row is good when it is answered correctly within this of the
instant it was DUE; goodput is the good rows over the window's seconds),
``replicas``, ``buckets``, ``warmup_s``, ``drain_s``,
``trace_at_s``/``trace_for_s``.  Admission policy, batcher wait and QoS class
stay at the program's defaults; the score cache is off.  Latency, too, runs
from the due instant, over the rows that were answered.

Set-up: the hot remap from the generator's own key counts (kept between
runs, ``harness/cache.py``); an FTRL state "as training leaves it" made on
the device from the seed in one jitted call (random accumulators z and n,
each weight the closed form FTRL keeps for them, half of them inside the L1
ball and so exactly zero); ``export_artifact`` -> ``ReplicaFleet.load``
(warms every bucket); then ``warmup_s`` of the same traffic.  The training
state is dropped once exported: the window runs with what a scoring tier
holds, the parameters.  State and artifact are made anew in every run: the
process's peak of device memory is theirs (PERF.md, section 2), and a run
that found them ready would report another.
"""

from __future__ import annotations

import functools
import glob
import os
import threading
import time

import numpy as np

from benchmarks.generators import timeline
from benchmarks.generators.rows import RowGenerator, RowSpec
from benchmarks.harness import cache, corpus, device, trace_reduce
from benchmarks.harness.context import Ctx, Outcome, beside
from benchmarks.reference import ftrl, steering

# float32 scores; the v5e's exp and divide are approximations, and the worst
# served score of 2.6e5 a run missed a float64 sigmoid by 1.28e-6 (PR 22).
# Weights rounded to bfloat16 would miss by ~1e-3.
ANSWER_ATOL = 5e-6


class Served:
    """A loaded fleet with what is needed to offer it rows and to say what
    each answer should have been."""

    def __init__(self, ctx: Ctx):
        import jax

        from xflow_tpu.config import Config
        from xflow_tpu.serve.artifact import export_artifact
        from xflow_tpu.serve.fleet import ReplicaFleet, ShedError
        from xflow_tpu.trainer import Trainer

        mix = ctx.traffic
        self.ctx, self.mix, self.shed_error = ctx, mix, ShedError
        self.gen = RowGenerator(RowSpec.from_params(mix["rows"]), ctx.seed)

        def build(root: str) -> dict:
            remap = corpus.save_hot_remap(self.gen, root, ctx.fields, ctx.seed)
            return {
                "checkpoint_dir": remap.get("checkpoint_dir", ""),
                "hot_mass": remap.get("hot_mass"),
            }

        meta = cache.entry(ctx, build)
        ctx.log(f"hot remap: cache {meta['cache']}")
        checkpoint_dir, self.remap = corpus.saved_remap(meta, ctx.work)
        self.cfg = cfg = Config(
            **ctx.fields, seed=ctx.seed, checkpoint_dir=checkpoint_dir,
        )
        artifact = os.path.join(ctx.work, "artifact")
        trainer = Trainer(cfg, log=ctx.log)
        try:
            trainer.state = {
                **trainer.state,
                "tables": _state_like_trained(trainer.state["tables"], cfg),
            }
            jax.block_until_ready(trainer.state)
            ctx.log("state made on the device")
            export_artifact(trainer, artifact)
        finally:
            trainer.close()
        del trainer  # and with it the accumulators: a scoring tier has none
        ctx.log("artifact exported")
        self.fleet = ReplicaFleet.load(
            artifact, replicas=mix["replicas"], num_devices=1,
            buckets=tuple(mix["buckets"]), cache_capacity=0,
        )
        ctx.log(f"fleet loaded, {self.fleet.engines[0].compile_count} programs")
        # the artifact's own weights, read back from its files
        self.weights = [
            (int(os.path.basename(p).split(".r")[1].split("-")[0]),
             np.load(p, mmap_mode="r"))
            for p in sorted(glob.glob(os.path.join(artifact, "w.param.r*.npy")))
        ]
        self._stream = 0

    def close(self) -> None:
        self.fleet.close()

    def rows(self, n: int) -> np.ndarray:
        """Raw table rows int64 [n, fields] of ``n`` fresh requests, as a
        client hashes them."""
        self._stream += 1
        gid, _ = self.gen.draw(n, (1000, self._stream))
        return self.gen.keys(gid, self.cfg.table_size, self.cfg.seed)

    def expected(self, keys: np.ndarray) -> np.ndarray:
        """sigmoid(sum of the kept features' weights), float32, from the
        artifact's files: remapped, steered as the engine steers."""
        cfg = self.cfg
        rows = self.remap[keys] if self.remap is not None else keys
        keep = steering.kept(rows, cfg.hot_size, cfg.hot_nnz, cfg.max_nnz)
        starts = np.asarray([s for s, _ in self.weights])
        which = np.searchsorted(starts, rows, side="right") - 1
        w = np.zeros(rows.shape, np.float32)
        for i, (start, arr) in enumerate(self.weights):
            sel = which == i
            w[sel] = arr[rows[sel] - start, 0]
        logit = (w * keep).sum(axis=1, dtype=np.float32)
        p = 1.0 / (1.0 + np.exp(-logit.astype(np.float64)))
        return np.where(logit > 30, 1.0, np.where(logit < -30, 1e-6, p))

    def offer(self, rate: float, seconds: float, seed: int, trace=None) -> dict:
        """``seconds`` of Poisson arrivals at ``rate`` rows a second; the
        fleet's stats window is reset before and flushed after."""
        mix, fleet = self.mix, self.fleet
        due = timeline.poisson_due(np.random.default_rng([seed, 77]), rate, seconds)
        keys = self.rows(len(due))
        loop = timeline.OpenLoop(fleet.submit, list(keys), due, self.shed_error)
        fleet.emit_stats()
        start = time.perf_counter()
        if trace is not None:
            trace.start(start)
        loop.run(start)
        depth_at_end = fleet.depth()
        unanswered = loop.drain(mix["drain_s"])
        stats = fleet.emit_stats()
        if trace is not None:
            trace.join()
        answered = loop.status == timeline.ANSWERED
        want = self.expected(keys[answered])
        wrong = np.abs(loop.answer[answered] - want) > ANSWER_ATOL
        good = np.zeros(len(due), bool)
        good[answered] = ~wrong & (loop.latency[answered] <= mix["limit_ms"] / 1e3)
        # the good rows due in each whole second: their median does not see a
        # stall of the host (0.65 s was seen once in ten runs) that the
        # users, and so goodput, do
        whole = int(seconds)
        per_second = np.bincount(
            due[good & (due < whole)].astype(np.int64), minlength=whole
        )[:whole]
        return {
            "goodput_rows_per_s": float(good.sum() / seconds),
            "start": start,
            "seconds": seconds,
            "offered": len(due),
            "answered": int(answered.sum()),
            "good": int(good.sum()),
            "good_per_second": per_second.tolist(),
            "wrong": int(wrong.sum()),
            "shed": int((loop.status == timeline.SHED).sum()),
            "errors": int((loop.status == timeline.ERROR).sum()),
            "unanswered": unanswered,
            "depth_at_end": depth_at_end,
            "late_ms": _percentiles(loop.late * 1e3),
            "latency_ms": _percentiles(loop.latency[answered] * 1e3),
            "worst_answer_err": float(
                np.abs(loop.answer[answered] - want).max()
            ) if answered.any() else 0.0,
            "serve_stats": stats["stats"],
            "serve_shed": stats["shed"],
        }


def _percentiles(values: np.ndarray) -> dict:
    if not len(values):
        return {"n": 0}
    p50, p90, p99 = np.percentile(values, [50, 90, 99])
    return {"n": len(values), "p50": float(p50), "p90": float(p90),
            "p99": float(p99), "max": float(values.max())}


def _state_like_trained(tables: dict, cfg) -> dict:
    """Tables shaped and laid out like ``tables`` (the trainer's zero state),
    made on the device in one jitted call from ``cfg.seed``: accumulators
    z ~ 3 N(0,1) and n ~ N(0,1)^2 on a random half of the entries, zero on
    the rest, and the weight FTRL-proximal keeps for them."""
    import jax
    import jax.numpy as jnp

    hyper = dict(ftrl.hyper_of(cfg))
    shapes = {name: entry["param"].shape for name, entry in sorted(tables.items())}
    shardings = jax.tree.map(lambda a: a.sharding, tables)

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            kz, kn, kl = jax.random.split(jax.random.fold_in(key, i), 3)
            live = jax.random.bernoulli(kl, 0.5, shape)
            z = jnp.where(live, 3.0 * jax.random.normal(kz, shape), 0.0)
            n = jnp.where(live, jnp.square(jax.random.normal(kn, shape)), 0.0)
            out[name] = {"param": ftrl.weight_of(z, n, hyper), "n": n, "z": z}
        return out

    return make(jax.random.PRNGKey(cfg.seed))


class _TraceSlice:
    """The profiler over ``for_s`` seconds from ``at_s`` into the offered
    traffic, on a thread of its own so that the generator never waits for
    it; the slice is the host span ``loadgen`` on the trace's clock."""

    def __init__(self, trace_dir: str, at_s: float, for_s: float):
        self.trace_dir, self.at_s, self.for_s = trace_dir, at_s, for_s
        self._thread: threading.Thread | None = None

    def start(self, t0: float) -> None:
        self._thread = threading.Thread(
            target=self._run, args=(t0,), name="bench-trace", daemon=True
        )
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax

        time.sleep(max(0.0, t0 + self.at_s - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "loadgen"):
                time.sleep(self.for_s)
        finally:
            jax.profiler.stop_trace()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=120.0)
            if self._thread.is_alive():
                raise RuntimeError("the trace thread did not finish")


def run(ctx: Ctx) -> Outcome:
    mix = ctx.traffic
    served = Served(ctx)
    try:
        engine = served.fleet.engines[0]
        rate = float(mix["offered_rows_per_s"])
        warm = served.offer(rate, mix["warmup_s"], ctx.seed + 1)
        ctx.log(f"warm-up: {warm['answered']} answered of {warm['offered']}")
        programs = engine.compile_count
        compiled_before = ctx.meter.snapshot()["compiles"]
        trace = None
        if ctx.trace:
            at = min(mix["trace_at_s"], max(0.0, ctx.seconds - mix["trace_for_s"]))
            trace = _TraceSlice(
                os.path.join(ctx.work, "trace"), at,
                min(mix["trace_for_s"], ctx.seconds),
            )
        got = served.offer(rate, ctx.seconds, ctx.seed, trace)
        compiled_in_window = (
            engine.compile_count - programs
            + ctx.meter.snapshot()["compiles"] - compiled_before
        )
        checks = {
            "answers_match_reference": got["wrong"] == 0 and got["answered"] > 0,
            "no_compile_in_window": compiled_in_window == 0,
            "weights_not_trivial": any(
                float(np.abs(arr[: 1 << 16]).max()) > 0 for _, arr in served.weights
            ),
        }
        # the tier alone: set-up's training state went with its trainer
        held = device.held_bytes()
        memory_peak = device.memory_peak_bytes(held)
    finally:
        served.close()
    reduced = None
    if trace is not None:
        xplane = trace_reduce.find_xplane(trace.trace_dir)
        tr = trace_reduce.load_xplane(xplane)
        window = trace_reduce.span_window(tr, "loadgen")
        reduced = trace_reduce.reduce(
            tr, window, labels={"loadgen": "loadgen"}, default_label="loadgen"
        )
    return Outcome(
        checks=checks,
        attempted=got["offered"],
        failed=got["errors"] + got["wrong"] + got["unanswered"],
        end_to_end={
            "serve_goodput_rows_per_s": got["goodput_rows_per_s"],
            "serve_latency_p90_ms": got["latency_ms"].get("p90"),
        },
        window_start=got["start"],
        run={
            "kind": mix["kind"], "fields": ctx.fields, "offered_rows_per_s": rate,
            "window": got, "warmup": warm, "trace": reduced,
            "memory_peak_bytes": memory_peak, "held_bytes": held,
            "peaks": ctx.peaks,
        },
        counts={
            "offered": got["offered"], "answered": got["answered"],
            "shed": got["shed"], "wrong": got["wrong"], "errors": got["errors"],
            "unanswered": got["unanswered"], "programs": programs,
        },
        compared={
            "answer_err": beside(got["worst_answer_err"], ANSWER_ATOL),
            "wrong_answers": beside(got["wrong"], 0),
            "compiles_in_window": beside(compiled_in_window, 0),
        },
    )

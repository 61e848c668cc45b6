"""What the two training kinds share: a ``Trainer`` over the cell's corpus,
driven epoch after epoch through ``Trainer.train_epoch()`` — loader or
stream pool, wire, staging ring, ``TrainStep`` — exactly as
``Trainer.train()`` drives it, with the window's clock around whole epochs.

The kinds differ only in the corpus they build (``corpus`` argument): packed
shards replayed, or libffm text parsed on every epoch.
"""

from __future__ import annotations

import math
import os
import time

from benchmarks.generators.rows import RowGenerator, RowSpec
from benchmarks.harness import costs, device, manifest, refcheck, trace_reduce
from benchmarks.harness import corpus as corpus_mod
from benchmarks.harness.context import Ctx, Outcome, beside
from benchmarks.reference import steering

MAX_DROPPED_SHARE = 0.005  # a geometry that drops more is a different job
GAP_LABELS = {"epoch": "epoch_boundary", "steps": "in_epoch"}


def run(ctx: Ctx, build_corpus) -> Outcome:
    """``build_corpus(ctx, gen, fields) -> dict`` with ``train_path``,
    ``checkpoint_dir``, ``shards``, ``rows`` and, with a hot table,
    ``remap`` (``corpus.resolve``).  The end-to-end metric is
    ``train_examples_per_s``: examples over the wall time of the window's
    whole epochs."""
    import jax
    import numpy as np

    from xflow_tpu.config import Config
    from xflow_tpu.io.loader import ShardLoader, make_parse_fn
    from xflow_tpu.trainer import Trainer

    fields, mix = ctx.fields, ctx.traffic
    gen = RowGenerator(RowSpec.from_params(mix["rows"]), ctx.seed)
    data = build_corpus(ctx, gen, fields)
    cfg = Config(
        **fields,
        seed=ctx.seed,
        train_path=data["train_path"],
        checkpoint_dir=data["checkpoint_dir"],
        epochs=1 << 30,
        # the program's own phase clocks and counters are read in the traced
        # run only; the end-to-end run leaves them off
        metrics_out=os.path.join(ctx.work, "train.jsonl") if ctx.trace else "",
    )
    ctx.log(
        f"corpus: {data['rows']} rows, cache {data['cache']}, "
        f"built in {data['seconds']}"
    )
    checks: dict = {}

    # what steering drops, counted on rows the remap's sample did not hold
    gid, _ = corpus_mod.shard_rows(gen, len(data["shards"]) - 1, cfg.batch_size)
    rows = gen.keys(gid, cfg.table_size, cfg.seed)
    if cfg.hot_size:
        rows = data["remap"][rows]
    dropped = steering.dropped_share(rows, cfg.hot_size, cfg.hot_nnz, cfg.max_nnz)
    checks["dropped_entry_share"] = dropped <= MAX_DROPPED_SHARE

    trainer = Trainer(cfg, log=ctx.log)
    try:
        dispatched = 0

        def epoch(span: str | None = None) -> dict:
            nonlocal dispatched
            if span is None:
                stats = trainer.train_epoch()
            else:
                with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + span):
                    stats = trainer.train_epoch()
            trainer.epoch += 1
            dispatched += stats["steps"]
            return stats

        warm = [epoch() for _ in range(mix["warmup_epochs"])]
        compiled_before = ctx.meter.snapshot()["compiles"]

        # -- the window: whole epochs, as many as the seconds hold ----------
        traced = None
        if ctx.trace:
            _mark_dispatches(trainer)
        window_start = time.perf_counter()
        epochs: list[dict] = []
        while True:
            if ctx.trace and len(epochs) == 1:
                traced = _traced_epoch(ctx, epoch)
                epochs.append(traced["stats"])
            else:
                epochs.append(epoch())
            elapsed = time.perf_counter() - window_start
            # stop where the next epoch would end further from the target
            # than this one did (a traced run needs its second epoch)
            if elapsed + 0.5 * elapsed / len(epochs) >= ctx.seconds and (
                traced or not ctx.trace
            ):
                break
        window_s = time.perf_counter() - window_start
        compiled_in_window = ctx.meter.snapshot()["compiles"] - compiled_before
        checks["no_compile_in_window"] = compiled_in_window == 0
        # with the trainer alive, before the reference's arrays
        held = device.held_bytes()
        memory_peak = device.memory_peak_bytes(held)

        seen = sum(e["examples"] for e in epochs)
        in_epochs_s = sum(e["seconds"] for e in epochs)
        loglosses = [e["train_logloss"] for e in warm + epochs]
        checks["logloss_finite"] = all(map(math.isfinite, loglosses))
        checks["logloss_fell"] = loglosses[-1] < loglosses[0]
        short_epochs = sum(e["examples"] != data["rows"] for e in epochs)
        checks["all_rows_trained"] = short_epochs == 0

        # -- outside the window: the reference, and the step alone ----------
        batches = _first_batches(
            cfg, data, max(mix["reference_steps"], 4), ShardLoader,
            make_parse_fn(cfg.table_size, True, cfg.seed),
        )
        family = manifest.reference(ctx.config_doc["family"])
        ref = refcheck.check_train_steps(
            trainer, family, batches[: mix["reference_steps"]], cfg, ctx.meter,
        )
        checks["steps_match_reference"] = ref["ok"]
        dispatched += len(ref["steps"])
        kept = float(np.mean([
            b.mask.sum() + b.hot_mask.sum() for b in batches
        ]))
        probe = None
        if ctx.trace and mix["step_probe_steps"]:
            probe = _step_alone(trainer, batches, mix["step_probe_steps"])
            dispatched += mix["step_probe_steps"]
        wire_format = trainer.step.wire_format
        hot_impl = trainer.step._hot_impl
    finally:
        trainer.close()

    worst_rows = max(max(s["rows_rel_err"].values()) for s in ref["steps"])
    worst_logloss = max(s["logloss_err"] for s in ref["steps"])
    bad_steps = sum(
        e["steps"] for e in warm + epochs if not math.isfinite(e["train_logloss"])
    ) + sum(not s["ok"] for s in ref["steps"])
    run_record = {
        "kind": mix["kind"],
        "fields": fields,
        "epochs": epochs,
        "warmup": warm,
        "window_s": window_s,
        "corpus": {k: v for k, v in data.items() if k not in ("remap", "shards")},
        "dropped_entry_share": dropped,
        "reference": ref,
        "wire_format": wire_format,
        "hot_impl": hot_impl,
        "step_alone": probe,
        "trace": traced["reduced"] if traced else None,
        "memory_peak_bytes": memory_peak,
        "held_bytes": held,
        "costs": costs.train_step(
            fields, family.TABLES,
            entries_per_step=kept,
            hot_share=float(np.mean([b.hot_mask.sum() for b in batches])) / kept,
            matmuls=family.matmuls(ref["dense_shapes"]) if "dense_shapes" in ref else (),
        ),
        "peaks": ctx.peaks,
    }
    return Outcome(
        checks=checks,
        attempted=dispatched,
        failed=bad_steps,
        end_to_end={"train_examples_per_s": seen / in_epochs_s},
        window_start=window_start,
        run=run_record,
        counts={
            "epochs_in_window": len(epochs),
            "steps_per_epoch": epochs[0]["steps"],
            "rows_per_epoch": data["rows"],
            "corpus_cache": data["cache"],
            "wire_format": wire_format,
            "dropped_entry_share": dropped,
            "reference_ok": ref["ok"],
            "reference_worst_rows_rel_err": worst_rows,
            "reference_worst_logloss_err": worst_logloss,
        },
        compared={
            "rows_rel_err": beside(worst_rows, refcheck.ROWS_RTOL),
            "logloss_err": beside(worst_logloss, refcheck.LOGLOSS_ATOL),
            "dropped_entry_share": beside(dropped, MAX_DROPPED_SHARE),
            "compiles_in_window": beside(compiled_in_window, 0),
            "short_epochs": beside(short_epochs, 0),
            # the last epoch's logloss less the first's
            "logloss_change": beside(loglosses[-1] - loglosses[0], 0.0, "<"),
            # a family with dense parameters: each array beside its limit
            **refcheck.dense_compared(ref["steps"]),
        },
    )


def _first_batches(cfg, data: dict, count: int, loader_cls, parse_fn) -> list:
    """The corpus's first ``count`` batches as the loader steers them (padded
    ``Batch`` objects, whatever the shard format)."""
    batches: list = []
    for path in data["shards"]:
        loader = loader_cls(
            path, batch_size=cfg.batch_size, max_nnz=cfg.max_nnz,
            table_size=cfg.table_size, hash_seed=cfg.seed, parse_fn=parse_fn,
            remap=data.get("remap"), hot_size=cfg.hot_size,
            hot_nnz=cfg.hot_nnz if cfg.hot_size else 0,
        )
        for batch, _ in loader.iter_batches():
            batches.append(batch)
            if len(batches) == count:
                return batches
    return batches


def _mark_dispatches(trainer) -> None:
    """Put a host span on the trace's clock around every call of the train
    program, from outside the program: the trainer looks ``dispatch_train``
    up on its step object each time, so the instance can carry a wrapped
    one.  Traced runs only."""
    import jax

    inner = trainer.step.dispatch_train
    name = trace_reduce.SPAN_PREFIX + "dispatch"

    def dispatch_train(state, arrays):
        with jax.profiler.TraceAnnotation(name):
            return inner(state, arrays)

    trainer.step.dispatch_train = dispatch_train


def _traced_epoch(ctx: Ctx, epoch) -> dict:
    """One whole ``train_epoch()`` call under the profiler, from the call to
    its return: the trainer's own trigger (``Config.profile_dir``) stops at
    the epoch's end and cannot hold profiler options, and the epoch's two
    ends are part of what examples per second pays for."""
    import jax

    trace_dir = os.path.join(ctx.work, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # every Python call is not wanted
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        stats = epoch("epoch")
    finally:
        jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(trace_dir)
    trace = trace_reduce.load_xplane(xplane)
    window = trace_reduce.span_window(trace, "epoch")
    calls = [(s, s + d) for n, s, d in trace.spans if n == "dispatch"]
    if calls:  # from the first call of the program to the last one's return
        first, last = min(c[0] for c in calls), max(c[1] for c in calls)
        trace.spans.append(("steps", first, last - first))
    reduced = trace_reduce.reduce(
        trace, window, steps=stats["steps"], labels=GAP_LABELS,
        default_label="epoch_boundary",
    )
    return {"stats": stats, "reduced": reduced}


def _step_alone(trainer, batches: list, steps: int) -> dict:
    """The step layer's device time with nothing else in the way
    (``bench.py::run``'s method): ``steps`` chained calls of the train
    program on batches already on the device, closed by a fetch that waits
    for the chain."""
    import jax

    def sync(state):
        first = next(iter(state["tables"].values()))
        jax.device_get(first["param"][:1, 0])

    staged = [trainer.step.put_batch(b) for b in batches]
    state = trainer.state
    for i in range(min(3, len(staged))):  # each shape, before the clock
        state, _ = trainer.step.train(state, staged[i])
    sync(state)
    t0 = time.perf_counter()
    for i in range(steps):
        state, _ = trainer.step.train(state, staged[i % len(staged)])
    sync(state)
    dt = time.perf_counter() - t0
    trainer.state = state
    return {"steps": steps, "seconds": dt, "ms_per_step": dt / steps * 1e3}

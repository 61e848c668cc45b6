"""Traffic kind ``train_replay``: a packed-v2 corpus, built by the program's
converter at the configuration's own geometry (in the set-up of the first
run with a seed in a checkout, ``harness/cache.py``), replayed epoch after
epoch.  Mix parameters: ``rows`` (generator), ``batches`` (full batches in
the corpus, split evenly over ``input_streams`` shards), ``warmup_epochs``,
``reference_steps`` (system steps held to the reference after the window),
``step_probe_steps`` (chained steps of the program alone in a traced run; 0
leaves the probe out)."""

from __future__ import annotations

from benchmarks.harness import cache, corpus, train_cell
from benchmarks.harness.context import Ctx, Outcome


def build_corpus(ctx: Ctx, gen, fields: dict) -> dict:
    def build(root: str) -> dict:
        text, remap = corpus.text_corpus(
            gen, root, fields, ctx.traffic["batches"], ctx.seed
        )
        return corpus.packed_corpus(text, remap, root, fields, ctx.seed)

    return corpus.resolve(cache.entry(ctx, build), ctx.work)


def run(ctx: Ctx) -> Outcome:
    return train_cell.run(ctx, build_corpus)

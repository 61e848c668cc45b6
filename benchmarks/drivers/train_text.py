"""Traffic kind ``train_text``: libffm text shards parsed by the native
parser on every epoch, no packed cache.  Mix parameters as for
``train_replay``.  The hot remap is handed to the trainer ready-made: it
would count keys and build one itself from the text
(``Trainer._init_remap``), which at 2^28 rows takes it most of a minute.

No cell of ``BENCHMARK.json`` is of this kind yet (PERF.md, Open questions:
``lr_tb.train_text``); the kind is here, and rehearsed by
``tests/test_manifest.py``, so that the cell can come back as a mix file and
an entry."""

from __future__ import annotations

from benchmarks.harness import cache, corpus, train_cell
from benchmarks.harness.context import Ctx, Outcome


def build_corpus(ctx: Ctx, gen, fields: dict) -> dict:
    def build(root: str) -> dict:
        return corpus.text_corpus(
            gen, root, fields, ctx.traffic["batches"], ctx.seed
        )[0]

    return corpus.resolve(cache.entry(ctx, build), ctx.work)


def run(ctx: Ctx) -> Outcome:
    return train_cell.run(ctx, build_corpus)

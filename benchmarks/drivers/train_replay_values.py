"""Traffic kind ``train_replay_values``: ``train_replay`` over rows whose
integer fields carry values (``generators/rows_values.py``): a packed-v2
corpus with a values plane, built by the program's converter at the
configuration's own geometry in the set-up of the first run with a seed in a
checkout (``harness/cache.py``), replayed epoch after epoch.  Mix parameters:
``train_replay``'s, and ``values`` (the generator's; its ``fields`` has to be
the configuration's ``numeric_fields``).

The generator the harness hands ``build_corpus`` is the stock one over the
mix's ``rows``; the one built here has the same spec and seed, so the same
draws and ids, and writes the value tokens.  The packing step is this file's
own: ``harness/corpus.py::packed_corpus`` builds its parser without
``numeric_fields``, which is the reference's loader and packs every value away
as 1.  The packed shards then carry the values themselves: ``ShardLoader``
reads a record's plane whatever parser it was given, so the batches the
harness checks against the reference (``train_cell._first_batches``) hold what
the text held."""

from __future__ import annotations

import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor

from benchmarks.generators.rows_values import ValueRowGenerator
from benchmarks.harness import cache, corpus, train_cell
from benchmarks.harness.context import Ctx, Outcome


def packed_corpus(text: dict, remap, root: str, fields: dict, hash_seed: int) -> dict:
    """``corpus.packed_corpus`` with the numeric fields' values kept: the text
    corpus under ``root`` as packed-v2 shards at the geometry of ``fields``,
    one packed shard per text shard, through the program's converter and a
    parser that keeps a value for the fields below ``numeric_fields``.  The
    text is removed once packed."""
    from xflow_tpu.io import packed
    from xflow_tpu.io.loader import make_parse_fn

    table_size = 1 << fields["table_size_log2"]
    hot_size = (1 << fields["hot_size_log2"]) if remap is not None else 0
    numeric = fields["numeric_fields"]
    parse_fn = make_parse_fn(table_size, True, hash_seed, numeric_fields=numeric)
    os.makedirs(os.path.join(root, "packed"))
    out = [os.path.join("packed", os.path.basename(p)) for p in text["shards"]]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(out)) as ex:  # the native parser drops the lock
        list(ex.map(
            lambda p: packed.convert_shard(
                os.path.join(root, p[0]), os.path.join(root, p[1]),
                batch_size=fields["batch_size"],
                max_nnz=fields["max_nnz"],
                table_size=table_size,
                hot_size=hot_size,
                hot_nnz=fields["hot_nnz"] if hot_size else 0,
                hash_seed=hash_seed,
                remap=remap,
                parse_fn=parse_fn,
                fmt="v2",
                numeric_fields=numeric,
            ),
            zip(text["shards"], out),
        ))
    shutil.rmtree(os.path.join(root, "text"))
    return {
        **text,
        "train_path": "packed/train",
        "shards": out,
        "packed_bytes": sum(os.path.getsize(os.path.join(root, p)) for p in out),
        "seconds": {**text["seconds"], "packed": time.perf_counter() - t0},
    }


def build_corpus(ctx: Ctx, gen, fields: dict) -> dict:
    from xflow_tpu.config import Config

    # a program older than the values plane does not know the file's
    # ``numeric_fields``: it says so here, before any row is written
    Config(**fields)
    values = ctx.traffic["values"]
    if values["fields"] != fields.get("numeric_fields"):
        raise ValueError(
            f"the mix writes values for {values['fields']} fields and the "
            f"configuration reads {fields.get('numeric_fields')}"
        )
    valued = ValueRowGenerator(gen.spec, gen.seed, values)

    def build(root: str) -> dict:
        text, remap = corpus.text_corpus(
            valued, root, fields, ctx.traffic["batches"], ctx.seed
        )
        return packed_corpus(text, remap, root, fields, ctx.seed)

    return corpus.resolve(cache.entry(ctx, build), ctx.work)


def run(ctx: Ctx) -> Outcome:
    return train_cell.run(ctx, build_corpus)

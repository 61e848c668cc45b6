"""A cell's training data, made in set-up from the seed.

Rows come from ``benchmarks/generators/rows.py`` as libffm text.  A packed
corpus is that text through the program's converter ``xflow_tpu.io.packed``,
as a user runs it; ``ShardLoader`` has no way to take batches that are
already in memory, so the corpus goes through the disk.  The hot remap is
made here (``hot_remap``) and handed to the program as the ``remap.npy`` it
would otherwise build itself.

Every shard holds whole batches only, so an epoch has no tail batch and one
wire shape per plane-capacity bucket.

The builders write under a ``root`` of ``harness/cache.py`` and return an
entry's meta, every path relative to that root; ``resolve`` turns the meta of
an entry linked into a run's work directory into what a train kind hands
``train_cell.run``.
"""

from __future__ import annotations

import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.generators.rows import PIECE, RowGenerator, write_text_shards


def text_corpus(
    gen: RowGenerator, root: str, fields: dict, batches: int, hash_seed: int
) -> tuple[dict, np.ndarray | None]:
    """``batches`` full batches of libffm text under ``root``, split evenly
    over ``input_streams`` shards, with the hot remap of ``fields`` (Config
    fields) saved as the trainer looks for one.  (meta, remap): ``train_path``,
    ``checkpoint_dir``, ``shards``, ``hot_mass``, ``rows``, ``seconds``."""
    shards = fields.get("input_streams", 1)
    if batches % shards:
        raise ValueError(f"{batches} batches do not divide over {shards} shards")
    os.makedirs(os.path.join(root, "text"))
    t0 = time.perf_counter()
    paths = write_text_shards(
        gen, os.path.join(root, "text", "train"), shards,
        batches // shards * fields["batch_size"],
    )
    seconds = {"text": time.perf_counter() - t0}
    t0 = time.perf_counter()
    remap = save_hot_remap(gen, root, fields, hash_seed)
    if remap:
        seconds["remap"] = time.perf_counter() - t0
    return {
        "train_path": "text/train",
        "checkpoint_dir": remap.get("checkpoint_dir", ""),
        "hot_mass": remap.get("hot_mass"),
        "shards": [os.path.relpath(p, root) for p in paths],
        "rows": batches * fields["batch_size"],
        "text_bytes": sum(os.path.getsize(p) for p in paths),
        "seconds": seconds,
    }, remap.get("remap")


def resolve(meta: dict, work: str) -> dict:
    """``meta`` of an entry whose files are in ``work``: its paths made
    absolute, and ``remap`` (the permutation, or None) read back."""
    ckpt, remap = saved_remap(meta, work)
    return {
        **meta,
        "train_path": os.path.join(work, meta["train_path"]),
        "checkpoint_dir": ckpt,
        "shards": [os.path.join(work, p) for p in meta["shards"]],
        "remap": remap,
    }


def saved_remap(meta: dict, work: str) -> tuple[str, np.ndarray | None]:
    """(checkpoint_dir, remap) of an entry in ``work`` that ``save_hot_remap``
    wrote into; ("", None) without a hot table."""
    from xflow_tpu.io import freq

    if not meta["checkpoint_dir"]:
        return "", None
    ckpt = os.path.join(work, meta["checkpoint_dir"])
    return ckpt, freq.load_remap(os.path.join(ckpt, "remap.npy"))


def hot_remap(
    gen: RowGenerator, table_size: int, hot_size: int, hash_seed: int,
    sample_rows: int = 131072,
) -> tuple[np.ndarray, float]:
    """(permutation int32 [T], share of sampled occurrences in the head):
    the ``sample_rows`` first rows' ``hot_size`` most frequent table rows go
    to [0, hot_size) in descending frequency, the rest keep their order
    behind them — what ``io/freq.py::build_remap`` makes of the same counts
    (ties aside), without its argpartition over all T rows, which takes
    most of a minute at 2^28."""
    keys = gen.keys(
        shard_rows(gen, 0, sample_rows)[0], table_size, hash_seed
    ).ravel()
    uniq, counts = np.unique(keys, return_counts=True)
    order = np.lexsort((uniq, -counts))[:hot_size]
    top = uniq[order]
    if len(top) < hot_size:  # a toy table: fill the head with unseen rows
        spare = np.setdiff1d(np.arange(table_size), top)[: hot_size - len(top)]
        top = np.concatenate([top, spare])
    # between two neighbouring head rows the others keep their order: such
    # a run moves up by the head's size and down by the head rows before it
    remap = np.empty(table_size, np.int32)
    cuts = np.concatenate([[-1], np.sort(top), [table_size]])
    for i in range(len(cuts) - 1):
        lo, hi = cuts[i] + 1, cuts[i + 1]
        remap[lo:hi] = np.arange(
            lo + hot_size - i, hi + hot_size - i, dtype=np.int32
        )
    remap[top] = np.arange(hot_size, dtype=np.int32)
    return remap, float(counts[order].sum() / len(keys))


def shard_rows(
    gen: RowGenerator, shard: int, rows: int
) -> tuple[np.ndarray, np.ndarray]:
    """(global ids, labels) of the first ``rows`` rows of text shard
    ``shard``, drawn again as ``write_text_shards`` drew them."""
    drawn = [
        gen.draw(min(PIECE, rows - lo), (shard, c))
        for c, lo in enumerate(range(0, rows, PIECE))
    ]
    return (
        np.concatenate([d[0] for d in drawn]),
        np.concatenate([d[1] for d in drawn]),
    )


def packed_corpus(
    text: dict, remap: np.ndarray | None, root: str, fields: dict, hash_seed: int
) -> dict:
    """The text corpus under ``root`` as packed-v2 shards at the geometry of
    ``fields``, one packed shard per text shard, through the program's
    converter ``xflow_tpu.io.packed``.  The text is removed once packed."""
    from xflow_tpu.io import packed
    from xflow_tpu.io.loader import make_parse_fn

    table_size = 1 << fields["table_size_log2"]
    hot_size = (1 << fields["hot_size_log2"]) if remap is not None else 0
    # without one the converter falls back to the pure-Python parser
    parse_fn = make_parse_fn(table_size, True, hash_seed)
    os.makedirs(os.path.join(root, "packed"))
    out = [os.path.join("packed", os.path.basename(p)) for p in text["shards"]]
    t0 = time.perf_counter()
    # the native parser and packer release the interpreter lock, so the
    # shards convert side by side
    with ThreadPoolExecutor(len(out)) as ex:
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


def save_hot_remap(
    gen: RowGenerator, root: str, fields: dict, hash_seed: int
) -> dict:
    """The hot remap of a configuration with a hot table, saved under
    ``root`` as the trainer looks for one (``<checkpoint_dir>/remap.npy``):
    ``remap``, ``hot_mass``, ``checkpoint_dir`` (relative); {} without a hot
    table."""
    from xflow_tpu.io import freq

    hot_log2 = fields.get("hot_size_log2", 0)
    if not hot_log2:
        return {}
    remap, hot_mass = hot_remap(
        gen, 1 << fields["table_size_log2"], 1 << hot_log2, hash_seed
    )
    os.makedirs(os.path.join(root, "ckpt"))
    freq.save_remap(os.path.join(root, "ckpt", "remap.npy"), remap)
    return {"remap": remap, "hot_mass": hot_mass, "checkpoint_dir": "ckpt"}

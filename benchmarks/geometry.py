"""Choose ``max_nnz`` and ``hot_nnz`` of a configuration by the rule, on the
benchmark's own rows.  A count: it needs no chip.

    python3 benchmarks/geometry.py --config lr_ftrl_criteo_tb \
        --traffic replay_packed_zipf [--seeds 1,2,3]

The rule: at the configuration's ``hot_size_log2``, the smallest cold
capacity ``max_nnz`` (a multiple of 4; each cold slot is a DMA gather and a
scatter whether or not the row fills it) for which some ``hot_nnz`` <= the
row width keeps the share of entries that steering drops at or under 0.5 %,
and then the smallest such ``hot_nnz`` (a multiple of 4).  The result goes
into the configuration file, with the share, under ``assumed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.generators.rows import RowGenerator, RowSpec  # noqa: E402
from benchmarks.harness import corpus, manifest  # noqa: E402
from benchmarks.reference import steering  # noqa: E402

MAX_DROPPED = 0.005


def choose(rows, hot_size: int, width: int) -> tuple[int, int, float]:
    """(max_nnz, hot_nnz, dropped share) by the rule, for remapped ``rows``."""
    steps = range(4, width + 4, 4)
    for max_nnz in steps:
        for hot_nnz in steps:
            share = steering.dropped_share(rows, hot_size, hot_nnz, max_nnz)
            if share <= MAX_DROPPED:
                return max_nnz, hot_nnz, share
    raise ValueError("no geometry keeps 99.5 % of the entries")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rows", type=int, default=131072)
    args = ap.parse_args(argv)
    fields = manifest.config_file(f"benchmarks/configs/{args.config}.json")
    spec = RowSpec.from_params(manifest.traffic(args.traffic)["rows"])
    table_size = 1 << fields["table_size_log2"]
    hot_size = 1 << fields["hot_size_log2"]
    for seed in map(int, args.seeds.split(",")):
        gen = RowGenerator(spec, seed)
        remap, hot_mass = corpus.hot_remap(gen, table_size, hot_size, seed)
        # rows the remap's sample did not see: the second shard's first
        gid, _ = corpus.shard_rows(gen, 1, args.rows)
        rows = remap[gen.keys(gid, table_size, seed)]
        max_nnz, hot_nnz, share = choose(rows, hot_size, spec.fields)
        print(json.dumps({
            "seed": seed, "hot_mass": round(hot_mass, 4),
            "max_nnz": max_nnz, "hot_nnz": hot_nnz,
            "dropped_share": round(share, 6),
            "as_configured": round(steering.dropped_share(
                rows, hot_size, fields["hot_nnz"], fields["max_nnz"]
            ), 6),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

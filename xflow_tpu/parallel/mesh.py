"""Device mesh and sharding layout — the ps-lite replacement.

The reference scales two ways (SURVEY §2): data parallelism (each
worker process streams its own shard, lr_worker.cc:210) and parameter
sharding (ps-lite range-partitions the uint64 key space over servers).
On TPU both collapse onto one 1-D mesh axis ``"data"``:

* weight/optimizer tables [T, D] are **row-sharded**: rows split into
  contiguous blocks across devices — the moral equivalent of ps-lite's
  contiguous key-range server partition.  One block a device, always;
  a deployment sized for a layout states the count
  (``Config.table_shards``, the reference's DMLC_NUM_SERVER) and the
  trainer refuses a mesh that would cut the tables otherwise;
* minibatches are sharded on the batch dimension (data parallelism);
* the cross-device traffic the reference did with ZMQ Push/Pull is
  written by the program (parallel/exchange.py; the step's
  ``_pull_model_rows`` / ``_push_grads`` in parallel/step.py), as
  collectives over ICI inside a ``shard_map`` over ``"data"``.  Pull:
  all-gather the batch's keys, gather from this chip's own row block
  with out-of-block keys reading zeros, reduce-scatter the rows back to
  the batch shards.  Push: all-gather keys and gradient rows,
  scatter-add into this chip's block, out-of-block keys dropped.  The
  hot head [0, H) is read once as a replicated block and its gradient
  summed in one all-reduce, so the one-hot scans of ops/hot.py run on a
  chip's batch shard with no collective inside.  A dense step holds 4
  collectives a table (pulled rows, pushed gradients, head read, head
  sum; XLA may combine them across tables) plus 2 for the shared key
  planes and the all-reduces of the step's scalars (example count,
  logloss) that the partitioner adds; none has the table's rows, none
  sits in a loop (tests/test_exchange.py holds the compiled program to
  that).  The optimizer pass and the gradient buffers stay elementwise
  on each chip's block.  What the partitioner chose when this was left
  to it: the whole table all-gathered every step and ~3*10^4
  collectives inside the hot head's scans (PERF.md section 6, PR 27).
  The touched-rows update modes (``update_mode="sparse"``, the sparse
  window end) still leave their gather/scatter to the partitioner.

Bootstrap: where the reference needed a scheduler + DMLC_* env vars
(scripts/local.sh:8-19), multi-host here is ``jax.distributed
.initialize()`` + SPMD; single-host multi-device needs nothing.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def make_mesh(num_devices: int = 0, devices: list | None = None) -> Mesh:
    devs = list(devices) if devices is not None else list(jax.devices())
    if num_devices:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, have {len(devs)}"
            )
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (DATA_AXIS,))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded, columns replicated."""
    return NamedSharding(mesh, P(DATA_AXIS, None))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dimension sharded."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())

"""Chip probe of the one-hot field contraction (PERF.md section 7, PR 32):
``models/blocks.py::field_contract``, which asks for float32
(``Precision.HIGHEST``), against the same einsum at the TPU's default
precision, at the shapes of the three blocks that call it.

    python scripts/probe_field_contract.py [--seeds 3] [--calls 20] [--rows 16384]

Per shape ``[B, K, F] x [B, K, E] -> [B, F, E]`` (B = ``--rows``, K = 40
entries a row over F = 40 fields; E = 10: ``mvm_slot_terms``; E = 8:
``field_sum_tower`` at ``emb_dim`` 8, wide&deep / DCN / two-tower; E = 160:
``ffm_field_interaction`` at F x ``ffm_v_dim`` 4) and per form: the largest
error of a field sum against float64 on the host, over the largest field
sum, and the milliseconds of a call (``--calls`` chained calls closed by one
fetch), forward and, for the families that differentiate through it, the
transpose with respect to the rows.

``--pick`` probes the contraction's transpose as MVM's backward uses it
(PERF.md section 6, PR 33) and nothing else: each entry's own field's row
of ``1 + s`` [B, F, E = 10], by ``take_along_axis`` (one index an entry)
against ``models/blocks.py::field_pick`` (``"bkf,bfe->bke"`` at HIGHEST)
and the same einsum at default precision: the milliseconds of a call and
the largest absolute difference from ``take_along_axis`` (0 for HIGHEST:
the one-hot is 0/1 and the bfloat16 pieces of the other operand add back
to it; ~4e-3 at default, 8 bits of a number near 1).  Every tenth slot
is outside [0, F): the gather clips it, the contraction reads 0, and the
comparison is over the slots in range, as ``grad_logit`` masks the rest.
Exits 1 where HIGHEST differs.

A measurement path: exits 1 without a TPU; every line names the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

K, F = 40, 40
SHAPES = {"mvm_slot_terms": 10, "field_sum_tower": 8, "ffm_field_interaction": 160}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--pick", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from xflow_tpu.models.blocks import field_contract

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("probe_field_contract: no TPU, no number", file=sys.stderr)
        return 1

    def default(onehot, rows):
        return jnp.einsum("bkf,bke->bfe", onehot, rows)

    forms = {"default": default, "highest": field_contract}

    def timed(fn, *operands) -> float:
        """ms a call: chained through a scalar so no call can be dropped."""
        def chained(carry, *ops):
            out = fn(ops[0], ops[1] + carry)
            return jnp.sum(out) * 0.0  # the whole result, and a 0 to chain on
        step = jax.jit(chained)
        carry = step(jnp.float32(0.0), *operands)
        carry.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.calls):
            carry = step(carry, *operands)
        carry.block_until_ready()
        return (time.perf_counter() - t0) / args.calls * 1e3

    if args.pick:
        return probe_pick(args, dev, timed)

    for block, e in SHAPES.items():
        for seed in range(1, args.seeds + 1):
            rng = np.random.default_rng(seed)
            slots = rng.integers(0, F, (args.rows, K))
            onehot = np.eye(F, dtype=np.float32)[slots]
            # gathered rows as drawn: N(0, 1) * 1e-2, values 1
            rows = (rng.normal(0.0, 1.0, (args.rows, K, e)) * 1e-2).astype(np.float32)
            want = np.matmul(  # [B, F, K] @ [B, K, E] in float64
                onehot.transpose(0, 2, 1).astype(np.float64), rows.astype(np.float64)
            )
            oh, rw = jnp.asarray(onehot), jnp.asarray(rows)
            line = {
                "platform": dev.platform, "kind": dev.device_kind,
                "block": block, "shape": [args.rows, K, F, e], "seed": seed,
                "max_abs_field_sum": float(np.abs(want).max()),
            }
            for name, fn in forms.items():
                got = np.asarray(jax.jit(fn)(oh, rw), np.float64)
                err = float(np.abs(got - want).max())
                line[name] = {
                    "max_abs_err": err,
                    "rel": err / line["max_abs_field_sum"],
                }
                if seed == 1:
                    line[name]["forward_ms"] = timed(fn, oh, rw)
                    line[name]["transpose_ms"] = timed(
                        lambda o, g, fn=fn: jax.vjp(
                            lambda r: fn(o, r), jnp.zeros_like(rw)
                        )[1](g)[0],
                        oh, jnp.asarray(want, jnp.float32),
                    )
            print(json.dumps(line), flush=True)
    return 0


def probe_pick(args, dev, timed) -> int:
    import jax
    import jax.numpy as jnp

    from xflow_tpu.models.blocks import field_pick

    e = SHAPES["mvm_slot_terms"]

    def onehot(slots, one_plus):
        return jax.nn.one_hot(slots, F, dtype=one_plus.dtype)

    def gather(slots, one_plus):
        idx = jnp.clip(slots, 0, F - 1)
        return jnp.take_along_axis(one_plus, idx[:, :, None], axis=1)

    forms = {
        "take_along_axis": gather,
        "default": lambda s, o: jnp.einsum("bkf,bfe->bke", onehot(s, o), o),
        "highest": lambda s, o: field_pick(onehot(s, o), o),
    }
    differs = False
    for seed in range(1, args.seeds + 1):
        rng = np.random.default_rng(seed)
        slots = rng.integers(0, F, (args.rows, K)).astype(np.int32)
        outside = rng.random((args.rows, K)) < 0.1
        slots[outside] = rng.choice([-3, -1, F, F + 7], int(outside.sum()))
        # 1 + a field's sum of a few N(0, 1) * 1e-2 rows
        one_plus = (
            1.0 + rng.normal(0.0, 2e-2, (args.rows, F, e))
        ).astype(np.float32)
        sl, op = jnp.asarray(slots), jnp.asarray(one_plus)
        line = {
            "platform": dev.platform, "kind": dev.device_kind,
            "block": "mvm_pick", "shape": [args.rows, K, F, e], "seed": seed,
        }
        want = None
        for name, fn in forms.items():
            got = np.asarray(jax.jit(fn)(sl, op))
            if want is None:
                want, line[name] = got, {}
            else:
                line[name] = {
                    "max_abs_diff": float(np.abs(got - want)[~outside].max()),
                    "outside_max_abs": float(np.abs(got[outside]).max()),
                }
            if seed == 1:
                line[name]["ms"] = timed(fn, sl, op)
        differs |= line["highest"]["max_abs_diff"] != 0.0
        print(json.dumps(line), flush=True)
    return 1 if differs else 0


if __name__ == "__main__":
    sys.exit(main())

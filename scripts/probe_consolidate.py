"""Op-level probe: is cold-key consolidation worth its argsort?

Measures, per (D, dup_frac) on real-ish zipf key sets:
  a) plain scatter-add of [M, D] occurrence grads (the dense-mode path)
  b) argsort + segment-sum + scatter of [M, D] consolidated grads
     (Config.cold_consolidate) — same M slots, duplicates collapsed
     into sentinel-key slots that XLA scatter mode="drop" discards
  c) the argsort alone (the price), and segment_sum alone

Prints one JSON line per config.  Run on the real chip:

    python scripts/probe_consolidate.py
"""

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def sync(x):
    import jax

    jax.block_until_ready(x)


def timeit(fn, *args, iters=8, warmup=2):
    for _ in range(warmup):
        sync(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.time() - t0) / iters


def main():
    if "--cpu" in sys.argv:  # smoke-test mode, no chip
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp

    from xflow_tpu.ops.sparse import consolidate_apply, consolidate_plan

    t_log2 = 24
    t = 1 << t_log2
    rng = np.random.default_rng(0)
    for m_log2 in (20, 21):
        m = 1 << m_log2
        # zipf(1.2) keys over a 3.9M vocab reduced mod 2^24 — the bench
        # dataset's distribution (gen_synth), which sets the real
        # duplicate rate
        raw = rng.zipf(1.2, size=2 * m)
        keys_np = (raw[raw < 3_900_000][:m] % t).astype(np.int32)
        dup = 1.0 - len(np.unique(keys_np)) / m
        keys = jnp.asarray(keys_np)
        for d in (1, 4, 8, 10):
            grads = jnp.asarray(
                rng.standard_normal((m, d)).astype(np.float32)
            )
            gbuf = jnp.zeros((t, d), jnp.float32)

            plain = jax.jit(
                lambda gb, k, g: gb.at[k].add(g, mode="drop")
            )

            def cons_fn(gb, k, g):
                order, seg, ukeys = consolidate_plan(k, t)
                return gb.at[ukeys].add(
                    consolidate_apply(g, order, seg), mode="drop"
                )

            cons = jax.jit(cons_fn)
            sort_only = jax.jit(lambda k: jnp.argsort(k))

            # does a dropped (sentinel) slice cost like a live one?  If
            # drops are ~free, consolidation saves the full duplicate
            # fraction of scatter time; if not, only the segment-sum's
            # bandwidth matters.
            all_sentinel = jnp.full_like(keys, t)
            row = {
                "m_log2": m_log2,
                "d": d,
                "dup_frac": round(dup, 3),
                "plain_ms": round(timeit(plain, gbuf, keys, grads) * 1e3, 3),
                "consolidated_ms": round(
                    timeit(cons, gbuf, keys, grads) * 1e3, 3
                ),
                "argsort_ms": round(timeit(sort_only, keys) * 1e3, 3),
                "all_dropped_ms": round(
                    timeit(plain, gbuf, all_sentinel, grads) * 1e3, 3
                ),
                "backend": jax.devices()[0].platform,
            }
            row["plain_ns_per_slice"] = round(
                row["plain_ms"] * 1e6 / m, 2
            )
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

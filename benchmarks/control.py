"""A cell's control, on the chip at the cell's own size: a run of ``run.py``
with the plain reference computed in the precision below the one the
configurations state (float32: its operands rounded to bfloat16, which is
what a default-precision contraction on the TPU makes of them) in the
reference's place.  ``correct`` has to come out false, by the check that
holds the program to the reference and by no other.

    python3 benchmarks/control.py --workload lr_tb.train_packed --seed 7 [--seconds 5]

A training cell's reference step is handed its gathered rows, and the dense
parameters of a family that owns any, rounded; a serving cell's expected
scores are summed from the artifact's weights rounded.  Everything else is the cell's own run: its corpus, its warm-up, a
short window, its check.  Prints one JSON line: the checks that failed and
every number compared beside its limit; never a result line.  Exit 0 where
the control failed as it must, 1 where it passed or another check failed.
The readings belong in PERF.md beside the sound runs' (section 2): a limit
lies between the two.  ``tests/test_reference.py`` keeps the control at toy
size.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REFERENCE_CHECKS = {"steps_match_reference", "answers_match_reference"}


class InBfloat16:
    """A family of ``reference/`` whose forward and backward see the gathered
    rows, and its dense parameters where it has any, rounded to bfloat16 (a
    gradient that autodiff takes through the rounding comes back rounded
    too); the FTRL and SGD recurrences stay in float32.  The ReLU arguments
    the check looks at (``reference/wide_deep.py::relu_arguments``) are those
    of the rounded forward: ``logit`` is the one entry both go through."""

    def __init__(self, family):
        self.family = family

    def __getattr__(self, name):  # TABLES, USES_FIELDS, DENSE, matmuls
        return getattr(self.family, name)

    @staticmethod
    def _rounded(tree):
        """Every float array of ``tree`` through bfloat16; field ids and
        counts as they are."""
        import jax
        import jax.numpy as jnp

        def one(a):
            if not jnp.issubdtype(jnp.result_type(a), jnp.floating):
                return a
            return a.astype(jnp.bfloat16).astype(a.dtype)

        return jax.tree.map(one, tree)

    def logit(self, rows, x, *rest):
        return self.family.logit(self._rounded(rows), x, *self._rounded(rest))

    def grad_logit(self, rows, x, *fields):
        return self.family.grad_logit(self._rounded(rows), x, *fields)


class _RoundedOnRead:
    """An artifact's weight file, rounded to bfloat16 where it is read."""

    def __init__(self, arr):
        self.arr = arr

    def __getitem__(self, at):
        import ml_dtypes
        import numpy as np

        return np.asarray(self.arr[at]).astype(ml_dtypes.bfloat16).astype(np.float32)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run
    from benchmarks.drivers import serve_open_loop
    from benchmarks.harness import manifest

    reference, served = manifest.reference, serve_open_loop.Served.__init__

    @functools.cache  # one object a family: the reference step compiles once
    def lowered(family: str):
        return InBfloat16(reference(family))

    def rounded(self, ctx):
        served(self, ctx)
        self.weights = [(start, _RoundedOnRead(arr)) for start, arr in self.weights]

    manifest.reference, serve_open_loop.Served.__init__ = lowered, rounded
    try:
        with contextlib.redirect_stdout(io.StringIO()):
            run.main(
                ["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", str(args.seconds), "--trace", "0"]
                + ["--rehearsal"] * args.rehearsal
            )
    finally:
        manifest.reference, serve_open_loop.Served.__init__ = reference, served
    with open(os.path.join(ROOT, ".bench_cache", f"{args.workload}.last.json")) as f:
        last = json.load(f)
    failed = sorted(k for k, ok in last["checks"].items() if not ok)
    print(json.dumps({
        "control": "bfloat16", "workload": args.workload, "seed": args.seed,
        "checks_failed": failed, "compared": last["compared"],
    }), flush=True)
    return 0 if failed and set(failed) <= REFERENCE_CHECKS else 1


if __name__ == "__main__":
    sys.exit(main())

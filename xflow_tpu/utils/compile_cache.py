"""Persistent XLA compile cache, placeable from outside.

Every entry point (train, serve and stream CLIs, benchmarks/run.py
through its harness, chip_smoke.py) calls ``enable_compile_cache``
before its first compile, so a second run of the same geometry — and a
second process of the same run — loads its programs instead of
compiling them.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
module sets no directory.  Otherwise the cache lives at ONE fixed path
inside the checkout: the directory is part of the cache key, so a path
built from a temp dir, a pid or the time would never hit.

A run pinned to the CPU (``JAX_PLATFORMS=cpu``, ``--platform cpu``) is
left alone: XLA:CPU compiles this repo's programs in seconds, a cached
CPU executable is tied to the instruction set of the host that built it
while a checkout gets copied between hosts, and jaxlib 0.9.0 logs a
multi-kilobyte machine-feature warning for every CPU entry it loads.
"""

from __future__ import annotations

import os
import re

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory in use (None
    on a CPU-pinned run).  Call after any ``jax_platforms`` update and
    before the first compile; it initializes no backend.  Also where
    the process's compile watch is installed (``obs/startup.py``): every
    entry point passes here before its first compile, CPU-pinned or
    not."""
    import jax

    from xflow_tpu.obs import startup

    startup.watch_compiles()
    platforms = jax.config.jax_platforms or ""
    if platforms.split(",")[0].strip() == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # JAX only persists programs that took >= 1 s to compile by default;
    # the serve buckets and the eval step compile faster than that on
    # the chip and would be paid again by every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def key_by_source() -> None:
    """From here on this process keys its compiles WITH their source
    metadata (``op_name`` paths, file names relative to the checkout).
    JAX leaves metadata out of the key by default, so a program loaded
    from the persistent cache carries the ``xf.*`` scopes of whichever
    source first compiled it, or none.  A run that reads those scopes
    off its running program (``TrainStep.op_scopes``; the trainer calls
    this when its Obs is live, before its first compile) must run what
    THIS source compiles: a hit only when this very source ran keyed
    like this before, from any checkout.  Idempotent; never undone."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", re.escape(_CHECKOUT)
    )

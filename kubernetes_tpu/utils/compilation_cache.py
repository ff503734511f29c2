"""Persistent XLA compilation cache for cold-start control.

The scheduler's first binding decision waits on XLA/Mosaic compiles
(tens of seconds per scan shape). The reference's CI disables tests that
blow its time window rather than paying recompiles (scheduler_perf
scheduler_test.go:93-101); the TPU-native answer is jax's persistent
compilation cache: compiled executables are keyed by (HLO, compile
options, backend) and reloaded from disk on the next process start, so
only the FIRST run of a given shape pays the compile.

Where the cache lives is decided OUTSIDE the program: when
JAX_COMPILATION_CACHE_DIR is set, jax already points there and this
module sets no directory; otherwise the cache is `<checkout>/.xla_cache`
(a fixed path — the path is part of the cache key, so a directory that
moves never hits). Enabled by every bench/driver entry point and
chip_smoke.py; tests keep the default in-memory cache (CPU compiles
there are cheap and the suite mutates shapes constantly).
"""

from __future__ import annotations

import os

from . import knobs

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def enable_persistent_cache() -> str:
    """Turn on jax's on-disk compilation cache; returns the directory in
    use ("" when KTPU_COMPILATION_CACHE=0 switched it off)."""
    if not knobs.get_bool("KTPU_COMPILATION_CACHE"):
        return ""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything that took meaningful compile time; the default
    # min-entry gate would skip small-but-hot programs
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir

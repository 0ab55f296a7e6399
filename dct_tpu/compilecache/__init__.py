"""Persistent compile cache + AOT executables (ROADMAP item 5).

Restart debt is a steady-state cost of continuous training: every
supervised relaunch (docs/ROBUSTNESS.md) and every serving worker
re-traces and re-compiles XLA programs whose identity — (program,
family, config_hash, mesh) — the compile accounting layer already
fingerprints (docs/OBSERVABILITY.md §compile). This package erases that
debt twice over:

1. :func:`enable_from_env` arms JAX's **persistent compilation
   cache** at the directory the one resolver names
   (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``) so
   any re-trace of an identical program is a disk hit instead of an
   XLA compile — wired into trainer startup, the supervised
   relauncher, and the serving entry point.
2. :class:`ExecutableStore` **AOT-serializes the hot executables**
   (the fused epoch/train-step programs, the jitted batched scorer)
   via ``jax.jit(...).lower().compile()`` + executable serialization,
   keyed by the exact compile-accounting identity, stored
   tmp+``os.replace`` inside the checkpoint/package layout — a resume
   snapshot carries its pre-compiled steps, a deployed package its
   pre-compiled scorer.

Every artifact carries version/jaxlib/backend fingerprints in its
header: a mismatched artifact is a **loud miss** (event + fallback to
a normal jit compile), never a wrong execution. Cache-hit runs are
bit-identical to cache-miss runs — the serialized executable IS the
executable the miss path would have built on this machine.
"""

from dct_tpu.compilecache.cache import (
    CACHE_DIR_ENV,
    DEFAULT_CACHE_DIR,
    aot_enabled,
    cache_mode,
    enable_from_env,
    enabled,
    export_env,
    resolve_cache_dir,
    warm_sizes,
)
from dct_tpu.compilecache.aot import (
    CachedProgram,
    ExecutableStore,
    runtime_fingerprint,
    signature_of,
    store_from_env,
    warm_package_scorer,
)

__all__ = [
    "CACHE_DIR_ENV",
    "DEFAULT_CACHE_DIR",
    "CachedProgram",
    "ExecutableStore",
    "aot_enabled",
    "cache_mode",
    "enable_from_env",
    "enabled",
    "export_env",
    "resolve_cache_dir",
    "runtime_fingerprint",
    "signature_of",
    "store_from_env",
    "warm_package_scorer",
    "warm_sizes",
]

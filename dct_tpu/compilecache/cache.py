"""The one compile-cache resolver: where compiled programs are kept.

The directory is placed from OUTSIDE the program, by the variable JAX
itself reads:

- ``JAX_COMPILATION_CACHE_DIR`` set — JAX already uses that directory
  for its persistent XLA cache; this module sets no directory in code
  and the AOT executable store (:mod:`.aot`) arms off the same variable;
- unset — the directory is :data:`DEFAULT_CACHE_DIR`,
  ``<checkout>/.jax_cache`` (gitignored), computed from this package's
  own location. The path is part of every cache key, so it must never
  depend on the working directory, a temporary name, a pid or the time.

Mode resolution (``DCT_COMPILE_CACHE``):

- ``off`` (and the usual falsy spellings) — nothing armed here (a
  ``JAX_COMPILATION_CACHE_DIR`` in the environment is still JAX's own
  business);
- ``auto`` (default) — armed **iff** ``JAX_COMPILATION_CACHE_DIR`` is
  set: the operator placing a cache dir is the opt-in;
- ``on`` / ``force`` — armed; the dir defaults to
  :data:`DEFAULT_CACHE_DIR` when the variable is unset.

The persistent XLA cache must be configured **before this process's
first compile**: JAX memoizes whether the cache is in use at the first
compilation, so a late :func:`enable_from_env` silently does nothing
for the rest of the process (the AOT store has no such constraint — it
is pure file I/O around ``lower().compile()``). Every long-running
entry point (trainer fit, the serving CLI, the MPMD stage worker, the
chip smoke) therefore calls it before touching jax-compiled code.

Cache directories are **per-machine**: XLA:CPU executables are pinned
to the host's CPU features, so a dir shared over NFS across
heterogeneous hosts can produce entries another host cannot run. The
AOT artifact header fingerprints backend/device/arch and degrades to a
loud miss; the XLA cache keys include the compile options but not the
micro-architecture — keep the dir host-local.
"""

from __future__ import annotations

import os
from collections.abc import Mapping

#: The variable JAX reads its persistent-cache directory from.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache lives when nothing outside placed it.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_FALSY = ("0", "false", "no", "off", "disable", "none")


def cache_mode(env: Mapping | None = None) -> str:
    """``off`` | ``auto`` | ``on`` (normalized)."""
    env = os.environ if env is None else env
    raw = str(env.get("DCT_COMPILE_CACHE", "auto")).strip().lower()
    if raw in _FALSY:
        return "off"
    if raw in ("on", "force", "1", "true", "yes"):
        return "on"
    return "auto"


def resolve_cache_dir(env: Mapping | None = None) -> str | None:
    """The persistent-XLA-cache dir the env selects (None = cache off)."""
    env = os.environ if env is None else env
    mode = cache_mode(env)
    if mode == "off":
        return None
    explicit = env.get(CACHE_DIR_ENV)
    if explicit:
        return str(explicit)
    return DEFAULT_CACHE_DIR if mode == "on" else None


def enabled(env: Mapping | None = None) -> bool:
    """True when the compile cache (XLA dir + AOT store) is armed."""
    return resolve_cache_dir(env) is not None


def aot_enabled(env: Mapping | None = None) -> bool:
    """AOT executable serialization on top of the enabled cache
    (``DCT_COMPILE_CACHE_AOT``, default on)."""
    env = os.environ if env is None else env
    if not enabled(env):
        return False
    raw = str(env.get("DCT_COMPILE_CACHE_AOT", "1")).strip().lower()
    return raw not in _FALSY


def warm_sizes(env: Mapping | None = None) -> list[int]:
    """Packaging-time scorer pre-compile batch sizes
    (``DCT_COMPILE_CACHE_WARM_SIZES``, comma-separated; empty = skip)."""
    env = os.environ if env is None else env
    raw = str(env.get("DCT_COMPILE_CACHE_WARM_SIZES", ""))
    sizes = []
    for tok in raw.split(","):
        tok = tok.strip()
        if tok.isdigit() and int(tok) > 0:
            sizes.append(int(tok))
    return sorted(set(sizes))


def enable_from_env() -> str | None:
    """Arm JAX's persistent compilation cache at the resolved dir.

    Returns the dir in use, or None when the cache is off. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it, so no
    directory is set here; only the default dir is pointed at in code.
    ``DCT_COMPILE_CACHE_MIN_COMPILE_S`` (default 0: cache everything)
    maps to ``jax_persistent_cache_min_compile_time_secs``.
    """
    path = resolve_cache_dir()
    if path is None:
        return None
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ.get("DCT_COMPILE_CACHE_MIN_COMPILE_S", "0") or 0.0),
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def export_env(child_env: dict, current_env: Mapping | None = None) -> None:
    """Pin the resolved cache dir into a child environment as
    ``JAX_COMPILATION_CACHE_DIR`` (the supervised relauncher calls
    this): every relaunch attempt must agree on ONE directory, or
    attempt 2 cannot hit what attempt 1 compiled. No-op when the cache
    is off. ``current_env`` is the merged view the children will
    actually see (defaults to this process's environ overlaid with
    ``child_env``)."""
    merged = dict(os.environ if current_env is None else current_env)
    merged.update({k: v for k, v in child_env.items() if v is not None})
    path = resolve_cache_dir(merged)
    if path is not None:
        child_env.setdefault(CACHE_DIR_ENV, os.path.abspath(path))

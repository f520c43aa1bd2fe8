"""Where the persistent XLA compilation cache lives — one function for
the linker, the serve engine, ``chipbench/run.py`` and ``chip_smoke.py``.

Compiling is a large share of every cold run (each per-rule kernel, EM
program and serve bucket shape is a separate XLA program), and the cache
persists compiled executables across PROCESSES. Its directory is part of
how a later process finds the entries, so it must not move:

  * ``JAX_COMPILATION_CACHE_DIR`` set: that directory and no other. jax
    reads the variable itself; nothing here touches the directory.
  * unset: the ``compilation_cache_dir`` setting when given, else
    :func:`default_cache_dir` — one fixed, git-ignored directory beside
    the package, never a temporary name, pid or time.

Either way the two thresholds that make the small programs cacheable are
applied (the per-rule kernels are what repeat), unless the user tuned them
through jax's own environment variables.

On the CPU backend, entries placed by this module land in a
``cpu-<fp16>/`` subdirectory keyed by the host's target-feature
fingerprint (utils/envfp.py): XLA:CPU entries embed exact machine features
and reloading one compiled under different target flags "could lead to
SIGILL" (jax's own warning).
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("splink_tpu")

CACHE_DIRNAME = ".jax_cache"

# jax binds its cache object to the first directory it initialises with, so
# a mid-process change would make jax.config report one path while entries
# keep landing in another: the first caller wins for the process.
_applied: str | None = None


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``, resolved from the package's location."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), CACHE_DIRNAME)


def enable_compilation_cache(setting: str | None = None) -> str:
    """Enable the persistent compilation cache; returns the directory in
    effect. ``setting`` is the ``compilation_cache_dir`` settings value
    (empty or None: the fixed default). Initialises the jax backend (the
    CPU keying needs its name), so a backend that cannot come up raises
    here."""
    global _applied
    import jax

    # cache small programs too — but never clobber a user's own tuning
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if _applied is not None:
        return _applied
    path = os.path.expanduser(setting) if setting else default_cache_dir()
    if jax.default_backend() == "cpu":
        from .envfp import cpu_target_fingerprint

        path = os.path.join(path, f"cpu-{cpu_target_fingerprint()[:16]}")
    jax.config.update("jax_compilation_cache_dir", path)
    _applied = path
    logger.debug("persistent compilation cache at %s", path)
    return path

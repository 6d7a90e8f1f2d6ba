"""paddle_tpu.sysconfig (ref: python/paddle/sysconfig.py)."""
from __future__ import annotations

import os


def get_include():
    """ref: paddle.sysconfig.get_include — C headers directory (the
    native helpers' sources live under _native)."""
    return os.path.join(os.path.dirname(__file__), '_native')


def get_lib():
    """ref: paddle.sysconfig.get_lib — directory holding the BUILT
    native libraries (the same cache _native compiles into)."""
    cache = os.environ.get(
        'PADDLE_TPU_CACHE',   # the SAME var _native/__init__.py honors
        os.path.join(os.path.expanduser('~'), '.cache', 'paddle_tpu'))
    os.makedirs(cache, exist_ok=True)
    return cache


_COMPILATION_CACHE_DIR = None


def enable_persistent_compilation_cache(path=None):
    """Wire jax's on-disk executable cache so a restarted process skips
    XLA compilation. The one place that decides where the cache lives:

    - an explicit `path` wins (and replaces a previously wired one):
      `paddle_tpu.aot` artifacts pass their own directory, because there
      the directory *is* the artifact;
    - otherwise `JAX_COMPILATION_CACHE_DIR` when it is set — the handle a
      deployment places the cache with from outside; jax has already read
      it, and no other directory is set in code;
    - otherwise `<checkout>/.jax_cache`, next to the package. Always the
      same name: a directory that moves between runs never hits.

    Used by `chip_smoke.py` and `bench.py` at their top, by
    `DecodeEngine(persistent_cache=True)` and by
    `PADDLE_TPU_PERSISTENT_CACHE=1` (an on/off switch only). Thresholds
    drop to zero so even small decode-step executables persist.
    Idempotent; returns the cache directory.

    The wired directory is observable in telemetry: a
    `compile.persistent_cache_dir` instant on the host trace (with the
    path) and a `compile.persistent_cache_enabled` gauge in the registry,
    so artifact-backed runs are distinguishable from cold ones."""
    global _COMPILATION_CACHE_DIR
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if path is not None:
        path = os.path.abspath(os.path.expanduser(path))
    else:
        path = os.environ.get('JAX_COMPILATION_CACHE_DIR') or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            '.jax_cache')
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    _COMPILATION_CACHE_DIR = path
    # jax freezes its is-the-cache-used verdict at the FIRST compile of
    # the process; wiring a directory after any compile (engine
    # construction alone compiles helpers) would silently never
    # persist. reset_cache() clears that verdict so the next compile
    # re-evaluates against the directory just wired.
    compilation_cache.reset_cache()
    from .observability import metrics as _obs
    from .observability import tracing as _obs_trace

    _obs.set_gauge('compile.persistent_cache_enabled', 1.0)
    _obs_trace.instant('compile.persistent_cache_dir', cat='compile',
                       path=path)
    return path


def persistent_compilation_cache_dir():
    """The directory enable_persistent_compilation_cache wired (None if
    never enabled this process)."""
    return _COMPILATION_CACHE_DIR


def restore_persistent_compilation_cache(path):
    """Re-wire the persistent cache to `path`, or UNWIRE it (back to what
    the environment gave jax at start-up) when `path` is None — the
    restore half of a scoped redirection (aot.build points the cache at
    an artifact directory for the duration of the build only; leaving it
    wired would leak every later compile of a still-serving builder into
    the artifact, and starve whatever dir the process had wired
    before)."""
    global _COMPILATION_CACHE_DIR
    if path is not None:
        return enable_persistent_compilation_cache(path)
    import jax

    _COMPILATION_CACHE_DIR = None
    jax.config.update('jax_compilation_cache_dir',
                      os.environ.get('JAX_COMPILATION_CACHE_DIR'))
    return None

"""Where this checkout keeps JAX's persistent compilation cache.

One rule for every entry point (``paddle_tpu.cli.main``, ``bench.py``,
``benchmarks/*.py``, each ``chip_smoke.py`` child): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and nothing is
set in code; otherwise the cache is ``<checkout>/.jax_cache`` (listed in
``.gitignore``). The path is part of the cache key's neighbourhood — a
directory that moves never hits — so it is never built from a temporary
name, a process id or the time. Child processes get the same directory:
through the inherited variable, or by running the same rule from the
same checkout. Every program is kept, however fast it compiled.
"""

import os
import threading

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_lock = threading.Lock()
_listening = False


def cache_dir() -> str:
    return os.environ.get(ENV) or os.path.join(CHECKOUT, ".jax_cache")


def _on_event(event: str, **_):
    key = _EVENTS.get(event)
    if key:
        with _lock:
            _counts[key] += 1


def configure() -> str:
    """Place the persistent cache (call before the first compile) and
    start counting its hits and misses. Returns the directory."""
    global _listening
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        # exported too, so a child that never reaches this function
        # (a bare ``python worker.py``) still lands in the same place
        os.environ[ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX keeps what compiled in under a second out of the cache, and
    # a serving artifact holds many such programs (a chunk program
    # whose sampler does not sort compiles in ~0.6 s): a warm start
    # that compiles each again is seconds slower than one that reads
    # them, so everything is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return path


def stats() -> dict:
    """``{"dir", "hits", "misses"}`` of this process since
    :func:`configure` — persistent-cache lookups, not jit-cache ones."""
    with _lock:
        return {"dir": cache_dir(), **_counts}

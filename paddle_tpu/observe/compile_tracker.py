"""Compile tracker: count XLA compilations and what caused them.

JAX's jit cache has no public hit/miss hook on this version, but a
cache miss is fully determined by the (function, abstract-signature)
pair — so tracking the signatures we have *seen* per function gives an
exact miss count from pure Python: a new signature on a tracked call IS
a compilation. The tracker records, per function:

- the miss count (``compile_cache_misses_total{fn=...}`` counter),
- the wall time of each miss-triggering call (compilation dominates it;
  ``compile_wall_seconds_total{fn=...}`` counter),
- the argument-shape signature that caused each miss (bounded list) —
  the evidence a recompile-storm postmortem needs ("the ragged last
  batch flips between 64 and 37").

A *recompile storm* — one function compiling ``storm_threshold``+ times
— logs a warning naming the latest offending signature, because the
usual cause (shape churn from the data pipeline) silently turns every
affected step into a multi-second compile.

jax-free at import time; ``arg_signature`` imports jax lazily and falls
back to a duck-typed container walk.
"""

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from paddle_tpu.observe import metrics as _metrics
from paddle_tpu.utils.logger import get_logger

log = get_logger("observe.compile")

_m_misses = _metrics.counter(
    "compile_cache_misses_total",
    "jit cache misses observed per tracked function (each is one "
    "XLA compilation)")
_m_compile_s = _metrics.counter(
    "compile_wall_seconds_total",
    "wall time of miss-triggering calls (compile-dominated)")


def _walk_leaves(obj, out):
    if isinstance(obj, dict):
        for k in sorted(obj, key=str):
            _walk_leaves(obj[k], out)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _walk_leaves(v, out)
    else:
        out.append(obj)


def arg_signature(*args) -> Tuple:
    """Abstract signature of a call: the (shape, dtype) of every array
    leaf, plus repr for non-array leaves (static scalars). Two calls
    with equal signatures hit the same jit cache entry."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # noqa: BLE001 — jax absent: best-effort walk
        leaves = []
        _walk_leaves(args, leaves)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
        else:
            sig.append(repr(leaf))
    return tuple(sig)


class CompileTracker:
    """Per-function signature sets + miss records (thread-safe)."""

    def __init__(self, storm_threshold: int = 5, max_miss_records: int = 64):
        self.storm_threshold = max(1, int(storm_threshold))
        self.max_miss_records = max_miss_records
        self._lock = threading.Lock()
        self._seen: Dict[str, set] = {}
        self._misses: Dict[str, List[dict]] = {}
        self._compile_s: Dict[str, float] = {}

    def record(self, name: str, sig: Tuple,
               wall_s: Optional[float] = None) -> bool:
        """Record one call of ``name`` with signature ``sig`` (from
        ``arg_signature``); ``wall_s`` is the call's wall time. Returns
        True when the signature is new — i.e. this call compiled."""
        with self._lock:
            seen = self._seen.setdefault(name, set())
            if sig in seen:
                return False
            seen.add(sig)
            miss = {"signature": repr(sig)[:512],
                    "wall_s": round(wall_s, 6) if wall_s else None,
                    "ts": round(time.time(), 3),
                    "miss_index": len(seen)}
            records = self._misses.setdefault(name, [])
            if len(records) < self.max_miss_records:
                records.append(miss)
            if wall_s:
                self._compile_s[name] = (self._compile_s.get(name, 0.0)
                                         + wall_s)
            n = len(seen)
        _m_misses.inc(fn=name)
        if wall_s:
            _m_compile_s.inc(wall_s, fn=name)
        if n >= self.storm_threshold and \
                (n - self.storm_threshold) % self.storm_threshold == 0:
            log.warning(
                "recompile storm: %r has compiled %d times — the jit "
                "cache is being missed repeatedly (usually shape churn "
                "from the data pipeline). Last miss signature: %s",
                name, n, miss["signature"])
        return True

    def track_call(self, name: str, fn, *args, **kwargs):
        """Call ``fn(*args, **kwargs)``, timing it and recording the
        signature — the one-liner for call sites that don't need the
        wrapper object. kwargs participate in the signature: a shape
        change in a keyword argument is a cache miss like any other."""
        return self.call_signed(name, arg_signature(args, kwargs), fn,
                                *args, **kwargs)

    def call_signed(self, name: str, sig: Tuple, fn, *args, **kwargs):
        """``track_call`` for a caller that took ``sig =
        arg_signature(args, kwargs)`` ahead of the call (the serving
        engine times the signature apart from the dispatch)."""
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.record(name, sig, time.perf_counter() - t0)
        return out

    def count(self, name: Optional[str] = None) -> int:
        """Compilations observed (for one function, or all)."""
        with self._lock:
            if name is not None:
                return len(self._seen.get(name, ()))
            return sum(len(s) for s in self._seen.values())

    def compile_seconds(self, name: Optional[str] = None) -> float:
        with self._lock:
            if name is not None:
                return self._compile_s.get(name, 0.0)
            return sum(self._compile_s.values())

    def misses(self, name: str) -> List[dict]:
        with self._lock:
            return list(self._misses.get(name, ()))

    def snapshot(self) -> Dict[str, dict]:
        """Per-function {count, compile_seconds, misses} — the flight
        recorder / healthz view."""
        with self._lock:
            return {name: {"count": len(seen),
                           "compile_seconds": round(
                               self._compile_s.get(name, 0.0), 6),
                           "misses": list(self._misses.get(name, ()))}
                    for name, seen in self._seen.items()}

    def clear(self):
        with self._lock:
            self._seen.clear()
            self._misses.clear()
            self._compile_s.clear()


_default = CompileTracker()


def default_compile_tracker() -> CompileTracker:
    return _default


def track_compiles(fn, name: Optional[str] = None,
                   tracker: Optional[CompileTracker] = None):
    """Wrap a jitted callable so every call is signature-tracked:
    ``step = observe.track_compiles(jax.jit(step), "train_step")``."""
    import functools
    tracker = tracker or _default
    label = name or getattr(fn, "__name__", repr(fn))

    @functools.wraps(fn, assigned=("__name__", "__doc__"), updated=())
    def wrapper(*args, **kwargs):
        return tracker.track_call(label, fn, *args, **kwargs)

    wrapper.tracker = tracker
    return wrapper

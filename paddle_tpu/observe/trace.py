"""Trace scopes: nested named timing regions on the profiler's clock.

Wraps ``utils/stat.py``'s StatSet (the reference's REGISTER_TIMER_INFO
accumulators), records each closed scope into the bounded span buffer
(``observe/chrome_trace.py``) and, whenever jax is already imported,
opens a ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` so
the region lands in the profiler's own trace beside the device's
operations. Outside a profiler session an annotation is a flag test, so
there is nothing to switch on. Without jax in the process the same
scopes are pure wall-clock timers — observability code never becomes a
hard jax dependency.

Scopes nest: a ``trace_scope("backward")`` inside ``trace_scope("step")``
accumulates under the qualified name ``step/backward`` (per thread), so a
StatSet print shows the call tree, flattened. The annotation carries the
name the scope was given: a top-level ``engine/decode_sync`` reads so in
the profiler, a nested ``convert`` reads ``convert`` under its parent.
"""

import sys
import threading
import time
from typing import Optional

from paddle_tpu.observe import chrome_trace as _chrome
from paddle_tpu.utils import stat as _stat

_tls = threading.local()

# (TraceAnnotation, StepTraceAnnotation), resolved once, the first time
# a scope opens with jax in the process; False when jax has no profiler
_annotations = None


def _stack():
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def current_scope() -> str:
    """The '/'-joined active scope path of this thread ('' at top level)."""
    return "/".join(_stack())


def _resolve_annotations():
    global _annotations
    try:
        from jax import profiler
        _annotations = (profiler.TraceAnnotation,
                        profiler.StepTraceAnnotation)
    except Exception:  # noqa: BLE001 — observability must not crash the job
        _annotations = False


def _annotation(step: bool, name: str, use_profiler, kw):
    if _annotations is None and "jax" in sys.modules:
        _resolve_annotations()
    if not _annotations or use_profiler is False:
        return None
    return _annotations[step](name, **kw)


class _Scope:
    """The context manager behind both scopes (a class, not a generator:
    the serving engine opens nine of these a step)."""

    __slots__ = ("_name", "_stats", "_annotation", "_args", "_qualified",
                 "_wall0", "_start")

    def __init__(self, name, stats, annotation, args):
        self._name = name
        self._stats = stats or _stat.global_stats
        self._annotation = annotation
        self._args = args

    def __enter__(self) -> str:
        stack = _stack()
        stack.append(self._name)
        self._qualified = "/".join(stack)
        self._wall0 = time.time()
        self._start = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self._qualified

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        dur = time.perf_counter() - self._start
        self._stats.get(self._qualified).add(dur)
        _chrome.record_span(self._qualified, self._wall0, dur,
                            args=self._args)
        _stack().pop()
        return False


def trace_scope(name: str, stats: Optional[_stat.StatSet] = None,
                use_profiler: Optional[bool] = None,
                args: Optional[dict] = None):
    """Open a named timing scope.

    - accumulates wall time into ``stats`` (default: the global StatSet)
      under the nesting-qualified name, e.g. ``train_step/forward``
    - records the closed span, with ``args``, into the span buffer
    - opens a ``jax.profiler.TraceAnnotation`` named ``name`` carrying
      ``args`` (``use_profiler=False`` leaves the annotation out)
    """
    return _Scope(name, stats,
                  _annotation(False, name, use_profiler, args or {}), args)


def step_scope(step_num: int, name: str = "train",
               stats: Optional[_stat.StatSet] = None,
               use_profiler: Optional[bool] = None):
    """Mark one training step: a ``jax.profiler.StepTraceAnnotation``
    (xprof's step-time view keys on it) that accumulates into the
    ``name`` timer. Participates in the nesting stack like trace_scope,
    so an inner ``trace_scope("region")`` accumulates under
    ``train_step/region``."""
    return _Scope(name, stats,
                  _annotation(True, name, use_profiler,
                              {"step_num": step_num}), {"step": step_num})


def traced(name: Optional[str] = None, **scope_kw):
    """Decorator form: ``@traced("encode")`` wraps the call in a
    trace_scope named after the function by default."""

    def deco(fn):
        import functools
        scope = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with trace_scope(scope, **scope_kw):
                return fn(*a, **kw)

        return wrapper

    return deco

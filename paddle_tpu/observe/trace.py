"""Trace scopes: nested named timing regions that show up in xprof.

Wraps ``utils/stat.py``'s StatSet (the reference's REGISTER_TIMER_INFO
accumulators) and, when profiling is enabled AND jax is importable, also
opens ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` scopes so
hot-loop regions land in the xprof timeline on real TPUs. On CPU (or with
profiling off, or without jax at all) the same scopes degrade to pure
wall-clock timers — observability code never becomes a hard jax
dependency.

Scopes nest: a ``trace_scope("backward")`` inside ``trace_scope("step")``
accumulates under the qualified name ``step/backward`` (per thread), so a
StatSet print shows the call tree, flattened.
"""

import contextlib
import threading
import time
from typing import Optional

from paddle_tpu.observe import chrome_trace as _chrome
from paddle_tpu.utils import stat as _stat

_tls = threading.local()


def _stack():
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def current_scope() -> str:
    """The '/'-joined active scope path of this thread ('' at top level)."""
    return "/".join(_stack())


def _profiler_ctx(kind: str, name: str, **kw):
    """A profiler annotation context, or nullcontext when the profiler
    is unavailable — never an ImportError."""
    try:
        import jax.profiler
        return getattr(jax.profiler, kind)(name, **kw)
    except Exception:  # noqa: BLE001 — observability must not crash the job
        return contextlib.nullcontext()


def _profiling_enabled(use_profiler: Optional[bool]) -> bool:
    if use_profiler is not None:
        return use_profiler
    from paddle_tpu.utils.flags import GLOBAL_FLAGS
    return bool(GLOBAL_FLAGS.get("profile", False))


@contextlib.contextmanager
def trace_scope(name: str, stats: Optional[_stat.StatSet] = None,
                use_profiler: Optional[bool] = None):
    """Open a named timing scope.

    - accumulates wall time into ``stats`` (default: the global StatSet)
      under the nesting-qualified name, e.g. ``train_step/forward``
    - opens a ``jax.profiler.TraceAnnotation`` when profiling is on
    """
    stats = stats or _stat.global_stats
    stack = _stack()
    stack.append(name)
    qualified = "/".join(stack)
    ctx = (_profiler_ctx("TraceAnnotation", name)
           if _profiling_enabled(use_profiler) else contextlib.nullcontext())
    wall0 = time.time()
    start = time.perf_counter()
    try:
        with ctx:
            yield qualified
    finally:
        dur = time.perf_counter() - start
        stats.get(qualified).add(dur)
        _chrome.record_span(qualified, wall0, dur)
        stack.pop()


@contextlib.contextmanager
def step_scope(step_num: int, name: str = "train",
               stats: Optional[_stat.StatSet] = None,
               use_profiler: Optional[bool] = None):
    """Mark one training step. With profiling on this is a
    ``jax.profiler.StepTraceAnnotation`` (xprof's step-time view keys on
    it); always accumulates into the ``name`` timer. Participates in the
    nesting stack like trace_scope, so an inner ``trace_scope("region")``
    accumulates under ``train_step/region``."""
    stats = stats or _stat.global_stats
    stack = _stack()
    stack.append(name)
    qualified = "/".join(stack)
    ctx = (_profiler_ctx("StepTraceAnnotation", name, step_num=step_num)
           if _profiling_enabled(use_profiler) else contextlib.nullcontext())
    wall0 = time.time()
    start = time.perf_counter()
    try:
        with ctx:
            yield
    finally:
        dur = time.perf_counter() - start
        stats.get(qualified).add(dur)
        _chrome.record_span(qualified, wall0, dur, args={"step": step_num})
        stack.pop()


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

"""HBM budgeting (reference: paddle/memory/ BuddyAllocator — the slot the
XLA runtime mostly absorbs: allocation itself belongs to XLA/PJRT, but the
*budgeting* decisions the reference made with its pool — "will this fit,
what batch size should I run" — live here).

Tools:
- ``device_memory_stats()`` — per-device HBM capacity/usage
- ``step_memory(fn, *args)`` — compiled peak/argument/temp bytes for a step
- ``max_batch_size(build_step, ...)`` — largest batch whose compiled peak
  fits the budget, found by geometric probe + bisection WITHOUT executing
  (AOT lowering only; the reference's equivalent was trial-and-OOM)
"""

import os
from typing import Callable, Dict, Optional

import jax

from paddle_tpu.utils.logger import get_logger

log = get_logger("memory")


def device_memory_stats(device=None) -> Dict[str, int]:
    """bytes_limit/bytes_in_use etc. for a device (empty dict when the
    backend does not expose memory stats, e.g. CPU)."""
    device = device or jax.devices()[0]
    stats = getattr(device, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}


def host_memory_stats() -> Dict[str, int]:
    """Host-process memory: {rss_bytes, peak_rss_bytes} (best-effort;
    empty dict on platforms without /proc or resource). Feeds the
    trainer's host-memory gauge next to the device HBM gauge."""
    out: Dict[str, int] = {}
    try:
        import resource
        import sys
        ru = resource.getrusage(resource.RUSAGE_SELF)
        # linux reports KiB, macOS bytes
        scale = 1 if sys.platform == "darwin" else 1024
        out["peak_rss_bytes"] = int(ru.ru_maxrss) * scale
    except (ImportError, ValueError):
        pass
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        out["rss_bytes"] = rss_pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    return out


def step_memory(fn: Callable, *args, static_argnums=()) -> Dict[str, int]:
    """Compile ``fn`` ahead-of-time and report its memory footprint:
    {peak, arguments, outputs, temps} in bytes. Nothing executes."""
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(
        *args).compile()
    ma = compiled.memory_analysis()
    return {
        "peak": int(ma.peak_memory_in_bytes),
        "arguments": int(ma.argument_size_in_bytes),
        "outputs": int(ma.output_size_in_bytes),
        "temps": int(ma.temp_size_in_bytes),
        "aliased": int(ma.alias_size_in_bytes),
    }


def max_batch_size(build_step: Callable[[int], tuple], *,
                   budget_bytes: Optional[int] = None,
                   headroom: float = 0.92, start: int = 8,
                   limit: int = 4096) -> int:
    """Largest power-of-two-probed batch size whose compiled step fits.

    ``build_step(batch) -> (fn, example_args)`` builds the step for a batch
    size (shapes only — jax.eval_shape-compatible abstract args are fine).
    ``budget_bytes`` defaults to the device's bytes_limit * headroom (falls
    back to 16 GiB when the backend hides its stats). Probes geometrically
    then bisects; compile-only, no step executes (the reference's
    BuddyAllocator learned this by OOM-ing at runtime)."""
    if budget_bytes is None:
        stats = device_memory_stats()
        cap = stats.get("bytes_limit") or (16 << 30)
        budget_bytes = int(cap * headroom)

    _cache: Dict[int, bool] = {}

    def fits(b):
        if b in _cache:
            return _cache[b]
        try:
            fn, args = build_step(b)
            peak = step_memory(fn, *args)["peak"]
            log.info("batch %d: peak %.2f GiB (budget %.2f GiB)", b,
                     peak / 2**30, budget_bytes / 2**30)
            ok = peak <= budget_bytes
        except Exception as e:  # noqa: BLE001 — compile failure = no fit
            log.info("batch %d failed to compile: %s", b, e)
            ok = False
        _cache[b] = ok
        return ok

    start = min(start, limit)
    if not fits(start):
        return 0
    lo = start
    while lo * 2 <= limit and fits(lo * 2):
        lo *= 2
    hi = min(lo * 2, limit)
    # bisect (lo fits, hi doesn't — unless hi==limit and fits)
    if hi == limit and hi != lo and fits(hi):
        return hi
    while hi - lo > max(1, lo // 8):      # ~12% resolution is plenty
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    return lo

"""XLA cost accounting: FLOPs/bytes per compiled step, and MFU.

``jax.stages.Lowered.cost_analysis()`` reports the HLO cost model's
FLOP and byte counts for a lowered (traced, pre-XLA-optimization)
computation — the *model* FLOPs of the step, before rematerialization
inflates them. Pulling it costs one extra trace of the function (no
XLA compile), so the trainer does it lazily, once per step signature,
and only when an observability consumer exists.

MFU (model FLOPs utilisation) = flops_per_step / (step_seconds ×
peak_flops), against the declared per-chip peak table in
``core/place.py`` (override: ``PADDLE_TPU_PEAK_TFLOPS``). This is the
number the perf program steers by — "15.9% MFU" says exactly how far
from "as fast as the hardware allows" a run is, where images/sec says
nothing across models.

jax-free at import time (the CLI and JAX-free launchers import
``observe``); every jax touch is inside a function and failure-tolerant
— cost accounting must never take down a training loop.
"""

from typing import Optional


def _abstract(args):
    """Concrete args → ShapeDtypeStruct pytree (lower() traces shapes,
    it never needs the buffers — donated args stay valid)."""
    import jax

    def to_sds(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        return leaf

    return jax.tree_util.tree_map(to_sds, args)


def normalize_cost(analysis) -> Optional[dict]:
    """cost_analysis() output (dict here, list-of-dicts on some
    versions) → {"flops", "bytes_accessed"} floats, or None."""
    if analysis is None:
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
        if analysis is None:
            return None
    flops = analysis.get("flops")
    nbytes = analysis.get("bytes accessed",
                          analysis.get("bytes_accessed"))
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0),
            "bytes_accessed": float(nbytes or 0.0)}


def lowered_cost(fn, *args, platforms=None) -> Optional[dict]:
    """FLOPs/bytes of ``fn(*args)`` from the lowered HLO cost model.

    ``fn`` is a jitted function; ``args`` may be concrete arrays or
    ShapeDtypeStructs (concrete args are abstracted first — nothing
    executes). ``platforms`` lowers for those platforms instead of the
    default backend (an export for a chip this host lacks). Returns
    ``{"flops", "bytes_accessed"}`` or None when the cost model is
    unavailable. A program that does not trace or lower raises: that
    is the caller's program failing (a kernel refused at trace time,
    say), not missing accounting.
    """
    traced = fn.trace(*_abstract(args))
    lowered = traced.lower(lowering_platforms=tuple(platforms)) \
        if platforms else traced.lower()
    try:
        return normalize_cost(lowered.cost_analysis())
    except Exception:  # noqa: BLE001 — accounting is best-effort
        return None


def compiled_cost(compiled) -> Optional[dict]:
    """Same normalization for a ``jax.stages.Compiled`` (post-XLA
    numbers — includes rematerialization; use for AOT artifacts where
    the compiled object already exists)."""
    try:
        return normalize_cost(compiled.cost_analysis())
    except Exception:  # noqa: BLE001
        return None


def device_peak_flops() -> Optional[float]:
    """Declared peak FLOP/s of the default device (core.place table /
    PADDLE_TPU_PEAK_TFLOPS override); None when unknown."""
    try:
        from paddle_tpu.core import place
        return place.peak_flops()
    except Exception:  # noqa: BLE001 — no backend / no table entry
        return None


def mfu(flops_per_step: Optional[float], step_seconds: float,
        peak_flops: Optional[float] = None) -> Optional[float]:
    """Model-FLOPs utilisation of one step; None when inputs unknown."""
    if peak_flops is None:
        peak_flops = device_peak_flops()
    if not flops_per_step or not peak_flops or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak_flops)

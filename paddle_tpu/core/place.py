"""Device & mesh abstraction.

Reference: paddle/platform/place.h:24 (CPUPlace/GPUPlace variant) and
device_context.h:38 (per-device contexts holding cublas/cudnn handles).

TPU-native: JAX owns streams/handles; the useful abstraction is *which devices*
and *what mesh shape*. A ``Place`` is a jax.Device; a mesh is
``jax.sharding.Mesh`` over the local (or global) device set. Axis naming
follows the scaling-book convention: ``data`` (DP), ``model`` (TP),
``seq`` (SP/CP), ``expert`` (EP), ``stage`` (PP).
"""

import functools
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical mesh-axis names used across the framework.
AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_STAGE = "stage"

# Declared per-chip peak dense-matmul FLOP/s (bf16 with fp32 accumulation
# — the MXU number every published TPU spec quotes), keyed by substrings
# of ``device.device_kind``. Matched longest-pattern-first so "v5 lite"
# wins over "v5". The MFU accounting in ``observe.costs`` divides by
# this; ``PADDLE_TPU_PEAK_TFLOPS`` overrides (also how a future chip gets
# a number before the table learns it). The "cpu" entry is a NOMINAL
# placeholder (0.1 TFLOP/s) so the MFU plumbing stays exercised in CPU
# tests — absolute CPU MFU values are meaningless and documented as such.
PEAK_FLOPS_TABLE = (
    ("v6 lite", 918e12),      # Trillium / v6e
    ("v6e", 918e12),
    ("v5 lite", 197e12),      # v5e (device_kind: "TPU v5 lite" / "v5e")
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4 lite", 137e12),      # v4i
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("cpu", 0.1e12),          # nominal — see note above
)


def peak_flops(device=None, *, required: bool = False) -> Optional[float]:
    """Declared peak FLOP/s of ``device`` (default: the default device).

    Resolution order: ``PADDLE_TPU_PEAK_TFLOPS`` (in TFLOP/s) →
    longest-matching ``PEAK_FLOPS_TABLE`` entry against the device kind
    → None (unknown hardware; MFU reporting then stays silent rather
    than inventing a denominator).

    ``required=True`` is the benchmark path, where the result divides a
    printed device metric: only the table counts, the device must be a
    TPU (the nominal "cpu" entry never reaches a printed number) and an
    unknown ``device_kind`` raises instead of returning None."""
    device = device or default_device()
    env = os.environ.get("PADDLE_TPU_PEAK_TFLOPS")
    if env and not required:
        try:
            return float(env) * 1e12
        except ValueError:
            pass
    kind = (getattr(device, "device_kind", "") or device.platform).lower()
    best = None
    for pat, flops in PEAK_FLOPS_TABLE:
        if pat in kind and (best is None or len(pat) > len(best[0])):
            best = (pat, flops)
    if required and (device.platform != "tpu" or best is None):
        raise ValueError(
            f"no declared peak for device {device.platform!r} kind "
            f"{kind!r}: a benchmark needs a TPU listed in "
            f"PEAK_FLOPS_TABLE (source: published per-chip bf16 peaks)")
    return best[1] if best else None


def local_devices(platform: Optional[str] = None):
    return jax.devices(platform) if platform else jax.devices()


def default_device():
    return local_devices()[0]


def is_tpu() -> bool:
    return default_device().platform == "tpu"


@functools.lru_cache(maxsize=None)
def _cached_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], ndev: int) -> Mesh:
    devices = np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Build a mesh over local devices; validates the device count."""
    n = int(np.prod(shape))
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, have {avail}")
    return _cached_mesh(tuple(shape), tuple(axes), avail)


def default_mesh(data_parallel: Optional[int] = None) -> Mesh:
    """1-D data-parallel mesh over all local devices (the
    MultiGradientMachine replacement's default shape,
    reference: gserver/gradientmachines/MultiGradientMachine.h:44)."""
    n = data_parallel or len(jax.devices())
    return make_mesh((n,), (AXIS_DATA,))


def device_count() -> int:
    return len(jax.devices())

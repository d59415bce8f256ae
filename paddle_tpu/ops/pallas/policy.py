"""The ``PADDLE_TPU_PALLAS`` dispatch policy, shared by every kernel in
this package (kernels import from here rather than from the package
``__init__`` so the re-export there cannot go circular). See the package
docstring for the knob's semantics.

Besides the mode resolution this module owns the two facts every
dispatch site shares: which chip a compiled kernel is being built for
(:func:`target_device_kind`, the VMEM table keyed by it) and which path
a site placed (:func:`note_path` — the record the engines report)."""

import contextlib
import contextvars
import os
from typing import Dict, Optional

PALLAS_MODES = ("auto", "on", "off", "interpret")


def pallas_mode(explicit=None, platform: Optional[str] = None) -> str:
    """Resolve the package-wide Pallas dispatch policy to one of
    ``"on" | "off" | "interpret"``.

    ``explicit`` is the call-site override (``None`` defers to the
    ``PADDLE_TPU_PALLAS`` env var, which defaults to ``auto``). ``auto``
    resolves to ``on`` exactly when the program's target platform is
    TPU: ``platform`` when the caller compiles for a named target (an
    AOT export), the default jax backend otherwise.

    ``on`` means the compiled kernels ARE placed: a geometry the
    compiler refuses, a working set past the chip's VMEM or a backend
    that cannot compile Mosaic raises — nothing degrades to the XLA
    path. Only ``off`` selects the XLA path."""
    mode = explicit if explicit is not None \
        else os.environ.get("PADDLE_TPU_PALLAS", "auto")
    mode = str(mode).lower()
    if mode not in PALLAS_MODES:
        raise ValueError(
            f"PADDLE_TPU_PALLAS={mode!r}: expected one of "
            f"{PALLAS_MODES} (explicit arg > env > auto)")
    if mode == "auto":
        if platform is None:
            import jax
            platform = jax.default_backend()
        mode = "on" if platform == "tpu" else "off"
    return mode


# ---------------------------------------------------------------------------
# which path a dispatch site placed
# ---------------------------------------------------------------------------

PATH_PALLAS = "pallas"                      # compiled Mosaic kernel
PATH_INTERPRET = "pallas_interpret"         # the kernel, interpreted
PATH_XLA = "xla"


def kernel_path(mode: str) -> str:
    """The path a resolved mode places at every kernel site."""
    return {"on": PATH_PALLAS, "interpret": PATH_INTERPRET,
            "off": PATH_XLA}[mode]


_PATH_RECORD = contextvars.ContextVar("pallas_path_record", default=None)


@contextlib.contextmanager
def record_paths(into: Dict[str, str]):
    """Collect ``{site: path}`` for every kernel site traced inside the
    block. Tracing is when placement happens, so a step function that
    wraps its body in this fills ``into`` exactly once per compiled
    program — the record the engines show in /healthz and the artifact
    stamps beside the modules."""
    token = _PATH_RECORD.set(into)
    try:
        yield into
    finally:
        _PATH_RECORD.reset(token)


def note_path(site: str, path: str):
    """Called by a dispatch site (at trace time) with the path it is
    about to place; a no-op outside :func:`record_paths`."""
    rec = _PATH_RECORD.get()
    if rec is not None:
        rec[site] = path


# ---------------------------------------------------------------------------
# the chip a compiled kernel targets, and its VMEM
# ---------------------------------------------------------------------------

MIB = 1 << 20

# VMEM per TensorCore, keyed by ``device.device_kind``. Source: jax
# 0.9.0 ``jax/_src/pallas/mosaic/tpu_info.py`` (TpuInfo.
# vmem_capacity_bytes); the v5e figure is also the bound libtpu 0.0.34
# prints when a kernel overflows it ("Used 135.00M of 128.00M").
VMEM_CAPACITY_BYTES = {
    "TPU v4": 16 * MIB,
    "TPU v5 lite": 128 * MIB,
    "TPU v5e": 128 * MIB,
    "TPU v5": 64 * MIB,
    "TPU v5p": 64 * MIB,
    "TPU v6 lite": 128 * MIB,
    "TPU v6e": 128 * MIB,
}
# what Mosaic grants a kernel that passes no ``vmem_limit_bytes`` (the
# XLA:TPU scoped-VMEM default); kernels estimated above it ask for more
SCOPED_VMEM_DEFAULT_BYTES = 16 * MIB
# share of the capacity a kernel may plan for: the rest is the
# compiler's (spills, semaphores, its own double buffers)
VMEM_PLANNING_SHARE = 0.85

_TARGET_KIND = contextvars.ContextVar("pallas_target_kind", default=None)


@contextlib.contextmanager
def compile_target(device_kind: str):
    """Name the chip compiled kernels are built for when it is not the
    attached one: a deviceless AOT compile or a cross-platform export
    (``with compile_target("TPU v5 lite"): ...``)."""
    token = _TARGET_KIND.set(device_kind)
    try:
        yield
    finally:
        _TARGET_KIND.reset(token)


def target_device_kind() -> str:
    kind = _TARGET_KIND.get()
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    return kind


def vmem_capacity_bytes() -> int:
    """VMEM of the target chip; an unknown ``device_kind`` is an error,
    not a default (a wrong budget either refuses kernels that fit or
    lets Mosaic die on ones that do not)."""
    kind = target_device_kind()
    try:
        return VMEM_CAPACITY_BYTES[kind]
    except KeyError:
        raise ValueError(
            f"no VMEM figure for device kind {kind!r}: compiled Pallas "
            f"kernels need a TPU in VMEM_CAPACITY_BYTES "
            f"({sorted(VMEM_CAPACITY_BYTES)}). Off-TPU use "
            f"PADDLE_TPU_PALLAS=interpret (the kernels, interpreted) "
            f"or =off (the XLA path); for a deviceless TPU compile "
            f"wrap it in policy.compile_target(kind).") from None


def vmem_budget_bytes() -> int:
    """The working set a kernel may plan on the target chip."""
    return int(vmem_capacity_bytes() * VMEM_PLANNING_SHARE)


def vmem_limit_bytes(need: int, what: str) -> int:
    """The ``vmem_limit_bytes`` a compiled kernel passes for an
    estimated working set of ``need`` bytes: never below the scoped
    default (so an optimistic estimate cannot make a fitting kernel
    fail), twice the estimate above it, and an error once the estimate
    passes the chip's planning budget."""
    budget = vmem_budget_bytes()
    if need > budget:
        raise ValueError(
            f"{what}: estimated VMEM working set {need / MIB:.1f} MiB "
            f"exceeds the {budget / MIB:.0f} MiB planning budget of "
            f"{target_device_kind()!r} — shrink the geometry or run "
            f"PADDLE_TPU_PALLAS=off")
    return min(budget, max(SCOPED_VMEM_DEFAULT_BYTES, 2 * int(need)))


def compiled_kernel_params(interpret, need: int, what: str) -> dict:
    """``pallas_call`` kwargs every compiled kernel passes: the
    explicit ``vmem_limit_bytes`` for its estimated working set on the
    target chip. Empty for the interpreter, which has no VMEM."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit_bytes(need, what))}

"""Pallas TPU kernels — hand-scheduled fusions where XLA's automatic fusion
is insufficient (reference slot: the hand-written CUDA in
paddle/cuda/src/hl_cuda_*.cu; see /opt/skills/guides/pallas_guide.md).

Each kernel ships with a jnp/XLA reference implementation, which is what
``auto`` selects off-TPU, so the package runs everywhere; tests exercise
the kernels in Pallas interpret mode on CPU.

Dispatch policy — ``PADDLE_TPU_PALLAS``
---------------------------------------
One documented knob decides whether the Pallas kernels run, shared by
every kernel in this package (``attention.flash_attention``,
``decode.flash_decode_attention`` / ``decode.fused_sample`` and whatever
lands next):

- ``auto`` (default) — ``on`` on TPU, ``off`` elsewhere;
- ``on``        — place the compiled kernels, or raise (a geometry the
  compiler refuses, a working set past the chip's VMEM, a backend that
  cannot compile Mosaic): nothing degrades to the XLA path;
- ``off``       — the pure-XLA path (the only way to get it);
- ``interpret`` — run the kernels through the Pallas interpreter (the
  CPU correctness path tier-1 exercises).

Precedence: explicit call-site argument > ``PADDLE_TPU_PALLAS`` env >
``auto`` (tested in tests/test_pallas_decode.py::TestPallasPolicy).
"""

from paddle_tpu.ops.pallas.policy import (  # noqa: F401
    PALLAS_MODES, pallas_mode)

from paddle_tpu.ops.pallas.attention import flash_attention  # noqa: F401
from paddle_tpu.ops.pallas.decode import (  # noqa: F401,E402
    flash_decode_attention, fused_sample)
from paddle_tpu.ops.pallas.prefill import (  # noqa: F401,E402
    flash_chunk_prefill, paged_span_write)

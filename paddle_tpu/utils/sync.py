"""Device-synchronisation helper for timing and profiling loops.

JAX dispatch is asynchronous: a wall-clock reading taken before the
device finished measures the enqueue. Every measurement loop (bench.py,
cli.measure_time, benchmarks/*) closes its timed window through this
helper so the barrier lives in one place.
"""

import jax


def host_sync(tree, *scalars) -> float:
    """Block until ``tree`` and the extra device ``scalars`` are
    computed (``jax.block_until_ready``), then return the float of the
    last scalar — the value the caller already has on the device (0.0
    without one)."""
    jax.block_until_ready((tree, scalars))
    return float(scalars[-1]) if scalars else 0.0

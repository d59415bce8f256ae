"""Deviceless compilation of the serving programs with the real TPU
compiler.

Lowering a Pallas kernel (``jax.export(..., platforms=["tpu"])``) only
proves the kernel traces: Mosaic and XLA:TPU run at COMPILE time, and
that is where block-16 score stores, an all-layer span block and a
``uint32 -> float32`` cast were refused. libtpu can compile for a chip
that is not attached — ``jax.experimental.topologies`` describes one and
``jit(fn).lower(args placed on it).compile()`` runs the whole compiler —
so tier-1 (tests/test_aot_tpu_compile.py) and ``serving_bench
--tpu-check`` compile the engine's programs for a v5e from any CPU host.
Nothing here catches a compiler error: a refusal propagates with the
compiler's message.
"""

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import policy

TOPOLOGY = "v5e:2x2"


def topology_device(topology: str = TOPOLOGY):
    """One device of a described (not attached) TPU topology. Raises
    whatever ``get_topology_desc`` raises when this libtpu cannot
    describe it — callers that may skip (tests) catch that."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name=topology).devices[0]


def compile_for(device, fn, *args, donate_argnums=()):
    """``fn(*args)`` (abstract args) compiled for ``device`` by the
    real compiler; kernels resolve their VMEM budget against the
    device's kind, not the host's. ``donate_argnums`` as the caller
    of the real program donates (the paged engine: 1, the pool)."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), args)
    with policy.compile_target(device.device_kind):
        return jax.jit(fn, donate_argnums=donate_argnums).lower(
            *placed).compile()


def engine_programs(cfg, *, batch: int, cache_len: int,
                    block_size: int, chunk_tokens: int,
                    kv_dtype: Optional[str] = None):
    """The paged engine's decode program and its chunk-prefill programs
    at the ends of the chunk grid (cold chunk, deepest context, a
    sub-block tail bucket), every kernel placed
    (``PADDLE_TPU_PALLAS=on``): ``({name: (fn, abstract args)},
    kernel_paths)`` — ``kernel_paths[name]`` fills in when ``name`` is
    traced."""
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import sampling
    bs, B = int(block_size), int(batch)
    P = cache_len // bs
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(
        lambda: transformer.init_block_pool(cfg, B * P, bs,
                                            kv_dtype=kv_dtype))
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, bs,
                                                    pallas="on")
    S = jax.ShapeDtypeStruct
    i32, f32 = S((), jnp.int32), S((), jnp.float32)

    def vec(dtype):
        return S((B,), dtype)

    programs = {"decode": (decode_fn, (
        params, pool, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        S((B, P), jnp.int32), vec(jnp.float32), vec(jnp.int32), i32))}
    for ctx, bucket in ((0, chunk_tokens),
                        (cache_len - chunk_tokens, chunk_tokens),
                        (chunk_tokens, max(8, bs // 2))):
        npages = ctx // bs + -(-bucket // bs)
        programs[f"prefill_{bucket}_{npages}"] = (prefill_fn, (
            params, pool, S((1, bucket), jnp.int32), i32,
            S((npages,), jnp.int32), f32, i32, i32))
    return programs, decode_fn.kernel_paths


def compile_engine_programs(cfg, *, device=None, **geometry
                            ) -> Dict[str, dict]:
    """Compile every :func:`engine_programs` program for ``device``
    (default: a described v5e). Returns, per program, what was placed,
    the compile seconds and the compiler's memory analysis."""
    device = device or topology_device()
    programs, paths = engine_programs(cfg, **geometry)
    out = {}
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        # the pool donated, as the engine calls them
        mem = compile_for(device, fn, *args,
                          donate_argnums=(1,)).memory_analysis()
        out[name] = {
            "kernel_paths": paths[name],
            "compile_s": round(time.perf_counter() - t0, 2),
            "temp_mib": round(mem.temp_size_in_bytes / 2**20, 1),
            "argument_mib": round(mem.argument_size_in_bytes / 2**20, 1)}
    return out

"""The serving kernels and the engine's programs, compiled for a TPU
v5e by the real compiler with no chip attached (``ops/pallas/aot.py``).

``jax.export`` lowering used to stand in for this and said "ok" to
kernels Mosaic then refused (block-16 score stores, the all-layer
span-write block, the sampler's ``uint32 -> float32`` cast). These
tests run the whole compiler at the engines' DEFAULT block size and the
width ``chip_smoke.py`` serves, so a kernel change the chip would
refuse fails tier-1 — not the first deploy. int4 pools compile several
times slower and ride the ``slow`` marker.
"""

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer
from paddle_tpu.ops.pallas import aot
from paddle_tpu.ops.pallas import decode as fd
from paddle_tpu.ops.pallas import prefill as fp
from paddle_tpu.serving.blocks import DEFAULT_BLOCK_SIZE as BS

S = jax.ShapeDtypeStruct
# chip_smoke.py's LM: the benchmarks/transformer_bench.py defaults
CFG = transformer.TransformerConfig(
    vocab=32000, d_model=512, n_heads=8, n_layers=6, d_ff=2048,
    max_len=2048, dtype=jnp.bfloat16)
B, CACHE, CHUNK = 8, 2048, 256
L, HKV, G, DH = CFG.n_layers, CFG.kv_heads, 1, CFG.head_dim
M = B * CACHE


@pytest.fixture(scope="module")
def device():
    try:
        return aot.topology_device()
    except Exception as e:  # noqa: BLE001 — no topology = cannot test
        pytest.skip(f"libtpu cannot describe a {aot.TOPOLOGY} topology "
                    f"on this host: {type(e).__name__}: {e}")


def _pool(kv, layers=None):
    """Abstract (k/v, scale) pool arrays at stored width."""
    d_st = DH // 2 if kv == "int4" else DH
    lead = () if layers is None else (layers,)
    vals = S(lead + (HKV, M, d_st),
             jnp.bfloat16 if kv == "none" else jnp.int8)
    return vals, S(lead + (HKV, M), jnp.float32)


def _decode(kv):
    vals, sc = _pool(kv)
    args = [S((B, HKV, G, DH), jnp.bfloat16), vals, vals,
            S((B, CACHE // BS), jnp.int32), S((B,), jnp.int32)]
    if kv == "none":
        return lambda q, k, v, pg, ps: fd.flash_decode_attention(
            q, k, v, pg, ps, block_size=BS), args
    return lambda q, k, v, pg, ps, ks, vs: fd.flash_decode_attention(
        q, k, v, pg, ps, block_size=BS, k_scale=ks, v_scale=vs,
        kv_dtype=kv), args + [sc, sc]


def _prefill(kv, ctx=CACHE - CHUNK):
    vals, sc = _pool(kv)
    ck = S((CHUNK, HKV, DH), jnp.bfloat16)
    args = [S((CHUNK, HKV, G, DH), jnp.bfloat16), ck, ck, vals, vals,
            S((ctx // BS,), jnp.int32)]
    if kv == "none":
        return lambda q, kc, vc, k, v, pg: fp.flash_chunk_prefill(
            q, kc, vc, k, v, pg, block_size=BS), args
    return lambda q, kc, vc, k, v, pg, ks, vs: fp.flash_chunk_prefill(
        q, kc, vc, k, v, pg, block_size=BS, k_scale=ks, v_scale=vs,
        kv_dtype=kv), args + [sc, sc]


def _span_write(kv):
    vals, sc = _pool(kv, layers=L)
    pc = CHUNK // BS
    span = S(vals.shape[:2] + (CHUNK,) + vals.shape[3:], vals.dtype)
    args = [vals, vals, span, span, S((pc,), jnp.int32),
            S((CHUNK,), jnp.bool_)]
    if kv != "none":
        args += [sc, sc] + [S((L, HKV, CHUNK), jnp.float32)] * 2

    def fn(pk, pv, sk, sv, pages, valid, *scales):
        pool, spans = {"k": pk, "v": pv}, {"k": sk, "v": sv}
        if scales:
            pool.update(k_scale=scales[0], v_scale=scales[1])
            spans.update(k_scale=scales[2], v_scale=scales[3])
        return fp.paged_span_write(pool, spans, pages, valid,
                                   block_size=BS)

    return fn, args


def _sample(kv):
    del kv
    return fd.fused_sample, [S((B, CFG.vocab), jnp.float32),
                             S((), jnp.int32), S((B,), jnp.float32),
                             S((B,), jnp.int32)]


KERNELS = {"decode": _decode, "prefill": _prefill,
           "prefill_cold": lambda kv: _prefill(kv, ctx=0),
           "span_write": _span_write, "sample": _sample}
CASES = ([(k, "none") for k in KERNELS]
         + [(k, "int8") for k in ("decode", "prefill", "span_write")]
         + [pytest.param(k, "int4", marks=pytest.mark.slow)
            for k in ("decode", "prefill", "span_write")])


@pytest.mark.parametrize("kernel,kv", CASES)
def test_serving_kernel_compiles_for_v5e(device, kernel, kv):
    fn, args = KERNELS[kernel](kv)
    aot.compile_for(device, fn, *args)


ENGINE = aot.engine_programs(CFG, batch=B, cache_len=CACHE,
                             block_size=BS, chunk_tokens=CHUNK)


@pytest.mark.parametrize("program", sorted(ENGINE[0]))
def test_engine_program_compiles_with_every_kernel_placed(device,
                                                          program):
    """The program the engine would dispatch — kernels inside the layer
    scan, the pool riding as scan xs, the sampler fused at the tail —
    not just each kernel alone."""
    programs, paths = ENGINE
    fn, args = programs[program]
    mem = aot.compile_for(device, fn, *args).memory_analysis()
    assert set(paths[program].values()) == {"pallas"}, paths[program]
    # one v5e chip: 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12e9


def test_training_flash_attention_compiles_fwd_and_bwd(device):
    from paddle_tpu.ops.pallas import flash_attention
    q = S((2, 2048, 8, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))

    aot.compile_for(device, jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_block_sizes_the_compiler_refuses_raise_at_trace_time(device):
    """Block 16 (the old engine default) dies in Mosaic with "cannot
    statically prove that index in dimension 1 is a multiple of 128";
    the kernels name the problem before the compiler has to."""
    vals = S((HKV, M, DH), jnp.bfloat16)
    with pytest.raises(ValueError, match="multiple of 128"):
        aot.compile_for(
            device, lambda q, k, v, pg, ps: fd.flash_decode_attention(
                q, k, v, pg, ps, block_size=16),
            S((B, HKV, G, DH), jnp.bfloat16), vals, vals,
            S((B, CACHE // 16), jnp.int32), S((B,), jnp.int32))

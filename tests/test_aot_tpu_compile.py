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
    loop, the pool riding it as the carry (decode) or read where it
    lies (prefill), the sampler fused at the tail — not just each
    kernel alone."""
    programs, paths = ENGINE
    fn, args = programs[program]
    mem = aot.compile_for(device, fn, *args).memory_analysis()
    assert set(paths[program].values()) == {"pallas"}, paths[program]
    # one v5e chip: 16 GB of HBM
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12e9


def _pool_sized_results(text, pool):
    """Per op kind, the instructions of the optimized program whose
    result has the shape of a whole value table of the pool (k, v), of
    its flat-row view or of one layer's slab of it: a round trip
    through HBM of 1/L of the table or more (the scale tables of a
    quantized pool are 1/Dh of that and not looked for). In-place
    updates show as ``fusion`` (the scatter) or a bare
    ``dynamic-update-slice`` over the aliased buffer and are not what
    this looks for: a ``copy`` or a ``dynamic-slice`` (bare, or leading
    a fusion's name) is."""
    import math
    import re
    dt = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}
    shapes = set()
    for t in (pool["k"], pool["v"]):
        tag = dt[jnp.dtype(t.dtype).name]
        for dims in (t.shape, (math.prod(t.shape[:3]),) + t.shape[3:],
                     t.shape[1:], (1,) + t.shape[1:]):
            shapes.add(f"{tag}[{','.join(map(str, dims))}]")
    found = {}
    fused = False           # inside a fusion's own computation: what
    for line in text.splitlines():   # crosses HBM is the fusion's result
        if line.endswith("{") and " = " not in line:
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                     r"([\w\-]+)\(", line)
        if fused or not m or m.group(2) not in shapes:
            continue
        name, _, op = m.groups()
        for kind in ("copy", "dynamic-slice", "dynamic-update-slice"):
            if op == kind or (op == "fusion" and kind in name
                              and not (kind == "dynamic-slice"
                                       and "update" in name)):
                found.setdefault(kind, []).append(name)
    return found


def _pool_bytes(pool):
    return sum(t.size * jnp.dtype(t.dtype).itemsize
               for t in pool.values())


# the same model with heads of one full lane width, as the benchmark's
# 1.3B has: the device keeps a [.., M, 128] table row-major, so its
# flat-row view is a bitcast. At the file's 64-wide heads the device
# lays the pool out position-minor ({2,3,1,0}) and the view costs one
# re-layout in and one out (below)
CFG_DH128 = transformer.TransformerConfig(
    vocab=32000, d_model=1024, n_heads=8, n_layers=6, d_ff=2048,
    max_len=2048, dtype=jnp.bfloat16)


def _paged_programs(cfg, pallas, kv=None):
    from paddle_tpu.serving import sampling
    programs, _ = aot.engine_programs(
        cfg, batch=B, cache_len=CACHE, block_size=BS,
        chunk_tokens=CHUNK, kv_dtype=kv)
    prefill_fn, decode_fn = sampling.paged_step_fns(cfg, BS,
                                                    pallas=pallas)
    return programs, prefill_fn, decode_fn


@pytest.mark.parametrize("pallas", ["off", "on"])
@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16", "int8"])
def test_decode_program_updates_the_donated_pool_in_place(device, kv,
                                                          pallas):
    """The guard against the pool going back onto the layer scan's
    ``xs``/``ys``: called with the pool donated, the compiled decode
    program aliases the whole pool to its output and holds no op that
    copies, slices out or update-slices a pool-sized or slab-sized
    array. With the kernels placed, the kernel's signature takes one
    layer's tables, so exactly its two operands (k and v) are sliced
    out of the carry, once each, and nothing else is."""
    programs, _, decode_fn = _paged_programs(CFG_DH128, pallas, kv)
    pool = programs["decode"][1][1]
    compiled = aot.compile_for(
        device, decode_fn, *programs["decode"][1], donate_argnums=(1,))
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= _pool_bytes(pool)
    found = _pool_sized_results(compiled.as_text(), pool)
    sliced = found.pop("dynamic-slice", [])
    assert len(sliced) == (2 if pallas == "on" else 0), sliced
    assert not found, found


def test_decode_program_at_64_wide_heads_relays_the_pool_out_once(device):
    """What the file's own geometry shows: 64 is half a lane row, the
    device stores the pool position-minor, and the decode program pays
    one re-layout of each table into rows and one back — around the
    layer loop, not in it. Nothing slab-sized moves, and the pool is
    still aliased."""
    programs, _, decode_fn = _paged_programs(CFG, "off")
    pool = programs["decode"][1][1]
    compiled = aot.compile_for(
        device, decode_fn, *programs["decode"][1], donate_argnums=(1,))
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= _pool_bytes(pool)
    found = _pool_sized_results(compiled.as_text(), pool)
    assert len(found.pop("copy")) == 4      # k and v, in and out
    assert not found, found


def test_prefill_program_updates_the_donated_pool_in_place(device):
    """The same aliasing for a paged prefill chunk with context: its
    span writes land in the donated pool, and the context is gathered
    off the pool as it lies — no copy of the pool, donated or not."""
    programs, prefill_fn, _ = _paged_programs(CFG_DH128, "off")
    args = programs[f"prefill_{CHUNK}_{CACHE // BS}"][1]
    compiled = aot.compile_for(device, prefill_fn, *args,
                               donate_argnums=(1,))
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= _pool_bytes(args[1])
    assert "copy" not in _pool_sized_results(compiled.as_text(), args[1])


def test_xla_sampler_selects_and_does_not_sort(device):
    """The guard against the full-vocabulary sort coming back into the
    XLA sampling tail (11.6 of a 41.6 ms decode step at this shape, the
    GLM-4.7-Flash cell's, and ~26 s of compile in EVERY step program):
    the k-th largest logit comes out of one 32-trip loop of
    compare-and-count passes whose temporaries are a few vectors."""
    from paddle_tpu.serving import sampling
    rows, vocab = 48, 154880

    def tail(logits, seed, temperature, top_k):
        return sampling.sample_tokens(logits, jax.random.PRNGKey(seed),
                                      temperature, top_k)

    compiled = aot.compile_for(
        device, tail, S((rows, vocab), jnp.float32), S((), jnp.int32),
        S((rows,), jnp.float32), S((rows,), jnp.int32))
    text = compiled.as_text()
    assert " sort(" not in text
    assert text.count(" while(") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2e6


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

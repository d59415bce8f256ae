"""LM serving artifact: AOT prefill+decode round-trip must reproduce the
in-code generate() exactly (greedy) with zero model code at load time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.io import lm_serving
from paddle_tpu.models import transformer

CFG = transformer.TransformerConfig(
    vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2, d_ff=32,
    max_len=32, dtype=jnp.float32, use_rope=True)


def test_artifact_roundtrip_matches_generate(tmp_path, rng):
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 8
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=Tp + new)
    srv = lm_serving.load_lm_artifact(path)
    got = srv.generate(prompt, max_new=new)
    want = np.asarray(transformer.generate(
        params, jnp.asarray(prompt), CFG, max_new=new))
    np.testing.assert_array_equal(got, want)


def test_artifact_shape_guards(tmp_path, rng):
    import pytest
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=1, prompt_len=4,
                                cache_len=12)
    srv = lm_serving.load_lm_artifact(path)
    with pytest.raises(ValueError, match="exported for batch"):
        srv.generate(np.zeros((2, 4), np.int32), max_new=2)
    with pytest.raises(ValueError, match="cache_len"):
        srv.generate(np.zeros((1, 4), np.int32), max_new=20)


def test_weights_int8_artifact(tmp_path, rng):
    """weights_int8: big matmul weights stored per-output-channel int8,
    dequantized inline by the exported modules — loader unchanged,
    artifact smaller, logits within per-channel-int8 tolerance."""
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 6
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    p_f = str(tmp_path / "lm_f.tar")
    p_q = str(tmp_path / "lm_q.tar")
    lm_serving.save_lm_artifact(p_f, params, CFG, batch=B, prompt_len=Tp,
                                cache_len=Tp + new)
    lm_serving.save_lm_artifact(p_q, params, CFG, batch=B, prompt_len=Tp,
                                cache_len=Tp + new, weights_int8=True)
    srv_f = lm_serving.load_lm_artifact(p_f)
    srv_q = lm_serving.load_lm_artifact(p_q)

    def param_bytes(tree):
        return sum(np.asarray(a).nbytes
                   for a in jax.tree_util.tree_leaves(tree))

    # the big weights store at 1 byte/elt (+ tiny scales); toy tar sizes
    # round to 512-byte blocks, so compare the parameter payload itself
    assert param_bytes(srv_q.params) < 0.5 * param_bytes(srv_f.params)
    assert srv_q.meta["weights_int8"] is True
    lg_f, _ = srv_f._prefill.call(srv_f.params,
                                  jnp.asarray(prompt, jnp.int32))
    lg_q, _ = srv_q._prefill.call(srv_q.params,
                                  jnp.asarray(prompt, jnp.int32))
    lf, lq = np.asarray(lg_f), np.asarray(lg_q)
    denom = np.abs(lf).max() + 1e-9
    assert np.abs(lq - lf).max() / denom < 0.05, "int8 weights drifted"
    # generation runs end-to-end off the quantized artifact
    out = srv_q.generate(prompt, max_new=new)
    assert out.shape == (B, Tp + new)


def test_quantize_lm_params_structure(rng):
    """Only the big matmul weights become {"q8","scale"} nodes; per-
    channel dequantization reconstructs within int8 resolution."""
    from paddle_tpu.ops import q8 as ops_q8
    params = transformer.init_params(jax.random.PRNGKey(1), CFG)
    qp = lm_serving.quantize_lm_params(params)
    assert ops_q8.is_quantized_weight(qp["embed"])
    assert ops_q8.is_quantized_weight(qp["blocks"]["qkv"])
    assert not ops_q8.is_quantized_weight(qp["blocks"]["ln1"])
    assert qp["blocks"]["qkv"]["q8"].dtype == jnp.int8
    w = np.asarray(params["blocks"]["qkv"])
    wq = np.asarray(ops_q8.dequantize_weight(qp["blocks"]["qkv"]))
    rel = np.abs(wq - w).max() / (np.abs(w).max() + 1e-9)
    assert rel < 0.01, rel
    # the original params were not mutated
    assert not ops_q8.is_quantized_weight(params["blocks"]["qkv"])


def test_generate_accepts_quantized_params(rng):
    """generate() detects {"q8","scale"} weights, threads them through
    the decode scan carry (hoist-proof int8 reads) and produces tokens
    close to the fp32 path."""
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    prompt = jnp.asarray(rng.randint(0, 40, (2, 6)).astype(np.int32))
    out_f = np.asarray(transformer.generate(params, prompt, CFG,
                                            max_new=8))
    qp = lm_serving.quantize_lm_params(params)
    out_q = np.asarray(transformer.generate(qp, prompt, CFG, max_new=8))
    assert out_q.shape == out_f.shape
    # toy-model near-ties flip some greedy picks; most must agree
    assert (out_f == out_q).mean() > 0.6
    # the int8 leaves reach the traced decode loop (not pre-dequantized):
    # the while-loop region of the STABLEHLO carries i8 operands. (What
    # the backend then does is its own business: the CPU pipeline deletes
    # barriers and hoists the dequant; the on-chip A/B measures TPU —
    # the LMServer path dequantizes per host call regardless.)
    shlo = jax.jit(
        lambda p, pr: transformer.generate(p, pr, CFG, max_new=8)
    ).lower(qp, prompt).as_text()
    import re
    loops = re.findall(r"stablehlo\.while.*?(?:\n  \}|\Z)", shlo, re.S)
    assert any("i8" in l for l in loops), "int8 absent from decode loop"


def test_server_metrics_prometheus_snapshot(tmp_path, rng):
    """The serving observability surface: prefill/decode call counters,
    token counter, and per-phase latency histograms, rendered as a
    Prometheus text snapshot (acceptance: lm_serving exposes
    prefill/decode latency histograms + token counters)."""
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 5
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=Tp + new)
    srv = lm_serving.load_lm_artifact(path)
    srv.generate(prompt, max_new=new)
    srv.generate(prompt, max_new=new)

    assert srv._m_prefill.value() == 2
    assert srv._m_decode.value() == 2 * (new - 1)
    assert srv._m_tokens.value() == 2 * new * B
    assert srv.metrics.get("lm_prefill_seconds").snapshot()["count"] == 2

    text = srv.metrics_text()
    assert "# TYPE lm_prefill_seconds histogram" in text
    assert "# TYPE lm_decode_seconds histogram" in text
    assert f"lm_tokens_generated_total {2 * new * B}" in text
    assert "lm_decode_seconds_bucket" in text and 'le="+Inf"' in text
    # a second server must start from zero (per-server registries)
    srv2 = lm_serving.load_lm_artifact(path)
    assert srv2._m_prefill.value() == 0

    # per-phase XLA cost accounting stamped into the artifact at export
    # time → the decode-MFU gauge moves on a server that generated
    assert srv.cost_analysis["prefill"]["flops"] > 0
    assert srv.cost_analysis["decode"]["flops"] > 0
    assert srv.metrics.get("lm_decode_mfu").value() > 0

    # /metrics + /healthz over HTTP from this server's own registry
    import json as _json
    import urllib.request
    http = srv.serve()
    try:
        scraped = urllib.request.urlopen(
            http.url + "/metrics", timeout=5).read().decode()
        assert f"lm_tokens_generated_total {2 * new * B}" in scraped
        health = _json.loads(urllib.request.urlopen(
            http.url + "/healthz", timeout=5).read())
        assert health["status"] == "ok" and health["requests"] == 2
        assert health["tokens_generated"] == 2 * new * B
    finally:
        http.close()


def test_generate_unseeded_sampling_not_deterministic(tmp_path, rng):
    """seed=None used to collapse to RandomState(0): every 'unseeded'
    sampling call replayed the same stream. Now it draws OS entropy."""
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 8
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=Tp + new)
    srv = lm_serving.load_lm_artifact(path)
    # near-uniform sampling over 40 symbols x 16 draws: a repeat of the
    # whole matrix is ~40^-16 — an effectively impossible coincidence
    a = srv.generate(prompt, max_new=new, temperature=100.0)
    b = srv.generate(prompt, max_new=new, temperature=100.0)
    assert not np.array_equal(a, b)
    # explicit seeds stay reproducible
    a = srv.generate(prompt, max_new=new, temperature=1.0, seed=7)
    b = srv.generate(prompt, max_new=new, temperature=1.0, seed=7)
    np.testing.assert_array_equal(a, b)


def test_generate_eos_early_exit(tmp_path, rng):
    """eos_id stops the lockstep decode loop once every row emitted it,
    and rows that finish first pad with eos_id."""
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 8
    # identical rows => identical greedy streams => both rows hit the
    # eos at the same (deterministic) step
    prompt = np.tile(rng.randint(0, 40, (1, Tp)), (B, 1)).astype(np.int32)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=Tp + new)
    srv = lm_serving.load_lm_artifact(path)
    full = srv.generate(prompt, max_new=new)
    gen = full[0, Tp:]
    # first position whose token value hasn't occurred before (the toy
    # model may emit one token forever: fall back to the first token)
    idx = next((i for i in range(1, new) if gen[i] not in gen[:i]), 0)
    steps_before = srv._m_decode.value()
    out = srv.generate(prompt, max_new=new, eos_id=int(gen[idx]))
    # loop exited right after the eos token: idx decode steps, not new-1
    assert out.shape == (B, Tp + idx + 1)
    np.testing.assert_array_equal(out, full[:, :Tp + idx + 1])
    assert srv._m_decode.value() - steps_before == idx
    # rows that never emit eos keep the full-length contract
    out2 = srv.generate(prompt, max_new=new, eos_id=39999)
    assert out2.shape == (B, Tp + new)


def test_engine_artifact_v3_roundtrip(tmp_path, rng):
    """Format v3 held the row-arena engine's modules, which nothing
    runs any more: an artifact from an older export is outside input,
    ``engine()`` refuses it with a one-line re-export hint (as it does
    a stale pool layout), and its lockstep pair still serves."""
    import io as _io
    import json
    import tarfile

    import pytest
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 8
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm_v4.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=16,
                                engine_buckets=(8,), engine_block_size=8)
    # what an older export left: format 3, engine_buckets, the arena's
    # two member names, no engine_paged section
    old = str(tmp_path / "lm_v3.tar")
    with tarfile.open(path) as src, tarfile.open(old, "w") as dst:
        for m in src.getmembers():
            blob, name = src.extractfile(m).read(), m.name
            if name == "meta.json":
                meta = json.loads(blob)
                assert meta["format_version"] == 4
                meta["format_version"] = 3
                del meta["engine_paged"]
                blob = json.dumps(meta).encode()
            elif name.startswith("engine_prefill_paged_"):
                name = "engine_prefill_8.bin"
            elif name == "engine_decode_paged.bin":
                name = "engine_decode.bin"
            info = tarfile.TarInfo(name)
            info.size = len(blob)
            dst.addfile(info, _io.BytesIO(blob))
    srv = lm_serving.load_lm_artifact(old)
    assert srv.meta["format_version"] == 3
    assert srv.engine_buckets == (8,)
    with pytest.raises(ValueError, match="format v3.*re-export"):
        srv.engine(seed=0)
    got = srv.generate(prompt, max_new=new)
    want = np.asarray(transformer.generate(
        params, jnp.asarray(prompt), CFG, max_new=new))
    np.testing.assert_array_equal(got, want)


def test_engine_paged_false_refused(tmp_path):
    """``engine_paged`` waits on its two callers to drop the keyword:
    it accepts only ``True``. ``False`` asked for the row-arena
    modules, which are gone, and says what to do instead."""
    import pytest
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match="PR 28.*drop the keyword"):
        lm_serving.save_lm_artifact(
            str(tmp_path / "bad.tar"), params, CFG, batch=2,
            prompt_len=6, cache_len=32, engine_buckets=(8,),
            engine_paged=False)
    assert not (tmp_path / "bad.tar").exists()


def test_engine_artifact_v4_paged_roundtrip(tmp_path, rng):
    """Format v4: paged engine modules ride the artifact; engine()
    schedules a PagedDecodeEngine (chunked prefill + prefix cache) over
    them, v4 still serves the legacy lockstep path, and a prompt beyond
    any chunk bucket is accepted."""
    import pytest
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    B, Tp, new = 2, 6, 8
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm_v4.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=B,
                                prompt_len=Tp, cache_len=32,
                                engine_buckets=(8, 16),
                                engine_block_size=8)
    from paddle_tpu.ops.pallas import policy as pallas_policy
    srv = lm_serving.load_lm_artifact(path)
    assert srv.meta["format_version"] == 4
    assert srv.meta["engine_paged"] == {
        "block_size": 8, "num_blocks": 8, "pages_per_slot": 4,
        "chunk_tokens": 16, "pallas": pallas_policy.pallas_mode(None),
        "kv_dtype": "none",
        "pool_layout": transformer.POOL_LAYOUT}
    assert srv.meta["engine_pallas"] == pallas_policy.pallas_mode(None)
    # what every exported module placed, recorded as it was traced:
    # one decode + (2 buckets x 2 context spans) chunk-prefill programs
    xla = {"attention": "xla", "sampler": "xla"}
    assert srv.meta["engine_kernel_paths"] == {
        "decode": xla,
        **{f"prefill_{b}_{pv}": dict(xla, span_write="xla")
           for b, pv in ((8, 1), (16, 2), (8, 3), (16, 4))}}
    assert srv.cost_analysis["engine_decode"]["flops"] > 0
    # legacy lockstep path unchanged on a v4 artifact
    got = srv.generate(prompt, max_new=new)
    want = np.asarray(transformer.generate(
        params, jnp.asarray(prompt), CFG, max_new=new))
    np.testing.assert_array_equal(got, want)
    # paged engine path: same tokens, chunked long prompt included
    tracker = CompileTracker()
    eng = srv.engine(seed=0, tracker=tracker)
    assert isinstance(eng, PagedDecodeEngine)
    assert eng.health()["kernel_paths"] == srv.meta["engine_kernel_paths"]
    reqs = [eng.submit(prompt[i], max_new=new) for i in range(B)]
    long_p = rng.randint(0, 40, 24).astype(np.int32)   # > max bucket 16
    reqs.append(eng.submit(long_p, max_new=4))
    eng.run_until_idle()
    want_long = np.asarray(transformer.generate(
        params, jnp.asarray(long_p[None]), CFG, max_new=4))[0]
    for r, w in zip(reqs, list(want) + [want_long]):
        np.testing.assert_array_equal(r.output, w)
    assert eng.compile_counts()["decode"] == 1
    # at most one program per (chunk bucket, context span) on the
    # exported grid: buckets {8,16} x context {0,16} tokens
    assert eng.compile_counts()["prefill"] <= 4
    # replaying the long prompt hits its cached prefix blocks
    r2 = eng.submit(long_p, max_new=4)
    eng.run_until_idle()
    assert r2.prefix_hit_tokens == 16
    np.testing.assert_array_equal(r2.output, want_long)
    # the chunk grid is baked into the artifact's module shapes —
    # engine() refuses to schedule a different one
    with pytest.raises(ValueError, match="chunk grid"):
        srv.engine(chunk_tokens=8)


def test_engine_artifact_legacy_pool_layout_hint(tmp_path, rng):
    """A v4/v5 artifact whose paged modules were exported against the
    pre-relayout slot-major pool (no ``pool_layout`` stamp, or a stale
    one) cannot be scheduled over the head-major pool this build
    constructs — the exported programs bake the pool array shapes.
    ``engine()`` must refuse with a one-line re-export hint instead of
    dying on an opaque shape mismatch at the first prefill; the
    non-engine paths (``generate``) still serve. Together with the v4
    roundtrips above this covers both directions: current-layout
    artifacts roundtrip, legacy-layout artifacts hint."""
    import io as _io
    import json
    import tarfile

    import pytest
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "lm_v4_legacy.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=2,
                                prompt_len=6, cache_len=32,
                                engine_buckets=(8, 16),
                                engine_block_size=8)
    # simulate a pre-relayout artifact: strip the pool_layout stamp
    # (absent == slot_major, the legacy default)
    legacy = str(tmp_path / "lm_v4_slotmajor.tar")
    with tarfile.open(path) as src, tarfile.open(legacy, "w") as dst:
        for m in src.getmembers():
            blob = src.extractfile(m).read()
            if m.name == "meta.json":
                meta = json.loads(blob)
                del meta["engine_paged"]["pool_layout"]
                blob = json.dumps(meta).encode()
            info = tarfile.TarInfo(m.name)
            info.size = len(blob)
            dst.addfile(info, _io.BytesIO(blob))
    srv = lm_serving.load_lm_artifact(legacy)
    with pytest.raises(ValueError, match="re-export"):
        srv.engine(seed=0)
    # the lockstep path carries no pool and keeps serving
    prompt = rng.randint(0, 40, (2, 6)).astype(np.int32)
    got = srv.generate(prompt, max_new=4)
    want = np.asarray(transformer.generate(
        params, jnp.asarray(prompt), CFG, max_new=4))
    np.testing.assert_array_equal(got, want)


def test_engine_artifact_v4_int8_roundtrip(tmp_path, rng):
    """v4 + weights_int8: the exported paged decode module consumes the
    {"q8","scale"} tree NATIVELY (in-scan dequant — 1-byte weight reads
    per token), and the engine's greedy output equals generate() over
    the dequantized tree exactly: quantization changes WHERE dequant
    happens, never the values."""
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.ops import q8 as ops_q8
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "lm_v4_q8.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=2,
                                prompt_len=6, cache_len=32,
                                engine_buckets=(8, 16),
                                engine_block_size=8,
                                weights_int8=True)
    srv = lm_serving.load_lm_artifact(path)
    assert srv.meta["format_version"] == 4
    assert srv.meta["weights_int8"] is True
    assert ops_q8.is_quantized_weight(srv.params["blocks"]["qkv"])
    live = jax.tree_util.tree_map(
        lambda n: jnp.asarray(ops_q8.dequantize_weight(n))
        if ops_q8.is_quantized_weight(n) else jnp.asarray(n),
        srv.params, is_leaf=ops_q8.is_quantized_weight)
    eng = srv.engine(seed=0, tracker=CompileTracker())
    prompts = [rng.randint(0, 40, n).astype(np.int32) for n in (5, 9)]
    reqs = [eng.submit(p, max_new=6) for p in prompts]
    eng.run_until_idle()
    for p, r in zip(prompts, reqs):
        want = np.asarray(transformer.generate(
            live, jnp.asarray(p[None]), CFG, max_new=6))[0]
        np.testing.assert_array_equal(r.output, want)
    assert eng.compile_counts()["decode"] == 1


def test_engine_artifact_v4_kv_int8_roundtrip(tmp_path, rng):
    """v4 + engine_kv_dtype="int8": the KV-dtype stamp rides
    meta.engine_paged, the loader rebuilds the quantized pool (int8
    values + fp32 scale tables) with no model code, and the served
    engine's output is bitwise the in-process int8-pool engine's —
    the artifact pins the pool layout, not just the programs."""
    import pytest
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "lm_v4_kv8.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=2,
                                prompt_len=6, cache_len=32,
                                engine_buckets=(8, 16),
                                engine_block_size=8,
                                engine_kv_dtype="int8")
    srv = lm_serving.load_lm_artifact(path)
    assert srv.meta["engine_paged"]["kv_dtype"] == "int8"
    eng = srv.engine(seed=0, tracker=CompileTracker())
    assert eng.kv_dtype == "int8"
    assert eng.cache["k"].dtype == jnp.int8 and "k_scale" in eng.cache
    ref = PagedDecodeEngine.from_params(
        params, CFG, batch=2, cache_len=32, block_size=8,
        chunk_tokens=16, seed=0, kv_dtype="int8",
        tracker=CompileTracker())
    prompts = [rng.randint(0, 40, n).astype(np.int32) for n in (5, 24)]
    outs = {}
    for name, e in (("art", eng), ("ref", ref)):
        reqs = [e.submit(p, max_new=6) for p in prompts]
        e.run_until_idle()
        outs[name] = [r.output.tolist() for r in reqs]
    assert outs["art"] == outs["ref"]
    h = eng.health()
    assert h["kv_dtype"] == "int8"
    assert h["kv_bytes_per_token"] == ref.kv_bytes_per_token
    # the quantized pool is the engine's: an export with NO engine
    # modules must raise rather than silently dropping the requested
    # quantization
    with pytest.raises(ValueError,
                       match="engine_kv_dtype needs engine_buckets"):
        lm_serving.save_lm_artifact(
            str(tmp_path / "bad2.tar"), params, CFG, batch=2,
            prompt_len=6, cache_len=32, engine_kv_dtype="int8")


def test_engine_requires_v3(tmp_path, rng):
    """v1/v2 artifacts refuse engine() with a re-export hint."""
    import pytest
    params = transformer.init_params(jax.random.PRNGKey(0), CFG)
    path = str(tmp_path / "lm_v1.tar")
    lm_serving.save_lm_artifact(path, params, CFG, batch=1,
                                prompt_len=4, cache_len=12)
    srv = lm_serving.load_lm_artifact(path)
    assert srv.meta["format_version"] == 1
    with pytest.raises(ValueError, match="engine_buckets"):
        srv.engine()


def test_moe_artifact_roundtrip_matches_generate(tmp_path, rng):
    """The serving artifact carries MoE configs transparently (cfg
    round-trips through dataclasses.asdict; decode runs the expert FFN
    drop-free), so the expert family serves like the dense one."""
    cfg = transformer.TransformerConfig(
        vocab=40, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_len=32, dtype=jnp.float32, moe_experts=4,
        moe_capacity_factor=4.0)
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)
    B, Tp, new = 2, 6, 8
    prompt = rng.randint(0, 40, (B, Tp)).astype(np.int32)
    path = str(tmp_path / "lm_moe.tar")
    lm_serving.save_lm_artifact(path, params, cfg, batch=B,
                                prompt_len=Tp, cache_len=Tp + new)
    srv = lm_serving.load_lm_artifact(path)
    got = srv.generate(prompt, max_new=new)
    want = np.asarray(transformer.generate(
        params, jnp.asarray(prompt), cfg, max_new=new))
    np.testing.assert_array_equal(got, want)


def test_engine_pallas_resolves_from_the_export_target(tmp_path,
                                                       monkeypatch):
    """The engine modules' kernel policy follows the platform they are
    exported FOR, not the exporting process's backend: a TPU-only
    export from this CPU host places the compiled kernels (it used to
    stamp the exporter's "off" and serve XLA on the chip without a
    word); a mixed target list cannot carry Mosaic kernels and
    resolves — and is stamped — "off"."""
    import dataclasses

    import pytest
    from paddle_tpu.ops.pallas import policy
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    cfg = dataclasses.replace(CFG, max_len=128)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(batch=2, prompt_len=4, cache_len=128,
              engine_buckets=(128,))
    tpu = str(tmp_path / "tpu.tar")
    with policy.compile_target("TPU v5 lite"):
        lm_serving.save_lm_artifact(tpu, params, cfg,
                                    platforms=["tpu"], **kw)
    meta = lm_serving.load_lm_artifact(tpu).meta
    assert meta["engine_pallas"] == "on"
    assert meta["engine_paged"]["block_size"] == 128     # the default
    assert {v for rec in meta["engine_kernel_paths"].values()
            for v in rec.values()} == {"pallas"}
    # without a named chip the compiled kernels have no VMEM figure
    with pytest.raises(ValueError, match="no VMEM figure"):
        lm_serving.save_lm_artifact(tpu, params, cfg,
                                    platforms=["tpu"], **kw)
    # a mixed target list cannot carry Mosaic kernels: XLA path, stamped
    assert policy.pallas_mode(None, platform="mixed") == "off"
    assert policy.pallas_mode(None, platform="tpu") == "on"


# -- the block matrices in the compute dtype (PR 29) -------------------------
# A serving program is handed ``qkv`` / ``attn_out`` / ``mlp_in`` /
# ``mlp_out`` in ``cfg.dtype``: cast once at export and once in
# ``LMServer.engine()``, not by every decode step and prefill chunk.

CFG_BF16 = transformer.TransformerConfig(
    vocab=40, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_len=32, dtype=jnp.bfloat16, use_rope=False)
MATRICES = transformer.COMPUTE_DTYPE_LEAVES
LEAVES = ("embed", "pos", "ln_f", "ln_f_b") + tuple(
    "blocks/" + n for n in ("ln1", "ln1_b", "ln2", "ln2_b") + MATRICES)


def _leaf(tree, path):
    for p in path.split("/"):
        tree = tree[p]
    return tree


def _export_paged(path, params, cfg, **kw):
    lm_serving.save_lm_artifact(
        path, params, cfg, batch=2, prompt_len=6, cache_len=32,
        engine_buckets=(8, 16), engine_block_size=8, **kw)


def _serve(eng, rng_seed=7, max_new=6):
    rng = np.random.RandomState(rng_seed)
    reqs = [eng.submit(rng.randint(0, 40, n).astype(np.int32),
                       max_new=max_new) for n in (5, 9, 20)]
    eng.run_until_idle()
    return [list(r.tokens) for r in reqs]


@pytest.fixture(scope="module")
def bf16_artifact(tmp_path_factory):
    """(float32 leaves, path of the artifact exported from them under
    the bf16 config): exported once for the cases below."""
    params = transformer.init_params(jax.random.PRNGKey(3), CFG_BF16)
    path = str(tmp_path_factory.mktemp("bf16") / "lm.tar")
    _export_paged(path, params, CFG_BF16)
    return params, path


@pytest.mark.parametrize("leaf", LEAVES)
def test_artifact_stores_matrices_in_compute_dtype(bf16_artifact, leaf):
    """Saved from float32 leaves under a bf16 config: exactly the four
    block matrices are stored as bf16, bit-equal to ``astype(bf16)``
    of the leaf handed in; every other leaf is the float32 one."""
    params, path = bf16_artifact
    got = _leaf(lm_serving.load_lm_artifact(path).params, leaf)
    want = _leaf(params, leaf)
    assert want.dtype == jnp.float32
    if leaf.split("/")[-1] in MATRICES:
        want = want.astype(jnp.bfloat16)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_engine_conforms_handed_in_float32_weights(bf16_artifact):
    """The benchmark's spelling: load, ``srv.params = <float32 tree>``,
    ``srv.engine()``. The tree is cast once, to the dtypes of the
    decode program's own inputs; the served greedy ids are those of
    the engine over the artifact's own leaves; no float32 matrix stays
    referenced by the server; the cast's seconds and the bytes the
    engine hands its programs are on record."""
    from paddle_tpu.utils.stat import global_stats
    params, path = bf16_artifact
    own = lm_serving.load_lm_artifact(path)
    eng_own = own.engine(seed=0)
    srv = lm_serving.load_lm_artifact(path)
    srv.params = params
    calls = global_stats.get("artifact/conform").count
    eng = srv.engine(seed=0)
    assert global_stats.get("artifact/conform").count == calls + 1
    assert eng.params is srv.params
    for leaf in LEAVES:
        x, want = _leaf(eng.params, leaf), _leaf(own.params, leaf)
        assert isinstance(x, jax.Array) and x.dtype == want.dtype, leaf
        np.testing.assert_array_equal(np.asarray(x), np.asarray(want))
        if leaf.split("/")[-1] not in MATRICES:
            # a device array in the right dtype is the one handed in
            assert x is _leaf(params, leaf), leaf
    assert _serve(eng) == _serve(eng_own)
    # the gauge reads the tree the programs are handed
    nbytes = sum(int(x.nbytes)
                 for x in jax.tree_util.tree_leaves(eng.params))
    assert nbytes < sum(int(x.nbytes)
                        for x in jax.tree_util.tree_leaves(params))
    assert eng.metrics.get("engine_weight_bytes").value() == nbytes
    # the artifact's own (host) leaves leave engine() on the device
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(eng_own.params))
    assert eng_own.metrics.get("engine_weight_bytes").value() == nbytes
    # float32 matrices set on the ENGINE, past the server (a
    # benchmark's next seed): conformed by the same one cast
    eng.params = params
    assert global_stats.get("artifact/conform").count == calls + 2
    assert eng.params["blocks"]["qkv"].dtype == jnp.bfloat16
    assert eng.params["embed"] is params["embed"]
    assert eng.metrics.get("engine_weight_bytes").value() == nbytes
    assert _serve(eng, rng_seed=8) == _serve(eng_own, rng_seed=8)
    eng_own.params = None           # a caller freeing the weights
    assert eng_own.params is None
    assert eng_own.metrics.get("engine_weight_bytes").value() == 0


def _identity_case(name, tmp_path, monkeypatch):
    """(config, tree, path of an artifact exported from that tree) of a
    case in which nothing is to cast."""
    from paddle_tpu.models import gated_hybrid
    path = str(tmp_path / "lm.tar")
    if name == "float32_config":
        params = transformer.init_params(jax.random.PRNGKey(0), CFG)
        _export_paged(path, params, CFG)
        return CFG, params, path
    if name == "int8_tree":
        params = lm_serving.quantize_lm_params(
            transformer.init_params(jax.random.PRNGKey(0), CFG_BF16))
        # an exported program typed from the tree stands in for the
        # int8 artifact's (test_engine_artifact_v4_int8_roundtrip
        # serves a real one)
    elif name == "gated_hybrid":
        cfg = transformer.TransformerConfig(
            vocab=64, d_model=32, n_heads=2, n_kv_heads=1, n_layers=4,
            d_ff=16, max_len=64, dtype=jnp.bfloat16, use_rope=True,
            skeleton="gated_hybrid", attn_head_dim=16, rotary_dim=8,
            full_attn_interval=4, rec_key_heads=2, rec_value_heads=2,
            rec_key_dim=16, rec_value_dim=16, moe_experts=4, moe_top_k=2,
            moe_shared_ff=16)
        return cfg, gated_hybrid.init_params(
            jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16), None
    elif name == "float32_programs":
        # what the parent commit exported: float32 leaves under a bf16
        # config, programs typed for them
        params = transformer.init_params(jax.random.PRNGKey(3), CFG_BF16)
        with monkeypatch.context() as m:
            m.setattr(transformer, "compute_dtype_params",
                      lambda p, cfg: p)
            _export_paged(path, params, CFG_BF16)
        return CFG_BF16, params, path
    return CFG_BF16, params, None


@pytest.mark.parametrize("case", ["float32_config", "int8_tree",
                                  "gated_hybrid", "float32_programs"])
def test_nothing_to_cast_is_the_identity(case, tmp_path, monkeypatch,
                                         bf16_artifact):
    """A float32 config, an int8 tree, the gated_hybrid skeleton (bf16
    leaves since it exists) and an artifact whose programs take float32
    leaves (the parent commit's): the helper returns the tree it was
    given, the conform step every leaf it was given, and the old
    artifact serves the ids the new one serves."""
    cfg, params, path = _identity_case(case, tmp_path, monkeypatch)
    if case != "float32_programs":
        assert transformer.compute_dtype_params(params, cfg) is params
    leaves = jax.tree_util.tree_leaves(params)
    if path is None:
        exported = jax.export.export(jax.jit(lambda p, x: x))(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
            jax.ShapeDtypeStruct((), jnp.int32))
        got = lm_serving._conform(params, exported)
        assert jax.tree_util.tree_structure(got) \
            == jax.tree_util.tree_structure(params)
    else:
        srv = lm_serving.load_lm_artifact(path)
        # host leaves of the artifact itself: placed, dtype kept
        own = srv.engine(seed=0)
        for a, b in zip(jax.tree_util.tree_leaves(own.params), leaves):
            assert isinstance(a, jax.Array) and a.dtype == b.dtype
        srv.params = params
        eng = srv.engine(seed=0)
        got = eng.params
        if case == "float32_programs":
            # float32 all through, cast by the program at every step:
            # the same ids as the artifact that stores them cast
            new = lm_serving.load_lm_artifact(bf16_artifact[1])
            assert _serve(eng) == _serve(new.engine(seed=0))
    for a, b in zip(jax.tree_util.tree_leaves(got), leaves):
        assert a is b


def test_spec_artifact_stores_the_draft_in_compute_dtype(tmp_path):
    """The speculative members take their trees the same way: the
    draft's matrices are stored in ITS compute dtype (the ``@bfloat16``
    member spelling), a float32 draft handed in is conformed to the
    propose program's inputs, and the engine serves the in-process
    engine's ids."""
    from paddle_tpu.serving import SpecDecodeEngine
    import dataclasses
    dcfg = dataclasses.replace(CFG_BF16, n_layers=1, d_ff=32)
    params = transformer.init_params(jax.random.PRNGKey(3), CFG_BF16)
    draft = transformer.init_params(jax.random.PRNGKey(4), dcfg)
    path = str(tmp_path / "spec.tar")
    lm_serving.save_lm_artifact(
        path, params, CFG_BF16, batch=2, prompt_len=6, cache_len=32,
        engine_buckets=(8,), engine_block_size=8,
        engine_draft_params=draft, engine_draft_config=dcfg,
        engine_spec_k=2)
    srv = lm_serving.load_lm_artifact(path)
    for tree in (srv.params, srv.draft_params):
        for n, x in tree["blocks"].items():
            assert x.dtype == (jnp.bfloat16 if n in MATRICES
                               else jnp.float32), n
        assert tree["embed"].dtype == jnp.float32
    srv.params, srv.draft_params = params, draft
    eng = srv.engine(seed=0)
    assert isinstance(eng, SpecDecodeEngine)
    assert eng.draft_params is srv.draft_params
    assert eng.draft_params["blocks"]["qkv"].dtype == jnp.bfloat16
    assert eng.metrics.get("engine_weight_bytes").value() == sum(
        int(x.nbytes) for x in jax.tree_util.tree_leaves(
            (eng.params, eng.draft_params)))
    mine = SpecDecodeEngine.from_params(
        params, CFG_BF16, draft, dcfg, spec_k=2, batch=2, cache_len=32,
        block_size=8, chunk_tokens=8, chunk_buckets=(8,), seed=0)
    assert mine.draft_params["blocks"]["mlp_out"].dtype == jnp.bfloat16
    assert _serve(eng) == _serve(mine)

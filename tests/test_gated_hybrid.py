"""The gated hybrid skeleton (models/gated_hybrid.py) against the plain
reference (chipbench/references/qwen3_next.py) at a small size: 2
periods = 8 layers, 16 experts top-4 with 8 held, vocabulary 256.

Tolerances, and why. float32: program and reference compute the same
float32 equations in another order of summation, so logits agree to
2e-4 of a logit scale of ~1 (read: 3e-5). bfloat16: the program rounds
activations and weights to 8 bits of mantissa at every map, and a
router near a tie may choose another k-th expert than the float32
reference, which moves single logits by up to ~1; so the bound is on
the MEAN absolute logit gap, 0.15 (read: 0.05-0.06), which the fp8
control (0.30) and a zeroed recurrent state fail.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next as ref
from paddle_tpu.models import gated_hybrid as gh
from paddle_tpu.models import transformer as tr
from paddle_tpu.parallel import moe
from paddle_tpu.serving.engine import PagedDecodeEngine, SpecDecodeEngine

HF = {"hidden_size": 64, "num_hidden_layers": 8,
      "full_attention_interval": 4, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32,
      "partial_rotary_factor": 0.25, "rope_theta": 10000.0,
      "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
      "linear_num_value_heads": 4, "linear_key_head_dim": 16,
      "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
      "num_experts": 16, "num_experts_per_tok": 4, "num_experts_held": 8,
      "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
      "vocab_size": 256}
DIMS = ref.dims_of(HF)
F32_TOL = 2e-4
BF16_MEAN_TOL = 0.15
BS, CHUNK, BUCKETS, CACHE = 16, 64, (16, 64), 256


def tiny(dtype=jnp.float32, **kw):
    base = dict(
        vocab=256, d_model=64, n_heads=4, n_kv_heads=2, n_layers=8,
        d_ff=32, max_len=512, dtype=dtype, use_rope=True, rope_theta=1e4,
        skeleton="gated_hybrid", attn_head_dim=32, rotary_dim=8,
        full_attn_interval=4, rec_key_heads=2, rec_value_heads=4,
        rec_key_dim=16, rec_value_dim=16, rec_conv=4, moe_experts=16,
        moe_top_k=4, moe_held=(0, 8), moe_shared_ff=32)
    base.update(kw)
    return tr.TransformerConfig(**base)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model(request):
    dt = jnp.dtype(request.param)
    cfg = tiny(dt)
    return cfg, gh.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, 256), np.int32)


def _close(got, want, dtype):
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    if jnp.dtype(dtype) == jnp.float32:
        return float(gap.max()) <= F32_TOL, float(gap.max())
    return float(gap.mean()) <= BF16_MEAN_TOL, float(gap.mean())


def test_forward_matches_reference(model):
    cfg, params = model
    toks = _tokens(128)
    got = jax.jit(lambda p, t: tr.forward(p, t, cfg))(params, toks[None])[0]
    want = ref.logits_at(params, toks, list(range(128)), dims=DIMS)
    ok, gap = _close(got, want, cfg.dtype)
    assert ok, gap
    # the control in the precision below the configuration's fails the
    # same bound
    low = ref.logits_at(params, toks, list(range(128)), dims=DIMS,
                        precision="fp8")
    assert not _close(low, want, cfg.dtype)[0]


def recording_engine(params, cfg, *, batch=3, num_blocks=40):
    """A PagedDecodeEngine over the real step programs with greedy
    sampling, whose every logit row is kept with the request and the
    position it belongs to."""
    log = {}        # (rid, position) -> logits row
    box = {}

    def kw(**k):
        return dict(block_size=BS, pallas="off", return_stats=True, **k)

    pf = jax.jit(lambda p, c, t, n, pg, s: tr.prefill_into_blocks(
        p, c, t, n, pg, cfg, **kw(slot=s)), donate_argnums=(1,))
    df = jax.jit(lambda p, c, t, pos, a, pg: tr.decode_step_paged(
        p, c, t, pos, a, pg, cfg, **kw()), donate_argnums=(1,))

    def prefill(p, pool, tokens, length, pages, slot, temp, topk, seed):
        lg, pool, st = pf(p, pool, tokens, length, pages, slot)
        req = box["eng"]._slot_req[int(slot)]
        if req is not None:     # None: precompile
            off = box["eng"]._slot_off[int(slot)]
            log[(req.rid, off + int(length) - 1)] = np.asarray(lg[0])
        return jnp.concatenate(
            [jnp.argmax(lg, -1).astype(jnp.int32), st]), pool

    def decode(p, pool, tokens, pos, active, pages, temp, topk, seed):
        lg, pool, st = df(p, pool, tokens, pos, active, pages)
        lg_h = np.asarray(lg)
        for s in np.flatnonzero(np.asarray(active)):
            log[(box["eng"]._slot_req[s].rid, int(pos[s]))] = lg_h[s]
        return jnp.concatenate(
            [jnp.argmax(lg, -1).astype(jnp.int32), st]), pool

    pool = tr.init_block_pool(cfg, num_blocks, BS, slots=batch)
    eng = box["eng"] = PagedDecodeEngine(
        prefill, decode, params, pool, batch=batch, cache_len=CACHE,
        block_size=BS, num_blocks=num_blocks, chunk_tokens=CHUNK,
        chunk_buckets=BUCKETS, seed=0)
    return eng, log


def test_paged_engine_logits_match_reference(model):
    """Chunked prefill over several chunks with a padded last chunk,
    then decode with slots at different positions, and slots reused by
    later requests (5 requests on 3 slots): every logit row the engine
    sampled from against the reference's full forward."""
    cfg, params = model
    eng, log = recording_engine(params, cfg)
    plan = [(150, 12), (70, 20), (9, 30), (130, 10), (64, 8)]
    reqs = [eng.submit(_tokens(n, seed=10 + i), m)
            for i, (n, m) in enumerate(plan)]
    eng.run_until_idle()
    assert eng.recurrent and eng.pool.cached_count == 0
    for req, (n, m) in zip(reqs, plan):
        assert len(req.tokens) == m
        seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1],
                                                     np.int32)])
        rows = list(range(n - 1, n - 1 + m))
        want = np.asarray(ref.logits_at(
            params, np.pad(seq, (0, -len(seq) % 64)), rows, dims=DIMS))
        got = np.stack([log[(req.rid, r)] for r in rows])
        ok, gap = _close(got, want, cfg.dtype)
        assert ok, (req.rid, gap)
        if cfg.dtype == jnp.float32:
            assert req.tokens == list(np.argmax(want, -1))
    c = eng.metrics.snapshot()
    calls = c["engine_moe_decode_layer_calls_total"]["series"][0]["value"]
    steps = c["engine_decode_steps_total"]["series"][0]["value"]
    assert calls == steps * cfg.n_layers
    assert c["engine_moe_assignments_total"]["series"][0]["value"] > 0
    hit = c["engine_moe_decode_experts_hit_total"]["series"][0]["value"]
    assert 0 < hit <= calls * 8
    doc = eng.health()
    assert doc["recurrent_state_bytes"] == eng.recurrent_state_bytes > 0
    # pages of the full-attention layers only: 2 of 8
    assert eng.kv_bytes_per_token == 2 * 2 * 2 * 32 * jnp.dtype(
        cfg.dtype).itemsize


def test_chunked_delta_rule_matches_recurrence():
    """The chunked form, state carried over three calls, against the
    token-by-token recurrence; zero ``beta`` and ``g`` (padding) leave
    the state alone."""
    B, T, H, dk, dv = 2, 192, 3, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 6)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = l2(jax.random.normal(ks[0], (B, T, H, dk))) / 4.0
    k = l2(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H), minval=0.001, maxval=0.7)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    S_seq, o_seq = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    S, outs = S0, []
    for a in range(0, T, 64):
        o, S = gh.chunked_delta_rule(*(x[:, a:a + 64]
                                       for x in (q, k, v, g, beta)), S)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1),
                               jnp.moveaxis(o_seq, 0, 1), atol=2e-5)
    np.testing.assert_allclose(S, S_seq, atol=2e-5)
    z = jnp.zeros((B, 64, H))
    _, S_pad = gh.chunked_delta_rule(q[:, :64], k[:, :64], v[:, :64], z, z,
                                     S)
    np.testing.assert_array_equal(S_pad, S)


def test_carried_state_matters(model):
    """Zeroing the recurrent rows between a prompt's chunks moves the
    next logits beyond the tolerance: the check sees the state."""
    cfg, params = model
    toks = _tokens(128, seed=5)
    pages = np.arange(8, dtype=np.int32)

    def run(zero_state):
        pool = tr.init_block_pool(cfg, 16, BS, slots=2)
        # a whole chunk, then a padded one: 8 valid tokens of 16
        for off, width, n in ((0, 64, 64), (64, 16, 8)):
            lg, pool = tr.prefill_into_blocks(
                params, pool, toks[None, off:off + width], np.int32(n),
                pages[:(off + width) // BS], cfg, block_size=BS,
                pallas="off", slot=np.int32(1))
            if zero_state and off == 0:
                pool = dict(pool, rec_state=jnp.zeros_like(
                    pool["rec_state"]), rec_tail=jnp.zeros_like(
                    pool["rec_tail"]))
        return lg[0]

    want = ref.logits_at(params, toks, [71], dims=DIMS)[0]
    ok, gap = _close(run(False), want, cfg.dtype)
    assert ok, gap
    ok, gap = _close(run(True), want, cfg.dtype)
    assert not ok, gap


def test_two_shares_sum_to_whole_layer():
    """The chip's share against the model: the routed parts of experts
    [0, 8) and [8, 16), with the shared expert counted once, add up to
    the uncut reference layer."""
    cfg = tiny()
    D = cfg.d_model
    w = moe.dropless_init_params(jax.random.PRNGKey(2), D, 32, 16, 16, 32)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    # the reference layer norms its input (weight 0: a plain RMS norm)
    layer = {"ln2": jnp.zeros((D,)), "moe": w}
    d = ref._Dims(ref._static(dict(DIMS, eps=1e-6)))
    whole = ref._experts(x, layer, d=d, fmt="f32", held=(0, 16)) - x
    xn = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    got, _ = moe.moe_dropless(w, xn, top_k=4, held=(0, 16))
    np.testing.assert_allclose(got, whole, atol=2e-5)
    no_experts = dict(w, **{n: w[n][:1] * 0 for n in ("w1", "w3", "w2")})
    shared, _ = moe.moe_dropless(no_experts, xn, top_k=4, held=(0, 1))
    parts, kept = [], 0
    for first in (0, 8):
        share = dict(w, **{n: w[n][first:first + 8]
                           for n in ("w1", "w3", "w2")})
        out, st = moe.moe_dropless(share, xn, top_k=4, held=(first, 8))
        parts.append(out - shared)
        kept += int(st[0])
    assert kept == 40 * 4               # every assignment computed once
    assert float(jnp.abs(parts[0]).max()) > 0
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=2e-5)


def test_preempt_and_resume_continue_token_for_token():
    cfg = tiny()
    params = gh.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [_tokens(100, seed=21), _tokens(40, seed=22)]

    def run(preempt_at):
        eng = PagedDecodeEngine.from_params(
            params, cfg, batch=2, cache_len=CACHE, block_size=BS,
            num_blocks=32, chunk_tokens=CHUNK, chunk_buckets=BUCKETS,
            seed=0, pallas="off", decode_flops=0.0)
        reqs = [eng.submit(p, 24) for p in prompts]
        steps = 0
        while not eng.idle:
            eng.step()
            steps += 1
            if steps == preempt_at:
                assert eng._preempt_victim()
        return eng, [r.tokens for r in reqs]

    _, plain = run(None)
    eng, resumed = run(9)           # both slots decoding by then
    assert resumed == plain
    c = eng.metrics.snapshot()
    assert c["engine_preemptions_total"]["series"][0]["value"] == 1
    modes = {tuple(s["labels"].items()): s["value"]
             for s in c["engine_resumes_total"]["series"]}
    assert modes == {(("mode", "replay"),): 1}
    assert eng.pool.cached_count == 0       # nothing was published


def test_artifact_round_trip(tmp_path):
    """save_lm_artifact -> load_lm_artifact -> engine(): bf16 leaves
    survive the .npz, the pool carries the recurrent rows, and the
    served tokens are those of the in-process engine."""
    from paddle_tpu.io import lm_serving
    cfg = tiny(jnp.bfloat16, n_layers=4)
    params = gh.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(
        path, params, cfg, batch=2, prompt_len=8, cache_len=128,
        engine_buckets=(16, 64), engine_block_size=BS,
        engine_num_blocks=16)
    srv = lm_serving.load_lm_artifact(path)
    assert srv.cfg == cfg
    flat = jax.tree_util.tree_leaves_with_path(srv.params)
    assert {str(np.asarray(v).dtype) for _, v in flat} \
        == {"bfloat16", "float32"}
    for (_, a), b in zip(flat, jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(NotImplementedError, match="gated_hybrid"):
        srv.generate(np.zeros((2, 8), np.int32), 4)
    eng = srv.engine(seed=0)
    eng.precompile()
    assert eng.recurrent and eng.moe_stats and "rec_state" in eng.cache
    mine = PagedDecodeEngine.from_params(
        params, cfg, batch=2, cache_len=128, block_size=BS, num_blocks=16,
        chunk_tokens=64, chunk_buckets=(16, 64), seed=0, pallas="off",
        decode_flops=0.0)
    out = []
    for e in (eng, mine):
        reqs = [e.submit(_tokens(70, seed=31), 10),
                e.submit(_tokens(20, seed=32), 12)]
        e.run_until_idle()
        out.append([r.tokens for r in reqs])
    assert out[0] == out[1]


def test_refusals():
    """Everything the skeleton does not run refuses through the one
    check and says so."""
    cfg = tiny()
    params = gh.init_params(jax.random.PRNGKey(0), cfg)
    pool = tr.init_block_pool(cfg, 8, BS, slots=2)
    toks = jnp.zeros((2,), jnp.int32)
    calls = {
        "decode_step": lambda: tr.decode_step(params, pool, toks, 0, cfg),
        "verify_step_paged": lambda: tr.verify_step_paged(
            params, pool, toks[:, None], toks, toks, toks > 0,
            jnp.zeros((2, 4), jnp.int32), cfg, block_size=BS),
        "prefill": lambda: tr.prefill(params, toks[None], cfg, 64),
        "generate": lambda: tr.generate(params, toks[None], cfg, max_new=2),
        "init_cache": lambda: tr.init_cache(cfg, 2, 64),
        "int8 pool": lambda: tr.init_block_pool(cfg, 8, BS,
                                                kv_dtype="int8", slots=2),
        "int4 pool": lambda: tr.init_block_pool(cfg, 8, BS,
                                                kv_dtype="int4", slots=2),
        "kernels": lambda: tr.decode_step_paged(
            params, pool, toks, toks, toks > 0,
            jnp.zeros((2, 4), jnp.int32), cfg, block_size=BS,
            pallas="interpret"),
        "int8 weights": lambda: tr.decode_step_paged(
            dict(params, head={"q8": jnp.zeros((256, 64), jnp.int8),
                               "scale": jnp.ones((256, 1))}),
            pool, toks, toks, toks > 0, jnp.zeros((2, 4), jnp.int32), cfg,
            block_size=BS, pallas="off"),
        "spec engine": lambda: SpecDecodeEngine(
            None, None, params, pool, draft_params=None, draft_cache={},
            draft_prefill=None, propose=None, verify=None,
            draft_verify=None, spec_k=2),
        "spec fns": lambda: __import__(
            "paddle_tpu.serving.sampling", fromlist=["x"]).paged_spec_fns(
            cfg, cfg, BS, 2),
        "mesh forward": lambda: tr.forward(params, toks[None], cfg,
                                           return_kv=True),
    }
    for what, call in calls.items():
        with pytest.raises(NotImplementedError, match="gated_hybrid"):
            call()
        assert what
    from paddle_tpu.io import lm_serving
    for kw in ({"weights_int8": True}, {"engine_kv_dtype": "int8"},
               {"engine_buckets": None}):
        args = dict(batch=2, prompt_len=8, cache_len=64,
                    engine_buckets=(16,), engine_block_size=BS)
        args.update(kw)
        with pytest.raises(NotImplementedError, match="gated_hybrid"):
            lm_serving.save_lm_artifact("/nonexistent/x.tar", params, cfg,
                                        **args)
    eng = PagedDecodeEngine.from_params(
        params, cfg, batch=2, cache_len=64, block_size=BS,
        chunk_tokens=16, seed=0, pallas="off", decode_flops=0.0)
    assert eng.prefix_digests(np.arange(40)) == []
    assert eng.export_prefix(np.arange(40)) is None
    with pytest.raises(ValueError, match="recurrent"):
        eng.import_prefix(b"")
    with pytest.raises(ValueError, match="recurrent"):
        PagedDecodeEngine.from_params(
            params, cfg, batch=2, cache_len=64, block_size=BS,
            chunk_tokens=16, seed=0, pallas="off", decode_flops=0.0,
            tiers={"dram_bytes": 1 << 20})
    with pytest.raises(ValueError, match="gated_hybrid"):
        dataclasses.replace(cfg, n_layers=6)
    with pytest.raises(ValueError, match="gated_hybrid"):
        tr.TransformerConfig(vocab=8, attn_head_dim=32)

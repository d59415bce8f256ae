"""The latent-attention skeleton (models/latent_moe.py) against the plain
reference (chipbench/references/glm4_moe_lite.py) at a small size: 1
dense + 2 expert layers, 4 heads over a 32-value latent with an 8-value
rotary key, 16 experts top-4 all held, vocabulary 256, a prediction
module of depth 1.

Tolerances, and why. float32: program and reference compute the same
float32 equations in another order of summation (the program's decode
and its short chunks in the ABSORBED form, the reference up-projecting
every position), so logits agree to 2e-4 of a logit scale of ~1 (read:
3e-6). bfloat16: the program rounds activations and weights to 8 bits
of mantissa at every map and a router near a tie may choose another
fourth expert than the float32 reference, which moves single logits by
up to ~1; so the bound is on the MEAN absolute logit gap over all the
rows a test compares (80 - 128 rows: single requests of 8 - 30 rows
spread 0.005 - 0.04), 0.02 (read: 0.0096 - 0.015), which the int8
control (the reference with both operands of every map rounded to int8
under per-row scales, the nearest precision below: 0.022 - 0.038 on
the same rows; at this width it is only 2.5 times as coarse as bf16)
and every planted fault of the reference (0.07 - 0.23) fail. The
prediction module's logits pass one block and one map more: their
bound is half as wide again, 0.03 (read: 0.022; int8 control 0.051).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import glm4_moe_lite as ref
from paddle_tpu.models import latent_moe as lm
from paddle_tpu.models import transformer as tr
from paddle_tpu.parallel import moe
from paddle_tpu.serving.engine import PagedDecodeEngine, SpecDecodeEngine

HF = {"hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
      "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
      "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "n_routed_experts": 16,
      "num_experts_per_tok": 4, "routed_scaling_factor": 1.8,
      "vocab_size": 256}
DIMS = ref.dims_of(HF)
F32_TOL = 2e-4
BF16_MEAN_TOL = 0.02
BS, CHUNK, BUCKETS, CACHE = 16, 64, (16, 64), 256


def tiny(dtype=jnp.float32, **kw):
    base = dict(
        vocab=256, d_model=64, n_heads=4, n_layers=3, d_ff=32, max_len=512,
        dtype=dtype, use_rope=True, rope_theta=1e4, skeleton="latent_moe",
        norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=32, qk_nope_dim=12,
        qk_rope_dim=8, v_head_dim=16, dense_layers=1, dense_ff=96,
        moe_experts=16, moe_top_k=4, moe_shared_ff=32, moe_route_scale=1.8,
        mtp_layers=1)
    base.update(kw)
    return tr.TransformerConfig(**base)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model(request):
    dt = jnp.dtype(request.param)
    cfg = tiny(dt)
    return cfg, lm.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)


def _tokens(n, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, 256), np.int32)


def _close(got, want, dtype, widen=1.0):
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    if jnp.dtype(dtype) == jnp.float32:
        return float(gap.max()) <= F32_TOL * widen, float(gap.max())
    return float(gap.mean()) <= BF16_MEAN_TOL * widen, float(gap.mean())


def test_forward_and_prediction_module_match_reference(model):
    cfg, params = model
    toks = _tokens(128)
    got, got_mtp = jax.jit(lambda p, t: lm.forward_mtp(p, t, cfg))(
        params, toks[None])
    assert got_mtp.shape == (1, 127, 256)
    np.testing.assert_array_equal(
        got, jax.jit(lambda p, t: tr.forward(p, t, cfg))(params, toks[None]))
    rows = list(range(128))
    want = ref.logits_at(params, toks, rows, dims=DIMS)
    ok, gap = _close(got[0], want, cfg.dtype)
    assert ok, gap
    want_mtp = ref.mtp_logits_at(params, toks, rows[:-1], dims=DIMS)
    ok, gap = _close(got_mtp[0], want_mtp, cfg.dtype, widen=1.5)
    assert ok, gap
    low = ref.mtp_logits_at(params, toks, rows[:-1], dims=DIMS,
                            precision="int8")
    assert not _close(low, want_mtp, cfg.dtype, widen=1.5)[0]
    # the control in the precision below the configuration's, and every
    # mistake a new part would make, fail the same bound
    low = ref.logits_at(params, toks, rows, dims=DIMS, precision="int8")
    assert not _close(low, want, cfg.dtype)[0]
    for fault in ref.FAULTS:
        bad = ref.logits_at(params, toks, rows, dims=DIMS, fault=fault)
        assert not _close(bad, want, cfg.dtype)[0], fault


def test_lengths_mask_padding(model):
    cfg, params = model
    a, b = _tokens(48, seed=2), _tokens(20, seed=3)
    batch = np.zeros((2, 48), np.int32)
    batch[0], batch[1, :20] = a, b
    got = tr.forward(params, batch, cfg, lengths=np.asarray([48, 20]))
    alone = tr.forward(params, b[None], cfg)
    tol = F32_TOL if cfg.dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(got[1, :20], alone[0], atol=tol)


def recording_engine(params, cfg, *, batch=3, num_blocks=40, **kw):
    """A PagedDecodeEngine over the real step programs with greedy
    sampling, whose every logit row is kept with the request and the
    position it belongs to."""
    log = {}        # (rid, position) -> logits row
    box = {}

    def step_kw():
        return dict(block_size=BS, pallas="off", return_stats=True)

    pf = jax.jit(lambda p, c, t, n, pg: tr.prefill_into_blocks(
        p, c, t, n, pg, cfg, **step_kw()), donate_argnums=(1,))
    df = jax.jit(lambda p, c, t, pos, a, pg: tr.decode_step_paged(
        p, c, t, pos, a, pg, cfg, **step_kw()), donate_argnums=(1,))

    def prefill(p, pool, tokens, length, pages, temp, topk, seed):
        lg, pool, st = pf(p, pool, tokens, length, pages)
        slot = box.get("slot")
        if slot is not None:    # None: precompile
            req = box["eng"]._slot_req[slot]
            off = box["eng"]._slot_off[slot]
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

    pool = tr.init_block_pool(cfg, num_blocks, BS)
    eng = box["eng"] = PagedDecodeEngine(
        prefill, decode, params, pool, batch=batch, cache_len=CACHE,
        block_size=BS, num_blocks=num_blocks, chunk_tokens=CHUNK,
        chunk_buckets=BUCKETS, seed=0, **kw)
    dispatch = eng._dispatch_chunk

    def noting(slot, *a):
        box["slot"] = slot
        return dispatch(slot, *a)

    eng._dispatch_chunk = noting
    return eng, log


def _served_rows(req, log):
    n, m = req.prompt.size, len(req.tokens)
    return np.stack([log[(req.rid, r)] for r in range(n - 1, n - 1 + m)])


def test_paged_engine_logits_match_reference(model):
    """Chunked prefill (prompts that end at a block boundary, off one,
    inside the first block; the 64-token chunks up-project, the padded
    16-token tails absorb), then paged decode (absorbed) with slots at
    different positions and slots reused by later requests (5 requests
    on 3 slots): every logit row the engine sampled from against the
    reference's full forward, which up-projects every position."""
    cfg, params = model
    assert lm.upproject_wins(cfg, 64) and not lm.upproject_wins(cfg, 16) \
        and not lm.upproject_wins(cfg, 1)
    eng, log = recording_engine(params, cfg)
    plan = [(150, 12), (70, 20), (9, 30), (128, 10), (64, 8)]
    reqs = [eng.submit(_tokens(n, seed=10 + i), m)
            for i, (n, m) in enumerate(plan)]
    eng.run_until_idle()
    assert not eng.recurrent and eng.recurrent_state_bytes == 0
    got, want, low = [], [], []
    for req, (n, m) in zip(reqs, plan):
        assert len(req.tokens) == m
        seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1],
                                                     np.int32)])
        seq = np.pad(seq, (0, -len(seq) % 64))
        rows = list(range(n - 1, n - 1 + m))
        want.append(np.asarray(ref.logits_at(params, seq, rows, dims=DIMS)))
        low.append(np.asarray(ref.logits_at(params, seq, rows, dims=DIMS,
                                            precision="int8")))
        got.append(_served_rows(req, log))
        if cfg.dtype == jnp.float32:
            assert req.tokens == list(np.argmax(want[-1], -1))
            assert _close(got[-1], want[-1], cfg.dtype)[0], req.rid
    want = np.concatenate(want)
    ok, gap = _close(np.concatenate(got), want, cfg.dtype)
    assert ok, gap
    assert not _close(np.concatenate(low), want, cfg.dtype)[0]
    c = eng.metrics.snapshot()

    def value(name):
        return c[name]["series"][0]["value"]

    steps = value("engine_decode_steps_total")
    assert value("engine_moe_decode_layer_calls_total") == steps * 2
    assert value("engine_moe_assignments_total") > 0
    assert 0 < value("engine_moe_decode_experts_hit_total") <= steps * 2 * 16
    # one latent row a token and layer, stored in whole lanes
    assert lm.row_width(cfg) == 40 and lm.pool_width(cfg) == 128
    per = 3 * 128 * jnp.dtype(cfg.dtype).itemsize
    assert eng.kv_bytes_per_token == per == tr.kv_pool_bytes_per_token(cfg)
    assert value("engine_kv_bytes_per_token") == per
    assert value("engine_kv_pool_bytes") == per * 40 * BS
    # the gathered view reads every slot's whole span at every step
    assert value("engine_decode_read_rows_total") == steps * 3 * CACHE
    live = value("engine_decode_live_rows_total")
    assert 0 < live < value("engine_decode_read_rows_total")
    assert live == sum(sum(range(n + 1, n + m)) for n, m in plan)


def test_absorbed_and_upprojecting_attention_agree_on_one_cache():
    """The two forms over the same pool rows, queries and mask."""
    cfg = tiny()
    w = jax.tree_util.tree_map(
        lambda a: a[0],
        lm.init_params(jax.random.PRNGKey(5), cfg)["sparse"])
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    B, N, T = 2, 5, 48
    q_nope = jax.random.normal(ks[0], (B, N, 4, 12))
    q_rope = jax.random.normal(ks[1], (B, N, 4, 8))
    rows = jnp.pad(jax.random.normal(ks[2], (B, T, 40)),
                   ((0, 0), (0, 0), (0, 88)))
    mask = jnp.arange(T)[None, None, :] <= (20 + jnp.arange(N))[None, :, None]
    mask = jnp.broadcast_to(mask, (B, N, T))
    a = lm.attend_absorbed(w, cfg, q_nope, q_rope, rows, mask)
    u = lm.attend_upproject(w, cfg, q_nope, q_rope, rows, mask)
    assert a.shape == u.shape == (B, N, 4, 16)
    np.testing.assert_allclose(a, u, atol=2e-5)
    # rows behind the mask do not matter
    junk = rows.at[:, 30:].set(7.0)
    np.testing.assert_allclose(
        lm.attend_absorbed(w, cfg, q_nope, q_rope, junk, mask), a, atol=1e-6)


def test_upproject_wins_at_the_published_widths():
    """The count the docstring makes: from 399 queries a sequence on."""
    cfg = tiny(q_lora_rank=768, kv_lora_rank=512, qk_nope_dim=192,
               qk_rope_dim=64, v_head_dim=256, n_heads=20, d_model=2048)
    assert not lm.upproject_wins(cfg, 1) and not lm.upproject_wins(cfg, 256)
    assert not lm.upproject_wins(cfg, 398) and lm.upproject_wins(cfg, 399)
    assert lm.upproject_wins(cfg, 1024)
    assert (lm.row_width(cfg), lm.pool_width(cfg)) == (576, 640)


def _routing_by_hand(w, x, k, scale):
    """s = sigmoid(x W); T = top-k of s + b; g = scale s / sum_T s."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(w["router"], np.float64))))
    b = np.asarray(w["router_bias"], np.float64)
    chosen = np.argsort(-(s + b), axis=-1)[:, :k]
    g = np.take_along_axis(s, chosen, -1)
    return chosen, scale * g / g.sum(-1, keepdims=True)


@pytest.mark.parametrize("scale", [1.0, 1.8])
def test_routing_rule_against_a_hand_computation(scale):
    """The bias moves the selection and not the weights; the weights
    are renormalised over the k and scaled."""
    D, E, k = 64, 16, 4
    w = moe.dropless_init_params(jax.random.PRNGKey(2), D, 32, E, E, 32,
                                 route="sigmoid_bias")
    assert "s_gate" not in w and w["router_bias"].shape == (E,)
    w["router_bias"] = w["router_bias"] * 3.0       # so that it bites
    x = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    logits = jnp.einsum("nd,de->ne", x, w["router"], precision="highest")
    gate, expert = moe.route_sigmoid_bias(scale)(w, logits, k)
    chosen, g = _routing_by_hand(w, x, k, scale)
    np.testing.assert_array_equal(np.sort(expert, -1), np.sort(chosen, -1))
    order = np.argsort(np.asarray(expert), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gate), order, -1),
        np.take_along_axis(g, np.argsort(chosen, -1), -1), atol=1e-6)
    np.testing.assert_allclose(np.sum(gate, -1), scale, atol=1e-6)
    # without the bias other experts are chosen for some tokens, and
    # where the same four are chosen the weights are the same
    plain, _ = _routing_by_hand(dict(w, router_bias=w["router_bias"] * 0),
                                x, k, scale)
    same = (np.sort(plain, -1) == np.sort(chosen, -1)).all(-1)
    assert 0 < same.sum() < len(same)
    # the layer under the rule: the hand-weighted sum of the chosen
    # experts' SwiGLUs plus the ungated shared expert
    got, st = moe.moe_dropless(w, x, top_k=k, held=(0, E),
                               route=moe.route_sigmoid_bias(scale))

    def swiglu(h, w1, w3, w2):
        return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2

    want = swiglu(x, w["s_w1"], w["s_w3"], w["s_w2"])
    for n in range(x.shape[0]):
        for e, ge in zip(chosen[n], g[n]):
            want = want.at[n].add(ge * swiglu(
                x[n], w["w1"][e], w["w3"][e], w["w2"][e]))
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert int(st[0]) == 40 * k


def test_four_shares_sum_to_whole_layer():
    """The chip's share against the model, under the sigmoid rule: the
    routed parts of experts [0, 16), [16, 32), [32, 48) and [48, 64),
    with the shared expert counted once, add up to the uncut reference
    layer."""
    D, E, k = 64, 64, 4
    route = moe.route_sigmoid_bias(1.8)
    w = moe.dropless_init_params(jax.random.PRNGKey(2), D, 32, E, E, 32,
                                 route="sigmoid_bias")
    x = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    # the reference layer norms its input (weight 1: a plain RMS norm)
    layer = {"ln2": jnp.ones((D,)), "moe": w}
    d = ref._static(dict(DIMS, E=E))
    whole = ref._experts(x, layer, d=d, fmt="f32", held=(0, E),
                         fault=None) - x
    xn = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    got, _ = moe.moe_dropless(w, xn, top_k=k, held=(0, E), route=route)
    np.testing.assert_allclose(got, whole, atol=3e-5)
    none = dict(w, **{n: w[n][:1] * 0 for n in ("w1", "w3", "w2")})
    shared, _ = moe.moe_dropless(none, xn, top_k=k, held=(0, 1),
                                 route=route)
    parts, kept = [], 0
    for first in (0, 16, 32, 48):
        share = dict(w, **{n: w[n][first:first + 16]
                           for n in ("w1", "w3", "w2")})
        out, st = moe.moe_dropless(share, xn, top_k=k, held=(first, 16),
                                   route=route)
        parts.append(out - shared)
        kept += int(st[0])
        assert float(jnp.abs(parts[-1]).max()) > 0
    assert kept == 40 * k               # every assignment computed once
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=3e-5)


def _undisturbed(params, cfg, prompts, max_new):
    eng, log = recording_engine(params, cfg, batch=2, num_blocks=64)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run_until_idle()
    return [(r.tokens, _served_rows(r, log)) for r in reqs]


def test_prefix_adoption_gives_the_logits_of_a_cold_run():
    """A second request with the first one's 128-token prefix adopts
    its published latent pages (two whole chunks) and its logits are
    bit for bit those of a cold run of the same prompt; the pages also
    cross the transfer wire into another engine's pool."""
    cfg = tiny()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    shared = _tokens(128, seed=40)
    second = np.concatenate([shared, _tokens(30, seed=41)])
    (cold_tokens, cold_rows), = _undisturbed(params, cfg, [second], 12)
    eng, log = recording_engine(params, cfg, batch=2, num_blocks=64)
    eng.submit(np.concatenate([shared, _tokens(9, seed=42)]), 4)
    eng.run_until_idle()
    assert eng.pool.cached_count == 8           # 128 tokens published
    req = eng.submit(second, 12)
    eng.run_until_idle()
    assert req.prefix_hit_tokens == 128
    assert req.tokens == cold_tokens
    np.testing.assert_array_equal(_served_rows(req, log), cold_rows)
    # P/D: the same pages exported, imported and hit on another engine
    payload = eng.export_prefix(second)
    other, log2 = recording_engine(params, cfg, batch=2, num_blocks=64)
    assert other.import_prefix(payload) == len(eng.prefix_digests(second))
    req2 = other.submit(second, 12)
    other.run_until_idle()
    assert req2.prefix_hit_tokens == 128 and req2.tokens == cold_tokens
    np.testing.assert_array_equal(_served_rows(req2, log2), cold_rows)


@pytest.mark.parametrize("mode", ["remap", "replay"])
def test_preempt_and_resume_give_the_logits_of_an_undisturbed_run(mode):
    """Preempt-to-blocks over a latent pool: the victim's pages are
    published and it resumes by re-mapping them (``remap``), or by a
    cache-hit prefill and forced replay after a large arrival evicted
    them (``replay``): tokens and logits are those of an undisturbed
    run."""
    cfg = tiny()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = _tokens(100, seed=21)
    (want_tokens, want_rows), = _undisturbed(params, cfg, [prompt], 24)
    # 8 blocks hold the victim (100 + 24 tokens)
    eng, log = recording_engine(params, cfg, batch=2, num_blocks=8)
    v = eng.submit(prompt, 24, tier="batch")
    for _ in range(8):
        eng.step()
    assert v.status == "running" and len(v.tokens) >= 3
    if mode == "remap":
        assert eng._preempt_victim() and v.status == "preempted"
    else:
        lat = eng.submit(_tokens(100, seed=22), 20, tier="latency")
        eng.step()
        assert v.status == "preempted"
    eng.run_until_idle()
    assert v.tokens == want_tokens
    np.testing.assert_allclose(_served_rows(v, log), want_rows, atol=2e-6)
    modes = {s["labels"]["mode"]: s["value"] for s in eng.metrics.snapshot()[
        "engine_resumes_total"]["series"]}
    assert modes == {mode: 1}


def test_pages_demoted_to_a_tier_come_back_as_they_were():
    """Tiered spill over a latent pool: a finished request's published
    pages are evicted by a larger one and demoted to host memory; the
    same prompt later re-adopts them from there and reads the logits of
    its first, cold run."""
    cfg = tiny()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = _tokens(130, seed=51)
    eng, log = recording_engine(params, cfg, batch=1, num_blocks=12,
                                tiers={"dram_bytes": 1 << 22})
    first = eng.submit(prompt, 6)
    eng.run_until_idle()
    eng.submit(_tokens(170, seed=52), 8)        # 12 blocks: evicts all
    eng.run_until_idle()
    snap = eng.metrics.snapshot()
    assert snap["engine_tier_demotions_total"]["series"][0]["value"] >= 8
    again = eng.submit(prompt, 6)
    eng.run_until_idle()
    hits = {s["labels"]["tier"]: s["value"] for s in eng.metrics.snapshot()[
        "engine_prefix_tier_hit_blocks_total"]["series"]}
    assert hits.get("dram", 0) >= 4 and again.prefix_hit_tokens >= 64
    assert again.tokens == first.tokens
    np.testing.assert_array_equal(_served_rows(again, log),
                                  _served_rows(first, log))


def test_artifact_round_trip(tmp_path):
    """save_lm_artifact -> load_lm_artifact -> engine(): bf16 leaves
    survive the .npz, the pool is the latent table alone, the chunk
    programs take no slot, and the served tokens are those of the
    in-process engine."""
    from paddle_tpu.io import lm_serving
    cfg = tiny(jnp.bfloat16, mtp_layers=0)
    params = lm.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    path = str(tmp_path / "lm.tar")
    lm_serving.save_lm_artifact(
        path, params, cfg, batch=2, prompt_len=8, cache_len=128,
        engine_buckets=(16, 64), engine_block_size=BS,
        engine_num_blocks=16)
    srv = lm_serving.load_lm_artifact(path)
    assert srv.cfg == cfg and srv.meta["format_version"] == 4
    assert set(srv.meta["engine_kernel_paths"]["decode"].values()) == {"xla"}
    flat = jax.tree_util.tree_leaves_with_path(srv.params)
    assert {str(np.asarray(v).dtype) for _, v in flat} \
        == {"bfloat16", "float32"}
    for (_, a), b in zip(flat, jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(NotImplementedError, match="latent_moe"):
        srv.generate(np.zeros((2, 8), np.int32), 4)
    eng = srv.engine(seed=0)
    eng.precompile()
    assert not eng.recurrent and eng.moe_stats
    assert set(eng.cache) == {"latent"}
    assert eng.cache["latent"].shape == (3, 1, 16 * BS, 128)
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
        assert e.pool.cached_count > 0      # prefix publishing stays on
    assert out[0] == out[1]


def test_refusals():
    """Everything the skeleton does not run refuses through the one
    check and says so."""
    cfg = tiny()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    pool = tr.init_block_pool(cfg, 8, BS)
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
                                                kv_dtype="int8"),
        "kernels": lambda: tr.decode_step_paged(
            params, pool, toks, toks, toks > 0,
            jnp.zeros((2, 4), jnp.int32), cfg, block_size=BS,
            pallas="interpret"),
        "spec engine": lambda: SpecDecodeEngine(
            None, None, params, pool, draft_params=None, draft_cache={},
            draft_prefill=None, propose=None, verify=None,
            draft_verify=None, spec_k=2),
        "spec fns": lambda: __import__(
            "paddle_tpu.serving.sampling", fromlist=["x"]).paged_spec_fns(
            cfg, cfg, BS, 2),
        "mesh forward": lambda: tr.forward(params, toks[None], cfg,
                                           return_kv=True),
        "sharded": lambda: tr.param_shardings(cfg, None),
    }
    for what, call in calls.items():
        with pytest.raises(NotImplementedError, match="latent_moe"):
            call()
        assert what
    from paddle_tpu.io import lm_serving
    for kw in ({"weights_int8": True}, {"engine_kv_dtype": "int8"},
               {"engine_buckets": None}):
        args = dict(batch=2, prompt_len=8, cache_len=64,
                    engine_buckets=(16,), engine_block_size=BS)
        args.update(kw)
        with pytest.raises(NotImplementedError, match="latent_moe"):
            lm_serving.save_lm_artifact("/nonexistent/x.tar", params, cfg,
                                        **args)
    with pytest.raises(ValueError, match="latent_moe"):
        dataclasses.replace(cfg, dense_layers=3)
    with pytest.raises(ValueError, match="latent_moe"):
        dataclasses.replace(cfg, qk_rope_dim=7)
    with pytest.raises(ValueError, match="latent_moe"):
        tr.TransformerConfig(vocab=8, kv_lora_rank=32)
    with pytest.raises(ValueError, match="skeleton"):
        tr.TransformerConfig(vocab=8, skeleton="latent")

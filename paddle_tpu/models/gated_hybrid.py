"""The gated hybrid block: ``TransformerConfig(skeleton="gated_hybrid")``.

A decoder whose layers come in periods of ``full_attn_interval``: the
first ``interval - 1`` mix tokens by a GATED DELTA RULE (a linear
recurrence over a per-head state matrix, behind a short causal
convolution), the last by softmax attention with an output gate. Every
layer's second half is a dropless expert layer (``parallel/moe.
moe_dropless``) with a shared expert. Norms are zero-centred RMSNorms,
the linear maps carry no bias, heads have a width of their own
(``attn_head_dim``), the rotary embedding turns the first ``rotary_dim``
dims of a head, the output head is untied.

    x <- x + mixer(N(x));  x <- x + experts(N(x))
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)                  (float32)

Gated attention (H heads over Hkv key/value heads of width Dh):
    [q | gate] = x Wq per head; k = x Wk; v = x Wv
    q, k <- N_Dh(q), N_Dh(k); rotary on the first ``rotary_dim`` dims
    out = (softmax(causal(q k^T / sqrt(Dh))) v * sigmoid(gate)) Wo

Gated delta rule (Hk key heads, Hv value heads, widths dk, dv; key head
j // (Hv/Hk) serves value head j):
    [q, k, v, z] = x W_qkvz (grouped per key head); [b, a] = x W_ba
    [q, k, v] <- silu(causal depthwise conv over time, kernel K, no bias)
    beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias)    (float32)
    q, k <- x / sqrt(sum x^2 + 1e-6) per head; q <- q / sqrt(dk)
    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t = S^T q_t;  out = (o_t / sqrt(mean o_t^2 + eps) * w * silu(z_t)) W_out

ONE definition of the block serves the three programs that run it,
through a small state adapter each:

- ``forward``: the whole sequence, no state kept (training-shaped use
  and the tests' full forward);
- ``prefill_chunk``: one chunk of one request: the slot's recurrent
  rows and its pages in and out (``transformer.prefill_into_blocks``);
- ``decode_step``: one token a slot (``transformer.decode_step_paged``).

The state beside the KV pool: per delta-rule layer and slot the matrix
``S`` ([Hv, dk, dv] float32) and the convolution's tail (the last K - 1
pre-convolution inputs). The pool pytree the engine donates is
``{"k", "v"}`` over the FULL-attention layers only (1 of ``interval``)
plus ``{"rec_state", "rec_tail"}``; all four ride the layer loop as its
carry and are updated in place. Layers are stacked per period and per
kind, and the loop scans over periods, so depth 4 and depth 48 are one
program body.
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import policy as _pallas_policy

HI = jax.lax.Precision.HIGHEST
SUB = 64        # tokens per sub-chunk of the chunked delta rule
SLOT_STATE = True   # the recurrent rows: per engine slot, beside the pages
_EXPERT = ("w1", "w3", "w2")    # the routed experts' weight stacks


def refuse(what: str):
    """The one refusal of everything the gated hybrid skeleton does not
    run: raised by every entry point that would otherwise compute on
    the GPT-2 block."""
    raise NotImplementedError(
        f"{what} is not supported for TransformerConfig(skeleton="
        f"'gated_hybrid'): the skeleton runs through forward, "
        f"prefill_into_blocks and decode_step_paged (the paged engine) "
        f"with weights and KV pool in the model's dtype")


def check_config(cfg):
    bad = []
    if cfg.n_layers % cfg.full_attn_interval or cfg.full_attn_interval < 2:
        bad.append(f"n_layers {cfg.n_layers} must be whole periods of "
                   f"full_attn_interval {cfg.full_attn_interval} >= 2")
    for name in ("rec_key_heads", "rec_value_heads", "rec_key_dim",
                 "rec_value_dim", "moe_experts", "moe_shared_ff"):
        if getattr(cfg, name) < 1:
            bad.append(f"{name} must be set")
    if cfg.rec_key_heads and cfg.rec_value_heads % cfg.rec_key_heads:
        bad.append("rec_value_heads must be a multiple of rec_key_heads")
    if cfg.rec_conv < 2:
        bad.append("rec_conv must be >= 2")
    rot = cfg.rotary_dim or cfg.head_dim
    if rot % 2 or rot > cfg.head_dim:
        bad.append(f"rotary_dim {rot} must be even and <= head_dim")
    first, count = held(cfg)
    if first < 0 or count < 1 or first + count > cfg.moe_experts:
        bad.append(f"moe_held {cfg.moe_held} outside moe_experts "
                   f"{cfg.moe_experts}")
    if not 1 <= cfg.moe_top_k <= max(cfg.moe_experts, 1):
        bad.append(f"moe_top_k {cfg.moe_top_k}")
    if cfg.use_ring_attention or cfg.use_flash_attention \
            or cfg.remat != "none" or cfg.dropout:
        bad.append("ring / flash attention, remat and dropout are not "
                   "wired for this skeleton")
    if bad:
        raise ValueError("gated_hybrid config: " + "; ".join(bad))


def held(cfg) -> tuple:
    """(first, count) of the experts whose weights this chip holds."""
    return tuple(cfg.moe_held) or (0, cfg.moe_experts)


def layer_counts(cfg) -> tuple:
    """(periods, delta-rule layers, full-attention layers)."""
    n = cfg.n_layers // cfg.full_attn_interval
    return n, n * (cfg.full_attn_interval - 1), n


def _rec_dims(cfg):
    Hk, Hv = cfg.rec_key_heads, cfg.rec_value_heads
    dk, dv = cfg.rec_key_dim, cfg.rec_value_dim
    return Hk, Hv, dk, dv, Hv // Hk, 2 * Hk * dk + Hv * dv


# -- parameters and state -----------------------------------------------------

def init_params(key: jax.Array, cfg, dtype=None):
    """Parameter pytree, layers stacked per period and per kind:
    ``periods.rec`` leaves lead with [periods, interval - 1], ``periods.
    full`` leaves with [periods]. Matrices in ``dtype`` (default
    float32); norms, router, ``A_log`` and ``dt_bias`` float32."""
    from paddle_tpu.parallel import moe
    dtype = dtype or jnp.float32
    D, V, F = cfg.d_model, cfg.vocab, cfg.d_ff
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Hk, Hv, dk, dv, r, ch = _rec_dims(cfg)
    Pn = cfg.n_layers // cfg.full_attn_interval
    R = cfg.full_attn_interval - 1
    s = 1.0 / math.sqrt(D)
    keys = iter(jax.random.split(key, 64))

    def nrm(shape, scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def experts(lead):
        n = 1
        for d in lead:
            n *= d
        ks = jax.random.split(next(keys), n)
        one = jax.vmap(lambda k: moe.dropless_init_params(
            k, D, F, cfg.moe_experts, held(cfg)[1], cfg.moe_shared_ff,
            dtype))(ks)
        return jax.tree_util.tree_map(
            lambda a: a.reshape(lead + a.shape[1:]), one)

    def norms(lead):
        return {"ln1": nrm(lead + (D,), 0.1, jnp.float32),
                "ln2": nrm(lead + (D,), 0.1, jnp.float32)}

    rec = {**norms((Pn, R)),
           "in_qkvz": nrm((Pn, R, D, 2 * Hk * dk + 2 * Hv * dv), s),
           "in_ba": nrm((Pn, R, D, 2 * Hv), s),
           "conv": nrm((Pn, R, ch, cfg.rec_conv), 0.5),
           "A_log": jnp.log(jax.random.uniform(
               next(keys), (Pn, R, Hv), jnp.float32, 0.02, 1.0)),
           "dt_bias": nrm((Pn, R, Hv), 0.5, jnp.float32),
           "norm": 1.0 + nrm((Pn, R, dv), 0.1, jnp.float32),
           "out": nrm((Pn, R, Hv * dv, D), 1.0 / math.sqrt(Hv * dv)),
           "moe": experts((Pn, R))}
    full = {**norms((Pn,)),
            "q": nrm((Pn, D, H * 2 * Dh), s),
            "k": nrm((Pn, D, Hkv * Dh), s),
            "v": nrm((Pn, D, Hkv * Dh), s),
            "q_norm": nrm((Pn, Dh), 0.1, jnp.float32),
            "k_norm": nrm((Pn, Dh), 0.1, jnp.float32),
            "o": nrm((Pn, H * Dh, D), 1.0 / math.sqrt(H * Dh)),
            "moe": experts((Pn,))}
    return {"embed": nrm((V, D), 1.0), "head": nrm((V, D), s),
            "ln_f": nrm((D,), 0.1, jnp.float32),
            "periods": {"rec": rec, "full": full}}


def init_block_pool(cfg, num_blocks: int, block_size: int, slots: int):
    """The engine's donated cache pytree: head-major K/V pages for the
    full-attention layers ONLY ([L_full, Hkv, M, Dh], ``transformer.
    POOL_LAYOUT``) and, per delta-rule layer and slot, the state matrix
    (float32) and the convolution's tail (model dtype)."""
    _, Lr, Lf = layer_counts(cfg)
    _, Hv, dk, dv, _, ch = _rec_dims(cfg)
    M = int(num_blocks) * int(block_size)
    kv = (Lf, cfg.kv_heads, M, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "rec_state": jnp.zeros((Lr, slots, Hv, dk, dv), jnp.float32),
            "rec_tail": jnp.zeros((Lr, slots, cfg.rec_conv - 1, ch),
                                  cfg.dtype)}


# -- pieces of the block ------------------------------------------------------

def _rms(x, w, eps, centred=True):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * ((1.0 + w) if centred else w)).astype(x.dtype)


def _rope_part(x, tabs, rot: int):
    """Rotary on the first ``rot`` dims of x [N, H, Dh] (halves paired),
    per-row tables [N, rot/2]."""
    from paddle_tpu.models import transformer
    if rot == x.shape[-1]:
        return transformer._rope_rows(x, tabs)
    return jnp.concatenate(
        [transformer._rope_rows(x[..., :rot], tabs), x[..., rot:]], axis=-1)


def _attn_mixer(w, h, cfg, tabs, attend):
    """Gated attention over rows h [N, D]; ``attend(q [N, H, Dh],
    k [N, Hkv, Dh], v [N, Hkv, Dh]) -> [N, H, Dh]`` is the adapter's:
    where the keys and values of earlier tokens come from."""
    N = h.shape[0]
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    rot = cfg.rotary_dim or Dh
    qg = (h @ w["q"].astype(h.dtype)).reshape(N, H, 2 * Dh)
    q, gate = qg[..., :Dh], qg[..., Dh:]
    k = (h @ w["k"].astype(h.dtype)).reshape(N, Hkv, Dh)
    v = (h @ w["v"].astype(h.dtype)).reshape(N, Hkv, Dh)
    q = _rope_part(_rms(q, w["q_norm"], cfg.norm_eps), tabs, rot)
    k = _rope_part(_rms(k, w["k_norm"], cfg.norm_eps), tabs, rot)
    a = attend(q, k, v).astype(jnp.float32) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))
    return a.reshape(N, H * Dh).astype(h.dtype) @ w["o"].astype(h.dtype)


def _rec_project(w, h, cfg):
    """h [..., D] -> (pre-convolution [q | k | v] [..., ch], z [..., Hv,
    dv], beta [..., Hv], g [..., Hv]); the projection's columns are
    grouped per key head: (q dk, k dk, v r*dv, z r*dv), and (b r, a r)."""
    Hk, Hv, dk, dv, r, _ = _rec_dims(cfg)
    lead = h.shape[:-1]
    p = (h @ w["in_qkvz"].astype(h.dtype)).reshape(
        lead + (Hk, 2 * dk + 2 * r * dv))
    mixed = jnp.concatenate(
        [p[..., :dk].reshape(lead + (Hk * dk,)),
         p[..., dk:2 * dk].reshape(lead + (Hk * dk,)),
         p[..., 2 * dk:2 * dk + r * dv].reshape(lead + (Hv * dv,))],
        axis=-1)
    z = p[..., 2 * dk + r * dv:].reshape(lead + (Hv, dv))
    ba = (h @ w["in_ba"].astype(h.dtype)).reshape(
        lead + (Hk, 2 * r)).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(lead + (Hv,)))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(lead + (Hv,)) + w["dt_bias"])
    return mixed, z, beta, g


def _rec_heads(u, cfg):
    """Convolved channels u [..., ch] -> float32 q, k [..., Hv, dk]
    (L2-normalised per head, q scaled by 1/sqrt(dk), each key head
    repeated for its value heads) and v [..., Hv, dv]."""
    Hk, Hv, dk, dv, r, _ = _rec_dims(cfg)
    lead = u.shape[:-1]
    u = u.astype(jnp.float32)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = l2(u[..., :Hk * dk].reshape(lead + (Hk, dk))) / math.sqrt(dk)
    k = l2(u[..., Hk * dk:2 * Hk * dk].reshape(lead + (Hk, dk)))
    v = u[..., 2 * Hk * dk:].reshape(lead + (Hv, dv))
    return jnp.repeat(q, r, axis=-2), jnp.repeat(k, r, axis=-2), v


def _rec_out(w, o, z, cfg, dtype):
    """Per-head RMSNorm (plain weight) of o [..., Hv, dv], gated by
    silu(z), then the output projection."""
    y = _rms(o, w["norm"], cfg.norm_eps, centred=False) \
        * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(y.shape[:-2] + (-1,)).astype(dtype)
    return y @ w["out"].astype(dtype)


def chunked_delta_rule(q, k, v, g, beta, S0):
    """The gated delta rule over a sequence, in sub-chunks of ``SUB``
    tokens. q, k [B, T, H, dk], v [B, T, H, dv], g, beta [B, T, H]
    (float32; T a multiple of SUB), S0 [B, H, dk, dv] ->
    (o [B, T, H, dv], S_T). A position with ``beta = 0, g = 0`` leaves
    the state as it is.

    Within a sub-chunk the rule's dependence of token i on tokens j < i
    is a unit lower-triangular system ``(I + A) U = beta V`` with
    ``A_ij = beta_i (k_i . k_j) exp(G_i - G_j)`` (G the running sum of
    g): its inverse is the product ``(I + B)(I + B^2)...(I + B^32)``,
    ``B = -A`` (B is nilpotent), six small matrix products. The state
    passes from sub-chunk to sub-chunk through a scan. Products are
    float32 at ``highest``: the inverse amplifies rounding."""
    B, T, H, dk = q.shape
    n = T // SUB

    def split(x):       # [B, T, H, ...] -> [n, B, H, SUB, ...]
        x = x.reshape((B, n, SUB, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                           # [n, B, H, SUB]
    i = jnp.arange(SUB)
    low = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(low, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                 # [.., SUB, SUB]
    kb = k * beta[..., None]

    def mm(a, b):
        return jnp.matmul(a, b, precision=HI)

    Bm = -mm(kb, jnp.swapaxes(k, -1, -2)) * decay \
        * (i[:, None] > i[None, :])
    eye = jnp.eye(SUB, dtype=jnp.float32)
    inv, pw = eye + Bm, Bm
    for _ in range(int(math.log2(SUB)) - 1):
        pw = mm(pw, pw)
        inv = mm(inv, eye + pw)
    u = mm(inv, v * beta[..., None])                     # [.., SUB, dv]
    wd = mm(inv, kb * jnp.exp(G)[..., None])             # [.., SUB, dk]
    qk = mm(q, jnp.swapaxes(k, -1, -2)) * decay
    qd = q * jnp.exp(G)[..., None]
    last = G[..., -1:]
    kd = k * jnp.exp(last - G)[..., None]

    def step(S, xs):
        u, wd, qk, qd, kd, last = xs
        vn = u - mm(wd, S)
        o = mm(qd, S) + mm(qk, vn)
        S = S * jnp.exp(last)[..., None] + mm(jnp.swapaxes(kd, -1, -2), vn)
        return S, o

    S, o = jax.lax.scan(step, S0, (u, wd, qk, qd, kd, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)     # [B, n, SUB, H, dv]
    return o.reshape(B, T, H, -1), S


def rec_sequence(w, h, S0, tail0, length, cfg):
    """The delta-rule mixer over a stretch of sequence: h [B, T, D],
    state S0 [B, Hv, dk, dv] and convolution tail0 [B, K-1, ch] in,
    ``length`` [B] valid tokens (the rest is padding and touches
    neither the state nor the tail) -> (out [B, T, D], S1, tail1)."""
    B, T, _ = h.shape
    K = cfg.rec_conv
    mixed, z, beta, g = _rec_project(w, h, cfg)
    win = jnp.concatenate([tail0.astype(mixed.dtype), mixed], axis=1)
    cw = w["conv"].astype(jnp.float32)                   # [ch, K]
    u = sum(win[:, j:j + T].astype(jnp.float32) * cw[:, j]
            for j in range(K))
    u = jax.nn.silu(u)
    # the last K-1 VALID inputs: row ``length`` of the window onward
    tail1 = jax.vmap(lambda wn, n: jax.lax.dynamic_slice_in_dim(
        wn, n, K - 1, axis=0))(win, length).astype(tail0.dtype)
    valid = jnp.arange(T)[None, :] < length[:, None]
    beta = jnp.where(valid[..., None], beta, 0.0)
    g = jnp.where(valid[..., None], g, 0.0)
    q, k, v = _rec_heads(u, cfg)
    pad = -T % SUB
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad))
                                    + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    o, S1 = chunked_delta_rule(q, k, v, g, beta, S0)
    return _rec_out(w, o[:, :T], z, cfg, h.dtype), S1, tail1


def rec_step(w, h, S, tail, cfg):
    """The delta-rule mixer for one token a row: h [B, D], S [B, Hv, dk,
    dv], tail [B, K-1, ch] -> (out [B, D], S', tail')."""
    mixed, z, beta, g = _rec_project(w, h, cfg)
    win = jnp.concatenate([tail.astype(mixed.dtype), mixed[:, None]],
                          axis=1)                        # [B, K, ch]
    u = jax.nn.silu(jnp.sum(win.astype(jnp.float32)
                            * w["conv"].astype(jnp.float32).T, axis=1))
    q, k, v = _rec_heads(u, cfg)
    # products and sums over the state, not matrix products: one row
    # against a [dk, dv] matrix per (slot, head) is a pass over the
    # state on the vector unit, exact in float32; as a batched matvec
    # it would load a tile of weights per row and precision pass
    S = S * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.sum(S * k[..., :, None], axis=-2))
    S = S + k[..., :, None] * d[..., None, :]
    o = jnp.sum(S * q[..., :, None], axis=-2)
    return _rec_out(w, o, z, cfg, h.dtype), S, win[:, 1:].astype(tail.dtype)


def _run_layers(params, x, cfg, carry, attn_fn, rec_fn, valid):
    """The layer loop over rows x [N, D]: a scan over periods, inside
    it a scan over the period's delta-rule layers, then its
    full-attention layer. ``attn_fn(carry, l_full, w, h)`` and
    ``rec_fn(carry, l_rec, w, h)`` are the adapter's two mixers,
    ``-> (out [N, D], carry)``; ``carry`` is whatever state they keep.
    Returns (x, carry, stats int32 [3] = (expert assignments kept here,
    distinct held experts hit summed over layers, expert-layer calls))."""
    from paddle_tpu.parallel import moe
    R = cfg.full_attn_interval - 1
    eps = cfg.norm_eps
    # the expert weights stay OUT of the scans' xs: they are handed to
    # the expert layer as whole stacks with the layer's number (see
    # ``moe_dropless``); everything else rides the scans
    stacks = {kind: {n: params["periods"][kind]["moe"][n] for n in _EXPERT}
              for kind in ("rec", "full")}
    light = {kind: dict(tree, moe={n: a for n, a in tree["moe"].items()
                                   if n not in _EXPERT})
             for kind, tree in params["periods"].items()}

    def experts(kind, layer, w, x, stats):
        out, st = moe.moe_dropless(
            dict(w["moe"], **stacks[kind]), _rms(x, w["ln2"], eps),
            top_k=cfg.moe_top_k, held=held(cfg), valid=valid, layer=layer)
        return x + out, stats + jnp.concatenate(
            [st, jnp.ones((1,), jnp.int32)])

    def period(c, scanned):
        w, p = scanned

        def rec_layer(c, scanned):
            x, carry, stats = c
            wr, j = scanned
            out, carry = rec_fn(carry, p * R + j, wr,
                                _rms(x, wr["ln1"], eps))
            x, stats = experts("rec", p * R + j, wr, x + out, stats)
            return (x, carry, stats), None

        c, _ = jax.lax.scan(rec_layer, c,
                            (w["rec"], jnp.arange(R, dtype=jnp.int32)))
        x, carry, stats = c
        wf = w["full"]
        out, carry = attn_fn(carry, p, wf, _rms(x, wf["ln1"], eps))
        x, stats = experts("full", p, wf, x + out, stats)
        return (x, carry, stats), None

    Pn = cfg.n_layers // cfg.full_attn_interval
    (x, carry, stats), _ = jax.lax.scan(
        period, (x, carry, jnp.zeros((3,), jnp.int32)),
        (light, jnp.arange(Pn, dtype=jnp.int32)))
    return x, carry, stats


def _head(params, x, cfg, centred=True):
    """Final norm and the untied head -> float32 logits; operands in
    the model dtype, accumulated in float32."""
    x = _rms(x, params["ln_f"], cfg.norm_eps, centred)
    return jnp.einsum("...d,vd->...v", x, params["head"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)


def _rope_tabs(cfg, positions):
    from paddle_tpu.models import transformer
    return transformer._rope_tables(
        positions, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta)


# -- the three programs -------------------------------------------------------

def forward(params, tokens, cfg, lengths=None):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, no state in or out."""
    from paddle_tpu.parallel import ring
    _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
    B, T = tokens.shape
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    _, Hv, dk, dv, _, ch = _rec_dims(cfg)
    tabs = _rope_tabs(cfg, jnp.tile(jnp.arange(T, dtype=jnp.int32), B))
    full = jnp.full((B,), T, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)

    def attn_fn(carry, lf, w, h):
        def attend(q, k, v):
            return ring.full_attention(
                q.reshape(B, T, H, Dh), k.reshape(B, T, Hkv, Dh),
                v.reshape(B, T, Hkv, Dh), causal=True,
                lengths=lengths).reshape(B * T, H, Dh)
        return _attn_mixer(w, h, cfg, tabs, attend), carry

    def rec_fn(carry, lr, w, h):
        out, _, _ = rec_sequence(
            w, h.reshape(B, T, -1),
            jnp.zeros((B, Hv, dk, dv), jnp.float32),
            jnp.zeros((B, cfg.rec_conv - 1, ch), h.dtype), full, cfg)
        return out.reshape(B * T, -1), carry

    valid = None if lengths is None else \
        (jnp.arange(T)[None, :] < full[:, None]).reshape(B * T)
    x = _embed(params, tokens, cfg).reshape(B * T, -1)
    x, _, _ = _run_layers(params, x, cfg, (), attn_fn, rec_fn, valid)
    return _head(params, x, cfg).reshape(B, T, -1)


def _flat_pool(cache):
    """K/V tables as flat rows [L_full * Hkv * M, Dh] (a bitcast: layer
    and head ride in the row index, the scatter's and the gather's
    major axis), the recurrent rows as they are."""
    out = dict(cache)
    for n in ("k", "v"):
        out[n] = cache[n].reshape((-1,) + cache[n].shape[3:])
    return out


def _unflat_pool(carry, cache):
    return {n: t.reshape(cache[n].shape) for n, t in carry.items()}


def prefill_chunk(params, cache, tokens, length, pages, slot, cfg, *,
                  block_size: int, return_stats=False):
    """One chunk of one request (``transformer.prefill_into_blocks``'s
    contract: tokens [1, C] right-padded, ``length`` valid, ``pages``
    [P] covering context + chunk, the context's length static in the
    shapes) for ``slot``'s recurrent rows. A chunk at offset 0 starts
    from a zero state and tail whatever the slot held: a new request
    never sees its predecessor's. Padded positions write no page and
    touch neither state nor tail."""
    from paddle_tpu.models import transformer
    for site in ("attention", "span_write"):
        _pallas_policy.note_path(site, _pallas_policy.PATH_XLA)
    C = tokens.shape[1]
    bs = int(block_size)
    P = pages.shape[0]
    pc = -(-C // bs)
    S = (P - pc) * bs                                    # static context
    if S < 0:
        raise ValueError(f"pages vector ({P}) shorter than the chunk's "
                         f"own span ({pc} pages for C={C})")
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    g = H // Hkv
    M = cache["k"].shape[2]
    NB = M // bs
    rows_total = cache["k"].shape[0] * Hkv * M
    length = jnp.asarray(length, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    gpos = S + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C, dtype=jnp.int32) < length
    tabs = _rope_tabs(cfg, gpos)
    heads = jnp.arange(Hkv, dtype=jnp.int32)
    # where each chunk position lands within one (layer, head) span;
    # padding aims past the table's end and is dropped
    wpos = jnp.take(pages, gpos // bs) * bs + gpos % bs
    attend_mask = jnp.concatenate(
        [jnp.ones((C, S), bool), jnp.tril(jnp.ones((C, C), bool))], axis=1)
    zero = jnp.zeros((), jnp.int32)

    def attn_fn(carry, lf, w, h):
        groups = lf * Hkv + heads
        carry = dict(carry)

        def attend(q, k, v):
            new = {"k": k, "v": v}
            seen = {}
            for n in ("k", "v"):
                ctx = transformer._gather_pages(
                    carry[n], groups, pages[:P - pc], bs, NB)  # [Hkv,S,Dh]
                seen[n] = jnp.concatenate(
                    [jnp.swapaxes(ctx, 0, 1).astype(jnp.float32),
                     new[n].astype(jnp.float32)], axis=0)     # [S+C,Hkv,Dh]
            q32 = q.reshape(C, Hkv, g, Dh).astype(jnp.float32)
            s = jnp.einsum("ckgd,tkd->ckgt", q32, seen["k"]) \
                / math.sqrt(Dh)
            s = jnp.where(attend_mask[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("ckgt,tkd->ckgd", p, seen["v"])
            rows = jnp.where(valid[None, :],
                             (groups * M)[:, None] + wpos[None, :],
                             rows_total).reshape(Hkv * C)
            for n in ("k", "v"):
                carry[n] = carry[n].at[rows].set(
                    jnp.swapaxes(new[n], 0, 1).reshape(
                        Hkv * C, Dh).astype(carry[n].dtype), mode="drop")
            return a.reshape(C, H, Dh)

        return _attn_mixer(w, h, cfg, tabs, attend), carry

    def rec_fn(carry, lr, w, h):
        st, tl = carry["rec_state"], carry["rec_tail"]
        at = (lr, slot, zero, zero, zero)
        if S:
            S0 = jax.lax.dynamic_slice(st, at, (1, 1) + st.shape[2:])[0]
            t0 = jax.lax.dynamic_slice(tl, at[:4], (1, 1) + tl.shape[2:])[0]
        else:
            S0 = jnp.zeros((1,) + st.shape[2:], st.dtype)
            t0 = jnp.zeros((1,) + tl.shape[2:], tl.dtype)
        out, S1, t1 = rec_sequence(w, h[None], S0, t0, length[None], cfg)
        carry = dict(carry,
                     rec_state=jax.lax.dynamic_update_slice(st, S1[None], at),
                     rec_tail=jax.lax.dynamic_update_slice(
                         tl, t1[None], at[:4]))
        return out[0], carry

    x = _embed(params, tokens[0], cfg)
    x, carry, stats = _run_layers(params, x, cfg, _flat_pool(cache),
                                  attn_fn, rec_fn, valid)
    x = jnp.take(x, jnp.reshape(jnp.maximum(length - 1, 0), (1,)), axis=0)
    out = (_head(params, x, cfg), _unflat_pool(carry, cache))
    return out + (stats,) if return_stats else out


def decode_step(params, cache, tokens, pos, active, pages, cfg, *,
                block_size: int, return_stats=False):
    """One token a slot (``transformer.decode_step_paged``'s contract;
    the decode batch IS the slots: row b owns recurrent row b). Inactive
    rows write nothing: no page, no state, no tail."""
    from paddle_tpu.models import transformer
    _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
    B = tokens.shape[0]
    bs = int(block_size)
    T = pages.shape[1] * bs
    H, Hkv, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    g = H // Hkv
    M = cache["k"].shape[2]
    NB = M // bs
    rows_total = cache["k"].shape[0] * Hkv * M
    if cache["rec_state"].shape[1] != B:
        raise ValueError(f"decode batch {B} must be the pool's "
                         f"{cache['rec_state'].shape[1]} slots")
    pos = jnp.asarray(pos, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    tabs = _rope_tabs(cfg, pos)
    wpos = jnp.take_along_axis(pages, (pos // bs)[:, None],
                               axis=1)[:, 0] * bs + pos % bs
    heads = jnp.arange(Hkv, dtype=jnp.int32)
    attend_mask = jnp.arange(T, dtype=jnp.int32)[None, :] <= pos[:, None]

    def attn_fn(carry, lf, w, h):
        groups = lf * Hkv + heads
        carry = dict(carry)

        def attend(q, k, v):
            rows = jnp.where(active[None, :],
                             (groups * M)[:, None] + wpos[None, :],
                             rows_total).reshape(Hkv * B)
            seen = {}
            for n, new in (("k", k), ("v", v)):
                carry[n] = carry[n].at[rows].set(
                    jnp.swapaxes(new, 0, 1).reshape(
                        Hkv * B, Dh).astype(carry[n].dtype), mode="drop")
                view = transformer._gather_pages(
                    carry[n], groups[:, None], pages, bs, NB)
                seen[n] = jnp.transpose(view, (1, 2, 0, 3))  # [B,T,Hkv,Dh]
            # the barrier keeps the widening beside its consumer (see
            # transformer.decode_step_paged)
            seen = jax.lax.optimization_barrier(seen)
            q32 = q.reshape(B, Hkv, g, Dh).astype(jnp.float32)
            s = jnp.einsum("bkgd,btkd->bkgt", q32,
                           seen["k"].astype(jnp.float32)) / math.sqrt(Dh)
            s = jnp.where(attend_mask[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("bkgt,btkd->bkgd", p,
                           seen["v"].astype(jnp.float32))
            return a.reshape(B, H, Dh)

        return _attn_mixer(w, h, cfg, tabs, attend), carry

    def rec_fn(carry, lr, w, h):
        st, tl = carry["rec_state"], carry["rec_tail"]
        S0 = jax.lax.dynamic_index_in_dim(st, lr, 0, keepdims=False)
        t0 = jax.lax.dynamic_index_in_dim(tl, lr, 0, keepdims=False)
        out, S1, t1 = rec_step(w, h, S0, t0, cfg)
        S1 = jnp.where(active[:, None, None, None], S1, S0)
        t1 = jnp.where(active[:, None, None], t1, t0)
        carry = dict(
            carry,
            rec_state=jax.lax.dynamic_update_index_in_dim(st, S1, lr, 0),
            rec_tail=jax.lax.dynamic_update_index_in_dim(tl, t1, lr, 0))
        return out, carry

    x = _embed(params, tokens, cfg)
    x, carry, stats = _run_layers(params, x, cfg, _flat_pool(cache),
                                  attn_fn, rec_fn, active)
    out = (_head(params, x, cfg), _unflat_pool(carry, cache))
    return out + (stats,) if return_stats else out

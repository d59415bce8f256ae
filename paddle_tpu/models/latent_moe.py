"""The latent-attention block: ``TransformerConfig(skeleton="latent_moe")``.

A decoder whose attention keeps, per token and layer, ONE low-rank row
for all heads (multi-head latent attention) and whose feed-forward
halves are a dense SwiGLU in the first ``dense_layers`` layers and a
dropless expert layer (``parallel/moe.moe_dropless`` under the
sigmoid-and-bias routing rule, an ungated shared expert) in the rest.
Norms are plain RMSNorms, the maps carry no bias, the head is untied.

    x <- x + MLA(N(x));  x <- x + FFN(N(x))
    N(x) = x / sqrt(mean(x^2) + eps) * w                        (float32)

Latent attention (H heads; ranks rq, rkv; head widths dn, dr, dv):
    c_q = N_rq(x W_qa);  [q_nope_i (dn) | q_rope_i (dr)] = c_q W_qb
    [c_kv (rkv) | k_rope (dr)] = x W_kva;  c_kv <- N_rkv(c_kv)
    k_rope <- RoPE(k_rope)  (one for all heads);  q_rope_i <- RoPE(q_rope_i)
    [k_nope_i (dn) | v_i (dv)] = c_kv W_kvb,i
    s_ij = (q_nope_i . k_nope_ij + q_rope_i . k_rope_j) / sqrt(dn + dr)
    o_i = softmax_causal(s_i) v_i;  out = [o_1 .. o_H] W_o
  the cache row per token and layer: [c_kv | k_rope] after norm and
  rotation, rkv + dr values (``init_block_pool``: ``{"latent": [L, 1,
  M, W]}``, a page table like any other with ONE "head"; W is rkv + dr
  rounded up to whole lanes, see ``pool_width``).

Two forms of the same attention (:func:`attend_absorbed`,
:func:`attend_upproject`); which program takes which is reckoned at
:func:`upproject_wins`.

ONE definition of the block serves the three programs that run it
(``forward``, ``prefill_chunk``, ``decode_step``), through an adapter
each that says where the rows of earlier tokens come from, as in
``models/gated_hybrid.py``, whose norm, rotary, head and embedding
helpers this module uses. A multi-token-prediction module of depth
``mtp_layers`` (``forward_mtp``) is modelled for the whole-sequence
program only: no served path runs it.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.models.gated_hybrid import (_EXPERT, _embed, _head, _rms,
                                            held)
from paddle_tpu.ops.pallas import policy as _pallas_policy

SLOT_STATE = False      # pages are all the state a position depends on
F32 = jnp.float32


def _norm(x, w, cfg):
    """The plain RMSNorm (``gated_hybrid._rms`` multiplies by 1 + w)."""
    return _rms(x, w, cfg.norm_eps, centred=False)


def refuse(what: str):
    """The one refusal of everything the latent skeleton does not
    run."""
    raise NotImplementedError(
        f"{what} is not supported for TransformerConfig(skeleton="
        f"'latent_moe'): the skeleton runs through forward, "
        f"prefill_into_blocks and decode_step_paged (the paged engine) "
        f"with weights and latent pool in the model's dtype")


def check_config(cfg):
    bad = []
    for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_dim",
                 "qk_rope_dim", "v_head_dim", "moe_experts",
                 "moe_shared_ff"):
        if getattr(cfg, name) < 1:
            bad.append(f"{name} must be set")
    if cfg.qk_rope_dim % 2:
        bad.append(f"qk_rope_dim {cfg.qk_rope_dim} must be even")
    if not 0 <= cfg.dense_layers < cfg.n_layers:
        bad.append(f"dense_layers {cfg.dense_layers} must leave an "
                   f"expert layer of n_layers {cfg.n_layers}")
    if cfg.dense_layers and cfg.dense_ff < 1:
        bad.append("dense_ff must be set with dense_layers")
    if cfg.n_kv_heads not in (0, cfg.n_heads):
        bad.append("every head has its own keys and values (n_kv_heads)")
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
        raise ValueError("latent_moe config: " + "; ".join(bad))


def row_width(cfg) -> int:
    """Values of one cache row: the normed latent and the rotated key."""
    return cfg.kv_lora_rank + cfg.qk_rope_dim


def pool_width(cfg) -> int:
    """Values a row takes in the pool: ``row_width`` rounded up to whole
    128-value lanes, the tail zero. A TPU tiles an array's minor dim in
    128 lanes, so a row-major row of 576 occupies 640 whatever its shape
    says; told 576, the compiler instead stores the table POSITION-minor
    and re-lays the whole pool out row-major on the way into every
    program and back on the way out (two copies of 3.96 GB a decode
    step, seen in the deviceless v5e compile). Stating the lanes keeps
    the table row-major and the page gather a gather of whole rows."""
    return -(-row_width(cfg) // 128) * 128


# -- parameters and pool ------------------------------------------------------

def init_params(key: jax.Array, cfg, dtype=None):
    """Parameter pytree, layers stacked per kind: ``dense`` leaves lead
    with [dense_layers] (absent without any), ``sparse`` leaves with
    [n_layers - dense_layers], ``mtp`` (with ``mtp_layers``) holds one
    more stack of the sparse kind and the module's own maps. Matrices
    in ``dtype`` (default float32); norms, router and its bias
    float32."""
    import math
    from paddle_tpu.parallel import moe
    dtype = dtype or F32
    D, V, H = cfg.d_model, cfg.vocab, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    s = 1.0 / math.sqrt(D)
    keys = iter(jax.random.split(key, 64))

    def nrm(shape, scale, dt=dtype, offset=0.0):
        return (jax.random.normal(next(keys), shape, F32)
                * scale + offset).astype(dt)

    def mixer(n):
        return {"ln1": nrm((n, D), 0.1, F32, 1.0),
                "ln2": nrm((n, D), 0.1, F32, 1.0),
                "q_a": nrm((n, D, rq), s),
                "q_a_norm": nrm((n, rq), 0.1, F32, 1.0),
                "q_b": nrm((n, rq, H * (dn + dr)), 1.0 / math.sqrt(rq)),
                "kv_a": nrm((n, D, rkv + dr), s),
                "kv_a_norm": nrm((n, rkv), 0.1, F32, 1.0),
                "kv_b": nrm((n, rkv, H * (dn + dv)), 1.0 / math.sqrt(rkv)),
                "o": nrm((n, H * dv, D), 1.0 / math.sqrt(H * dv))}

    def sparse(n):
        one = jax.vmap(lambda k: moe.dropless_init_params(
            k, D, cfg.d_ff, cfg.moe_experts, held(cfg)[1],
            cfg.moe_shared_ff, dtype, route="sigmoid_bias"))(
            jax.random.split(next(keys), n))
        return dict(mixer(n), moe=one)

    Ld, Fd = cfg.dense_layers, cfg.dense_ff
    out = {"embed": nrm((V, D), 1.0), "head": nrm((V, D), s),
           "ln_f": nrm((D,), 0.1, F32, 1.0),
           "sparse": sparse(cfg.n_layers - Ld)}
    if Ld:
        out["dense"] = dict(mixer(Ld), gate=nrm((Ld, D, Fd), s),
                            up=nrm((Ld, D, Fd), s),
                            down=nrm((Ld, Fd, D), 1.0 / math.sqrt(Fd)))
    if cfg.mtp_layers:
        out["mtp"] = {"enorm": nrm((D,), 0.1, F32, 1.0),
                      "hnorm": nrm((D,), 0.1, F32, 1.0),
                      "eh_proj": nrm((2 * D, D), 1.0 / math.sqrt(2 * D)),
                      "block": sparse(cfg.mtp_layers),
                      "norm": nrm((D,), 0.1, F32, 1.0)}
    return out


def init_block_pool(cfg, num_blocks: int, block_size: int, slots=None):
    """The engine's donated cache pytree: ONE table of latent rows,
    head-major like every pool (``transformer.POOL_LAYOUT``) with a
    single "head": ``{"latent": [L, 1, M, pool_width]}``. Nothing is
    kept per slot."""
    M = int(num_blocks) * int(block_size)
    return {"latent": jnp.zeros((cfg.n_layers, 1, M, pool_width(cfg)),
                                cfg.dtype)}


# -- the two forms of the attention -------------------------------------------

def upproject_wins(cfg, queries: int) -> bool:
    """Whether a program with ``queries`` query rows a sequence should
    up-project the cached rows to per-head keys and values rather than
    absorb the up-projection into the query and the output. By count,
    per cached row and layer (2 FLOP a multiply-add, H heads):

        up-projecting   2 rkv H (dn + dv)  once, whatever the queries,
                        then 2 H (dn + dr + dv) a query
        absorbed        2 H (rkv + dr + rkv) a query

    so up-projecting wins where ``queries (2 rkv - dn - dv) > rkv (dn +
    dv)``. At rkv 512, dn 192, dr 64, dv 256, H 20: 9.18 MFLOP a cached
    row + 20,480 a query against 43,520 a query: from 399 queries on. A
    1024-token chunk against 11k cached rows up-projects (368 against
    557 GFLOP a layer; the up-projected rows are a temporary of 11k x
    8960 x 2 B = 0.2 GB a layer), a 256-token chunk absorbs (139
    against 174), and decode, one query a slot, absorbs: it is bound by
    the bytes of the latent rows, 576 values a token where the
    up-projected keys and values are 8960."""
    rkv, dn, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.v_head_dim
    return queries * (2 * rkv - dn - dv) > rkv * (dn + dv)


def _kv_b(w, cfg, dtype):
    """W_kvb as per-head key and value maps [rkv, H, dn], [rkv, H, dv]."""
    kvb = w["kv_b"].astype(dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    return kvb[..., :cfg.qk_nope_dim], kvb[..., cfg.qk_nope_dim:]


def _softmax(s, mask, cfg):
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    return jax.nn.softmax(
        jnp.where(mask[:, None], s * scale, -1e30), axis=-1)


def attend_absorbed(w, cfg, q_nope, q_rope, rows, mask):
    """Attention over the latent rows themselves: q_nope [B, N, H, dn],
    q_rope [B, N, H, dr], rows [B, T, W >= rkv + dr] (pool rows, the
    tail past rkv + dr zero), mask [B, N, T] -> [B, N, H, dv]. The key
    up-projection moves onto the query (q~_i = W_kvb,i^K q_nope_i, rkv
    wide) and the value up-projection onto the output (o_i = W_kvb,i^V
    (softmax(s_i) c_kv)): per head the same sums in another order."""
    rkv = cfg.kv_lora_rank
    dt = rows.dtype
    wk, wv = _kv_b(w, cfg, dt)
    # (a product that is rounded to the model dtype right away asks
    # for that dtype: the unit accumulates in float32 either way)
    qt = jnp.einsum("bnhd,rhd->bnhr", q_nope.astype(dt), wk)
    tail = jnp.zeros(qt.shape[:-1] + (rows.shape[-1] - row_width(cfg),), dt)
    s = jnp.einsum("bnhr,btr->bhnt",
                   jnp.concatenate([qt, q_rope.astype(dt), tail], axis=-1),
                   rows, preferred_element_type=F32)
    p = _softmax(s, mask, cfg)
    u = jnp.einsum("bhnt,btr->bnhr", p.astype(dt), rows[..., :rkv])
    return jnp.einsum("bnhr,rhd->bnhd", u, wv, preferred_element_type=F32)


def attend_upproject(w, cfg, q_nope, q_rope, rows, mask):
    """The published form over the same arguments: every row's keys and
    values up-projected ([B, T, H, dn + dv], a temporary), then plain
    attention per head with the one rotary key shared by all heads."""
    rkv = cfg.kv_lora_rank
    dt = rows.dtype
    wk, wv = _kv_b(w, cfg, dt)
    k_nope = jnp.einsum("btr,rhd->bthd", rows[..., :rkv], wk)
    v = jnp.einsum("btr,rhd->bthd", rows[..., :rkv], wv)
    s = jnp.einsum("bnhd,bthd->bhnt", q_nope.astype(dt), k_nope,
                   preferred_element_type=F32) \
        + jnp.einsum("bnhd,btd->bhnt", q_rope.astype(dt),
                     rows[..., rkv:row_width(cfg)],
                     preferred_element_type=F32)
    p = _softmax(s, mask, cfg)
    return jnp.einsum("bhnt,bthd->bnhd", p.astype(dt), v,
                      preferred_element_type=F32)


# -- the block ----------------------------------------------------------------

def _rope_tabs(cfg, positions):
    from paddle_tpu.models import transformer
    return transformer._rope_tables(positions, cfg.qk_rope_dim,
                                    cfg.rope_theta)


def _project(w, h, cfg, tabs):
    """Rows h [N, D] -> (q_nope [N, H, dn], q_rope [N, H, dr] rotated,
    row [N, rkv + dr]: the cache row, normed latent and rotated key)."""
    from paddle_tpu.models import transformer
    N = h.shape[0]
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    cq = _norm(h @ w["q_a"].astype(h.dtype), w["q_a_norm"], cfg)
    q = (cq @ w["q_b"].astype(h.dtype)).reshape(N, cfg.n_heads, -1)
    kv = h @ w["kv_a"].astype(h.dtype)
    row = jnp.concatenate(
        [_norm(kv[:, :rkv], w["kv_a_norm"], cfg),
         transformer._rope_rows(kv[:, None, rkv:], tabs)[:, 0]], axis=-1)
    return q[..., :dn], transformer._rope_rows(q[..., dn:], tabs), row


def _attend(w, cfg, q_nope, q_rope, rows, mask, upproject: bool, dtype):
    """The attention of B sequences and the output map: q_nope, q_rope
    [B * N, H, .] (of :func:`_project`), rows [B, T, >= rkv + dr] with
    mask [B, N, T] -> [B * N, D]; ``upproject`` picks the form."""
    n = q_nope.shape[0]
    seq = (rows.shape[0], n // rows.shape[0])
    o = (attend_upproject if upproject else attend_absorbed)(
        w, cfg, q_nope.reshape(seq + q_nope.shape[1:]),
        q_rope.reshape(seq + q_rope.shape[1:]), rows, mask)
    return o.reshape(n, -1).astype(dtype) @ w["o"].astype(dtype)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(x.dtype))
            * (x @ up.astype(x.dtype))) @ down.astype(x.dtype)


def _sparse_layers(tree, x, cfg, carry, attn_fn, valid, first: int):
    """A scan over a stack of expert layers (``tree``: the model's
    ``sparse`` stack or the prediction module's); ``first`` numbers its
    first layer for ``attn_fn``. -> (x, carry, stats int32 [3])."""
    from paddle_tpu.parallel import moe
    # the expert stacks stay OUT of the scan's xs (``moe_dropless``)
    stacks = {n: tree["moe"][n] for n in _EXPERT}
    light = dict(tree, moe={n: a for n, a in tree["moe"].items()
                            if n not in _EXPERT})
    route = moe.route_sigmoid_bias(cfg.moe_route_scale)

    def layer(c, scanned):
        x, carry, stats = c
        w, j = scanned
        out, carry = attn_fn(carry, first + j, w, _norm(x, w["ln1"], cfg))
        x = x + out
        out, st = moe.moe_dropless(
            dict(w["moe"], **stacks), _norm(x, w["ln2"], cfg),
            top_k=cfg.moe_top_k, held=held(cfg), valid=valid, layer=j,
            route=route)
        return (x + out, carry, stats + jnp.concatenate(
            [st, jnp.ones((1,), jnp.int32)])), None

    n = tree["ln1"].shape[0]
    (x, carry, stats), _ = jax.lax.scan(
        layer, (x, carry, jnp.zeros((3,), jnp.int32)),
        (light, jnp.arange(n, dtype=jnp.int32)))
    return x, carry, stats


def _run_layers(params, x, cfg, carry, attn_fn, valid):
    """The layer loop over rows x [N, D]: a scan over the leading dense
    layers, then one over the expert layers. ``attn_fn(carry, layer,
    w, h) -> (out [N, D], carry)`` is the adapter's mixer. Returns (x,
    carry, stats int32 [3] = (expert assignments kept here, distinct
    held experts hit summed over layers, expert-layer calls))."""
    Ld = cfg.dense_layers

    def dense_layer(c, scanned):
        x, carry = c
        w, l = scanned
        out, carry = attn_fn(carry, l, w, _norm(x, w["ln1"], cfg))
        x = x + out
        return (x + _swiglu(_norm(x, w["ln2"], cfg), w["gate"], w["up"],
                            w["down"]), carry), None

    if Ld:
        (x, carry), _ = jax.lax.scan(
            dense_layer, (x, carry),
            (params["dense"], jnp.arange(Ld, dtype=jnp.int32)))
    return _sparse_layers(params["sparse"], x, cfg, carry, attn_fn, valid,
                          Ld)


# -- the three programs -------------------------------------------------------

def _whole_sequence(cfg, B: int, T: int, lengths):
    """The whole-sequence adapter: (the mixer over B x T rows, every
    position up-projected, nothing cached; which rows are valid)."""
    tabs = _rope_tabs(cfg, jnp.tile(jnp.arange(T, dtype=jnp.int32), B))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    valid = None
    if lengths is not None:
        live = jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]
        mask = mask & live[:, None, :]
        valid = live.reshape(B * T)

    def attn_fn(carry, l, w, h):
        q_nope, q_rope, row = _project(w, h, cfg, tabs)
        return _attend(w, cfg, q_nope, q_rope, row.reshape(B, T, -1), mask,
                       True, h.dtype), carry

    return attn_fn, valid


def forward(params, tokens, cfg, lengths=None):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, nothing cached, the up-projecting attention."""
    return _forward(params, tokens, cfg, lengths)[0]


def _forward(params, tokens, cfg, lengths):
    _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
    B, T = tokens.shape
    attn_fn, valid = _whole_sequence(cfg, B, T, lengths)
    x = _embed(params, tokens, cfg).reshape(B * T, -1)
    x, _, _ = _run_layers(params, x, cfg, (), attn_fn, valid)
    return (_head(params, x, cfg, centred=False).reshape(B, T, -1),
            x.reshape(B, T, -1))


def forward_mtp(params, tokens, cfg):
    """tokens [B, T] -> (logits [B, T, vocab], prediction-module logits
    [B, T - 1, vocab]: row i, from the last layer's output at position i
    (before the final norm) and the embedding of token i + 1, scores
    token i + 2). One module (``mtp_layers`` is its block's depth),
    sharing the model's embedding and head:

        h' = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)] -> the block (at
        positions 0 .. T-2) -> N_out -> the head"""
    if not cfg.mtp_layers:
        raise ValueError("forward_mtp needs cfg.mtp_layers >= 1")
    logits, h = _forward(params, tokens, cfg, None)
    m = params["mtp"]
    B, T = tokens.shape
    e = _embed(params, tokens[:, 1:], cfg)
    x = jnp.concatenate([_norm(e, m["enorm"], cfg),
                         _norm(h[:, :-1], m["hnorm"], cfg)],
                        axis=-1) @ m["eh_proj"].astype(e.dtype)
    attn_fn, _ = _whole_sequence(cfg, B, T - 1, None)
    x, _, _ = _sparse_layers(m["block"], x.reshape(B * (T - 1), -1), cfg,
                             (), attn_fn, None, 0)
    return logits, _head({"ln_f": m["norm"], "head": params["head"]}, x,
                         cfg, centred=False).reshape(B, T - 1, -1)


def _paged_mixer(cfg, cache, tabs, write_rows, seen, mask, B: int):
    """The mixer of the two paged programs over ``carry`` = the latent
    table as flat rows [L * M, W]: this call's rows are written at
    ``write_rows`` [B * N] within the layer's span (-1 drops the row:
    padding, an inactive slot), then ``seen(table, layer, row) -> [B,
    T, W]`` gathers what the B sequences attend over. The form is
    :func:`upproject_wins` of the queries a sequence."""
    M, W = cache["latent"].shape[2:]
    up = upproject_wins(cfg, write_rows.shape[0] // B)

    def attn_fn(table, l, w, h):
        q_nope, q_rope, row = _project(w, h, cfg, tabs)
        row = jnp.pad(row.astype(table.dtype),
                      ((0, 0), (0, W - row.shape[1])))
        table = table.at[jnp.where(write_rows < 0, table.shape[0],
                                   l * M + write_rows)].set(row,
                                                            mode="drop")
        return _attend(w, cfg, q_nope, q_rope, seen(table, l, row), mask,
                       up, h.dtype), table

    return attn_fn


def prefill_chunk(params, cache, tokens, length, pages, cfg, *,
                  block_size: int, return_stats=False):
    """One chunk of one request (``transformer.prefill_into_blocks``'s
    contract: tokens [1, C] right-padded, ``length`` valid, ``pages``
    [P] covering context + chunk, the context's length static in the
    shapes). The chunk attends over the slot's cached latent rows and
    its own; which form runs is :func:`upproject_wins` of C. Padded
    positions write no page."""
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
    shape = cache["latent"].shape
    NB = shape[2] // bs
    length = jnp.asarray(length, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    gpos = S + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C, dtype=jnp.int32) < length
    wpos = jnp.where(valid, jnp.take(pages, gpos // bs) * bs + gpos % bs,
                     -1)
    mask = jnp.concatenate(
        [jnp.ones((C, S), bool), jnp.tril(jnp.ones((C, C), bool))],
        axis=1)[None]

    def seen(table, l, row):
        # the chunk's own rows as computed, not as stored and re-read
        ctx = transformer._gather_pages(table, l[None], pages[:P - pc], bs,
                                        NB)                   # [1, S, R]
        return jnp.concatenate([ctx, row[None].astype(ctx.dtype)], axis=1)

    attn_fn = _paged_mixer(cfg, cache, _rope_tabs(cfg, gpos), wpos, seen,
                           mask, 1)
    x = _embed(params, tokens[0], cfg)
    x, table, stats = _run_layers(
        params, x, cfg, cache["latent"].reshape(-1, shape[3]), attn_fn,
        valid)
    x = jnp.take(x, jnp.reshape(jnp.maximum(length - 1, 0), (1,)), axis=0)
    out = (_head(params, x, cfg, centred=False),
           {"latent": table.reshape(shape)})
    return out + (stats,) if return_stats else out


def decode_step(params, cache, tokens, pos, active, pages, cfg, *,
                block_size: int, return_stats=False):
    """One token a slot (``transformer.decode_step_paged``'s contract),
    the absorbed form: each slot's row is written, then every slot
    attends over the gathered view of its pages, [B, cache_len, rkv +
    dr]. Inactive rows write nothing."""
    from paddle_tpu.models import transformer
    _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
    B = tokens.shape[0]
    bs = int(block_size)
    T = pages.shape[1] * bs
    shape = cache["latent"].shape
    NB = shape[2] // bs
    pos = jnp.asarray(pos, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    wpos = jnp.where(
        active, jnp.take_along_axis(pages, (pos // bs)[:, None],
                                    axis=1)[:, 0] * bs + pos % bs, -1)
    mask = (jnp.arange(T, dtype=jnp.int32)[None, :] <= pos[:, None])[:, None]

    def seen(table, l, row):
        return transformer._gather_pages(table, l[None], pages, bs, NB)

    attn_fn = _paged_mixer(cfg, cache, _rope_tabs(cfg, pos), wpos, seen,
                           mask, B)
    x = _embed(params, tokens, cfg)
    x, table, stats = _run_layers(
        params, x, cfg, cache["latent"].reshape(-1, shape[3]), attn_fn,
        active)
    out = (_head(params, x, cfg, centred=False),
           {"latent": table.reshape(shape)})
    return out + (stats,) if return_stats else out

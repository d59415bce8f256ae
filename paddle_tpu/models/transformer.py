"""Decoder-only transformer LM — the long-context / large-scale flagship.

The reference predates transformers; this is the modern capability filling
the "scale sequence length / scale out" slot (SURVEY.md §2.3, §5): causal LM
with ring-attention context parallelism over the ``seq`` mesh axis, tensor
parallelism over ``model`` (heads + MLP), data parallelism over ``data``,
all as one jit-compiled GSPMD program.

Functional design (not the v1 layer DSL): parameters are a pytree with
blocks stacked on a leading axis and the layer loop is a ``lax.scan`` —
one compiled block body regardless of depth, weights ride the MXU in bf16.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import place
from paddle_tpu.ops import loss as ops_loss
from paddle_tpu.ops import norm as ops_norm
from paddle_tpu.ops.pallas import policy as _pallas_policy
from paddle_tpu.parallel import ring


# "gpt2" is the block of this module; each other skeleton is the module
# of that name beside it (``skeleton_module``)
SKELETONS = ("gpt2", "gated_hybrid", "latent_moe")


def skeleton_module(cfg):
    """The module that runs a skeleton other than "gpt2":
    ``models/<skeleton>.py``. Each brings ``check_config``,
    ``init_params``, ``init_block_pool``, the three programs
    ``forward`` / ``prefill_chunk`` / ``decode_step``, ``refuse`` (what
    it does not run) and ``SLOT_STATE`` (whether its pool keeps rows
    per engine slot beside the pages, which a chunk then has to be
    told the slot of)."""
    import importlib
    return importlib.import_module(f"paddle_tpu.models.{cfg.skeleton}")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 0                # 0 = MHA; fewer = grouped-query
                                       # attention (smaller KV cache)
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 2048
    dtype: object = jnp.bfloat16
    dropout: float = 0.0               # residual/embedding dropout rate
    use_rope: bool = False             # rotary q/k embeddings instead of
                                       # learned absolute positions
    rope_theta: float = 10000.0
    use_ring_attention: bool = False   # shard_map CP over the seq axis
    cp_mode: str = "ring"              # "ring" (K/V rotate over ICI) or
                                       # "alltoall" (Ulysses head-scatter;
                                       # needs seq-axis | n_heads)
    use_flash_attention: bool = False  # Pallas fused attention (TPU)
    remat: str = "none"                # "none" | "bf16" | "q8": layer-
                                       # granular recompute; autodiff
                                       # saves only one (quantized) copy
                                       # of each block's input instead of
                                       # every intermediate — the
                                       # long-context capacity lever
                                       # (ops/q8.q8_remat)
    moe_experts: int = 0               # >0: the FFN is a top-k MoE over
                                       # this many experts (parallel/moe)
                                       # sharded on the ``expert`` axis;
                                       # 0 = dense mlp
    moe_top_k: int = 1                 # 1 = Switch; 2 = GShard top-2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01       # load-balance loss weight (added
                                       # to lm_loss per layer)
    # -- the gated hybrid skeleton (models/gated_hybrid.py) ---------------
    skeleton: str = "gpt2"             # "gpt2": LayerNorm + GELU block
                                       # below. "gated_hybrid": zero-
                                       # centred RMSNorm, bias-free maps,
                                       # untied head; periods of
                                       # ``full_attn_interval - 1`` gated
                                       # delta-rule layers and one gated
                                       # full-attention layer; every FFN a
                                       # dropless expert layer (d_ff is the
                                       # expert width) plus a shared expert
    attn_head_dim: int = 0             # 0 = d_model // n_heads
    rotary_dim: int = 0                # leading dims of a head that
                                       # rotate; 0 = the whole head
    norm_eps: float = 1e-6
    full_attn_interval: int = 4
    rec_key_heads: int = 0             # delta-rule layers: key heads,
    rec_value_heads: int = 0           # value heads (a multiple),
    rec_key_dim: int = 0               # their head widths and the
    rec_value_dim: int = 0             # causal convolution's kernel
    rec_conv: int = 4
    moe_held: tuple = ()               # (first, count): the experts this
                                       # chip holds of moe_experts (expert
                                       # parallelism's share); () = all
    moe_shared_ff: int = 0             # width of the shared expert
    # -- the latent-attention skeleton (models/latent_moe.py):
    # skeleton="latent_moe": plain RMSNorm, bias-free maps, untied head;
    # latent attention in every layer; ``dense_layers`` leading layers
    # with a dense SwiGLU of width ``dense_ff``, then dropless expert
    # layers (d_ff the expert width, moe_held / moe_shared_ff as above)
    # routed by sigmoid scores plus a selection bias
    q_lora_rank: int = 0               # rank of the query's down-map
    kv_lora_rank: int = 0              # width of the cached latent
    qk_nope_dim: int = 0               # a head's unrotated key width,
    qk_rope_dim: int = 0               # its rotary width (one rotary
                                       # key serves all heads) and
    v_head_dim: int = 0                # its value width
    dense_layers: int = 0
    dense_ff: int = 0
    moe_route_scale: float = 1.0       # factor on the k renormalised
                                       # expert weights
    mtp_layers: int = 0                # depth of the multi-token-
                                       # prediction module's block
                                       # (``latent_moe.forward_mtp``; no
                                       # served path runs it)

    def __post_init__(self):
        object.__setattr__(self, "moe_held",
                           tuple(int(i) for i in self.moe_held))
        if self.skeleton not in SKELETONS:
            raise ValueError(f"skeleton must be one of {SKELETONS}, "
                             f"got {self.skeleton!r}")
        if self.skeleton != "gpt2":
            skeleton_module(self).check_config(self)
        elif self.attn_head_dim or self.rotary_dim or self.moe_held \
                or self.moe_shared_ff or self.kv_lora_rank \
                or self.dense_layers or self.mtp_layers:
            raise ValueError("attn_head_dim, rotary_dim, moe_held and "
                             "moe_shared_ff belong to skeleton="
                             "'gated_hybrid' or 'latent_moe', "
                             "kv_lora_rank, dense_layers and mtp_layers "
                             "to 'latent_moe'")
        if self.cp_mode not in ("ring", "alltoall"):
            raise ValueError(
                f"cp_mode must be 'ring' or 'alltoall', got "
                f"{self.cp_mode!r}")
        if self.remat not in ("none", "bf16", "q8"):
            raise ValueError(
                f"remat must be 'none', 'bf16' or 'q8', got "
                f"{self.remat!r}")

    def moe_cfg(self):
        """The parallel/moe.MoEConfig this FFN runs under."""
        from paddle_tpu.parallel import moe
        return moe.MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff,
            num_experts=self.moe_experts,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_weight=self.moe_aux_weight, top_k=self.moe_top_k)

    @property
    def head_dim(self):
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self):
        """Effective number of key/value heads (GQA groups q heads over
        fewer kv heads; 0 means standard multi-head attention)."""
        h = self.n_kv_heads or self.n_heads
        if h <= 0 or self.n_heads % h:
            raise ValueError(f"n_heads={self.n_heads} must be a multiple "
                             f"of n_kv_heads={h}")
        return h


def init_params(key: jax.Array, cfg: TransformerConfig):
    """Parameter pytree; block weights stacked on axis 0 (scan layout)."""
    if cfg.skeleton != "gpt2":
        return skeleton_module(cfg).init_params(key, cfg)
    k = jax.random.split(key, 8)
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    kvd = cfg.kv_heads * cfg.head_dim     # == D for MHA; smaller for GQA
    s = 1.0 / math.sqrt(D)

    def nrm(kk, shape, scale):
        return (jax.random.normal(kk, shape, jnp.float32) * scale).astype(
            jnp.float32)

    if cfg.moe_experts:
        E = cfg.moe_experts
        ffn = {
            "gate": nrm(k[4], (L, D, E), s),
            "moe_w_in": nrm(k[5], (L, E, D, F), s),
            "moe_w_out": nrm(k[6], (L, E, F, D), 1.0 / math.sqrt(F) /
                             math.sqrt(2 * L)),
        }
    else:
        ffn = {
            "mlp_in": nrm(k[4], (L, D, F), s),
            "mlp_out": nrm(k[5], (L, F, D), 1.0 / math.sqrt(F) /
                           math.sqrt(2 * L)),
        }
    return {
        "embed": nrm(k[0], (V, D), 1.0 / math.sqrt(D)),
        # rope computes positions analytically; keep a 1-row stub so the
        # pytree structure (and shardings) stay config-independent
        "pos": (nrm(k[1], (cfg.max_len, D), 0.02) if not cfg.use_rope
                else jnp.zeros((1, D), jnp.float32)),
        "blocks": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "ln1_b": jnp.zeros((L, D), jnp.float32),
            "qkv": nrm(k[2], (L, D, D + 2 * kvd), s),
            "attn_out": nrm(k[3], (L, D, D), s / math.sqrt(2 * L)),
            "ln2": jnp.ones((L, D), jnp.float32),
            "ln2_b": jnp.zeros((L, D), jnp.float32),
            **ffn,
        },
        "ln_f": jnp.ones((D,), jnp.float32),
        "ln_f_b": jnp.zeros((D,), jnp.float32),
    }


def param_shardings(cfg: TransformerConfig, mesh: Mesh):
    """TP layout (scaling-book): qkv/mlp_in column-parallel, attn_out/mlp_out
    row-parallel over ``model``; embeddings vocab-sharded over ``model``;
    MoE experts sharded over ``expert``. An axis the mesh doesn't carry
    degrades to replication, so the same layout serves DP-only,
    DPxTP and DPxEP meshes."""
    require_gpt2(cfg, "param_shardings (a sharded mesh)")
    M = place.AXIS_MODEL if place.AXIS_MODEL in mesh.axis_names else None

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if cfg.moe_experts:
        # FFN is expert-parallel instead of tensor-parallel: experts
        # shard over the ``expert`` axis, gate replicated
        E = (place.AXIS_EXPERT if place.AXIS_EXPERT in mesh.axis_names
             else None)
        ffn = {"gate": ns(),
               "moe_w_in": ns(None, E, None, None),
               "moe_w_out": ns(None, E, None, None)}
    else:
        ffn = {"mlp_in": ns(None, None, M),
               "mlp_out": ns(None, M, None)}
    return {
        "embed": ns(M, None),
        "pos": ns(),
        "blocks": {
            "ln1": ns(), "ln1_b": ns(), "ln2": ns(), "ln2_b": ns(),
            "qkv": ns(None, None, M),
            "attn_out": ns(None, M, None),
            **ffn,
        },
        "ln_f": ns(), "ln_f_b": ns(),
    }


def require_gpt2(cfg, what: str):
    """THE check of everything a skeleton other than "gpt2" does not
    run (its module's ``refuse`` says what is supported): every
    entry point below that computes on the GPT-2 block calls it first,
    so nothing computes silently on the wrong block."""
    if cfg.skeleton != "gpt2":
        skeleton_module(cfg).refuse(what)


# the stacked matrices the GPT-2 block consumes ONLY as
# ``w[name].astype(<activation dtype>)`` (every block body below);
# ``moe_w_in`` / ``moe_w_out`` are not among them: ``moe.moe_ffn``
# multiplies by them in float32
COMPUTE_DTYPE_LEAVES = ("qkv", "attn_out", "mlp_in", "mlp_out")


def compute_dtype_params(params, cfg: TransformerConfig):
    """``params`` with the block matrices (``COMPUTE_DTYPE_LEAVES``) in
    ``cfg.dtype``: what a SERVING program is handed, so that the cast
    the block makes of them (``w["qkv"].astype(h.dtype)`` ...) is a
    no-op and a decode step reads each weight byte once, where float32
    leaves are read at 4 bytes, written at 2 and read again, every
    step. The value is the one the block cast every time. Every other
    leaf (embedding, positions, LayerNorm, a router's gate, an expert
    stack, ``{"q8", "scale"}`` nodes of an int8 tree) comes back as it
    is, and so does the whole tree (``is``) when nothing is to cast: a
    float32 config, leaves already in the compute dtype, the other
    skeletons (bf16 leaves since they exist). The
    trainer keeps float32 master weights and runs the same block."""
    if cfg.skeleton != "gpt2":
        return params
    dtype = jnp.dtype(cfg.dtype)
    blocks = params["blocks"]
    cast = {n: blocks[n].astype(dtype) for n in COMPUTE_DTYPE_LEAVES
            if n in blocks and not isinstance(blocks[n], dict)
            and blocks[n].dtype != dtype}
    if not cast:
        return params
    return {**params, "blocks": {**blocks, **cast}}


def _layer_norm(x, g, b):
    return ops_norm.layer_norm(x, g, b).astype(x.dtype)


def _rope_tables(positions, head_dim, theta):
    """cos/sin tables [T, Dh/2] for GLOBAL positions — computed once per
    forward (outside the layer scan) and shared by every layer's q and k."""
    if head_dim % 2:
        raise ValueError(f"RoPE requires an even head_dim, got {head_dim}")
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope_rows(x, tables):
    """Rotary embedding for one token PER ROW: x [B, H, Dh] with per-row
    cos/sin tables [B, Dh/2] (each batch row sits at its own position —
    the continuous-batching decode layout). Elementwise math is identical
    to ``_rope``'s, so a row at position p rotates bitwise the same as a
    lockstep step at scalar position p."""
    cos, sin = tables
    cos = cos[:, None, :]
    sin = sin[:, None, :]
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def _rope(x, tables):
    """Rotary position embedding over the head dim of [..., T, H, Dh]
    (pairing halves: (x1, x2) -> (x1·cos − x2·sin, x1·sin + x2·cos)).
    Positions entered the tables as GLOBAL indices, so the rotation is
    correct under ring context parallelism too — it applies to q/k before
    any attention engine (full / flash / ring), no kernel change."""
    cos, sin = tables
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


def _blocks_quantized(params) -> bool:
    """True when the big matmul weights ride as {"q8","scale"} nodes
    (``io/lm_serving.quantize_lm_params``) — the int8-weight serving
    path the decode steps handle natively (dequant INSIDE the layer
    scan, so weights are read from HBM at 1 byte/elt per token)."""
    from paddle_tpu.ops import q8 as ops_q8
    return any(ops_q8.is_quantized_weight(n) for n in
               jax.tree_util.tree_leaves(
                   params["blocks"], is_leaf=ops_q8.is_quantized_weight))


def _live_layer_weights(w, li):
    """Dequantize ONE layer's {"q8","scale"} weights inside the scan
    body, with the anti-hoist defenses proven in ``generate``: the
    weights arrive as scanned xs (loop-VARIANT by data dependence — a
    dynamic slice of the int8 stack per iteration), sit behind an
    optimization barrier, and the scales fold in a float zero derived
    from the layer counter. XLA therefore cannot rematerialize the full
    fp32 weight stack outside the loop; each layer's dequant multiply
    fuses into its matmul operand reads (asserted on the optimized HLO
    in tests/test_pallas_decode.py)."""
    from paddle_tpu.ops import q8 as ops_q8
    w = jax.lax.optimization_barrier(w)
    eps = li.astype(jnp.float32) * 0.0

    def leaf(n):
        if ops_q8.is_quantized_weight(n):
            return ops_q8.dequantize_weight(
                {"q8": n["q8"], "scale": n["scale"] + eps})
        return n

    return {k: leaf(v) for k, v in w.items()}


def _embed_rows(params, tokens, cfg):
    """Token-embedding gather, q8-aware: quantized embeddings gather
    int8 rows and dequantize per row (the [B, 1] scale broadcast fuses
    into the gather's consumer) — no fp32 [V, D] table materializes."""
    from paddle_tpu.ops import q8 as ops_q8
    emb = params["embed"]
    if ops_q8.is_quantized_weight(emb):
        return (jnp.take(emb["q8"], tokens, axis=0).astype(jnp.float32)
                * jnp.take(emb["scale"], tokens, axis=0)).astype(cfg.dtype)
    return jnp.take(emb, tokens, axis=0).astype(cfg.dtype)


def _vocab_logits(x, params):
    """Final vocab projection [B, D] -> [B, V], q8-aware: the dequant
    multiply is elementwise on the einsum operand, which XLA fuses into
    the dot's weight read (1-byte weight traffic on TPU; CPU may
    materialize — the logits head is one matrix, amortized against the
    L-layer stack the scan protects)."""
    from paddle_tpu.ops import q8 as ops_q8
    emb = params["embed"]
    emb32 = (ops_q8.dequantize_weight(emb)
             if ops_q8.is_quantized_weight(emb)
             else emb.astype(jnp.float32))
    return jnp.einsum("bd,vd->bv", x.astype(jnp.float32), emb32)


def forward(params, tokens: jax.Array, cfg: TransformerConfig, *,
            mesh: Optional[Mesh] = None,
            lengths: Optional[jax.Array] = None,
            return_kv: bool = False, return_aux: bool = False,
            dropout_key: Optional[jax.Array] = None):
    """tokens [B, T] int32 → logits [B, T, vocab] (float32).

    With ``cfg.use_ring_attention`` and a mesh carrying a >1 ``seq`` axis,
    attention runs as ring CP; activations get seq-sharding constraints so
    XLA keeps the [B, T, D] tensors distributed end-to-end.
    ``return_kv=True`` additionally returns the per-layer (k, v)
    projections stacked [L, B, T, kv_heads, Dh] (kv_heads < n_heads
    under GQA) — the prefill path of the KV-cache decoder shares this
    exact block so the two can't drift.
    ``dropout_key`` enables inverted dropout at rate ``cfg.dropout``
    (embedding + both residual branches per block); omit it — as eval
    and serving paths do — for deterministic inference.
    ``return_aux=True`` additionally returns the summed MoE
    load-balance loss (zero for dense configs) — lm_loss adds it.
    """
    if cfg.skeleton != "gpt2":
        if mesh is not None or return_kv or return_aux \
                or dropout_key is not None:
            require_gpt2(cfg, "forward(mesh=, return_kv=, return_aux=, "
                              "dropout_key=)")
        return skeleton_module(cfg).forward(params, tokens, cfg,
                                            lengths=lengths)
    return _forward_impl(params, tokens, cfg, mesh, lengths, return_kv,
                         head="all", dropout_key=dropout_key,
                         return_aux=return_aux)


def _forward_impl(params, tokens, cfg, mesh, lengths, return_kv, head,
                  dropout_key=None, return_aux=False, gather_pos=None):
    B, T = tokens.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    if not 0.0 <= cfg.dropout < 1.0:
        raise ValueError(f"cfg.dropout must be in [0, 1), got {cfg.dropout}")
    rate = cfg.dropout if dropout_key is not None else 0.0

    def drop(h, key):
        if rate <= 0.0:
            return h
        keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
        return jnp.where(keep, h / (1.0 - rate), 0).astype(h.dtype)

    if rate > 0.0:
        emb_key, blk_key = jax.random.split(dropout_key)
    else:
        emb_key = blk_key = jax.random.PRNGKey(0)   # unused (rate is static)
    layer_keys = jax.random.split(blk_key, cfg.n_layers)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if not cfg.use_rope:
        x = x + params["pos"][:T].astype(cfg.dtype)[None]
    if rate > 0.0:
        x = drop(x, emb_key)
    rope_tabs = _rope_tables(jnp.arange(T, dtype=jnp.int32), Dh,
                             cfg.rope_theta) if cfg.use_rope else None

    seq_sharded = (mesh is not None and place.AXIS_SEQ in mesh.axis_names
                   and mesh.shape[place.AXIS_SEQ] > 1)

    def constrain(h):
        if mesh is None:
            return h
        spec = P(place.AXIS_DATA,
                 place.AXIS_SEQ if seq_sharded else None, None)
        return jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, spec))

    x = constrain(x)

    Hkv = cfg.kv_heads
    kvd = Hkv * Dh

    def block(x, scanned):
        w, lkey = scanned
        k1, k2 = jax.random.split(lkey)
        h = _layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = jnp.einsum("btd,de->bte", h, w["qkv"].astype(h.dtype))
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        q = q.reshape(B, T, H, Dh)
        k = k.reshape(B, T, Hkv, Dh)
        v = v.reshape(B, T, Hkv, Dh)
        if cfg.use_rope:
            q = _rope(q, rope_tabs)
            k = _rope(k, rope_tabs)
        kv = (k.astype(cfg.dtype), v.astype(cfg.dtype)) \
            if return_kv else None
        # GQA: every engine takes Hkv-head k/v directly — the ring path
        # rotates the small tensors over ICI and broadcasts to the q-head
        # layout locally per step; the jnp engines group in the einsum
        if seq_sharded and cfg.use_ring_attention:
            if cfg.cp_mode == "alltoall":
                # Ulysses layout: two all-to-alls reshuffle seq<->heads,
                # attention runs fully local per head group
                attn = ring.alltoall_attention_spmd(
                    q, k, v, mesh, causal=True, lengths=lengths,
                    use_flash=cfg.use_flash_attention and lengths is None)
            else:
                # flash blocks inside the ring when the batch is packed —
                # O(T/P·D) per chip with no score tensor even per ring
                # step
                attn = ring.ring_attention_spmd(
                    q, k, v, mesh, causal=True, lengths=lengths,
                    use_flash=cfg.use_flash_attention and lengths is None)
        elif cfg.use_flash_attention and lengths is None:
            from paddle_tpu.ops.pallas import flash_attention
            attn = flash_attention(q, k, v, causal=True)
        else:
            _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
            attn = ring.full_attention(q, k, v, causal=True, lengths=lengths)
        attn = attn.reshape(B, T, cfg.d_model)
        x = x + drop(jnp.einsum("btd,de->bte", attn,
                                w["attn_out"].astype(attn.dtype)), k1)
        x = constrain(x)
        h2 = _layer_norm(x, w["ln2"], w["ln2_b"])
        if cfg.moe_experts:
            from paddle_tpu.parallel import moe
            out, aux = moe.moe_ffn(
                {"gate": w["gate"], "w_in": w["moe_w_in"],
                 "w_out": w["moe_w_out"]},
                h2.reshape(B * T, cfg.d_model), cfg.moe_cfg(), mesh=mesh)
            x = x + drop(out.reshape(B, T, cfg.d_model).astype(x.dtype),
                         k2)
            return constrain(x), (kv, aux)
        ff = jnp.einsum("btd,df->btf", h2, w["mlp_in"].astype(h2.dtype))
        ff = jax.nn.gelu(ff)
        x = x + drop(jnp.einsum("btf,fd->btd", ff,
                                w["mlp_out"].astype(ff.dtype)), k2)
        return constrain(x), (kv, jnp.zeros((), jnp.float32))

    if cfg.remat != "none" and not return_kv:
        # layer-granular recompute: backward rebuilds each block from a
        # (quantized) copy of its input; the scan then saves one stash
        # per layer instead of every intermediate (ops/q8.q8_remat).
        # KV-returning calls are serving-only (no backward) — skip there.
        from paddle_tpu.ops import q8 as ops_q8
        inner = ops_q8.q8_remat(
            block, stash="int8" if cfg.remat == "q8" else "bf16")
        x, (kvs, auxs) = jax.lax.scan(inner, x,
                                      (params["blocks"], layer_keys))
    else:
        x, (kvs, auxs) = jax.lax.scan(block, x,
                                      (params["blocks"], layer_keys))
    aux_total = jnp.sum(auxs)
    if head == "last":
        # serving prefill: only the final position feeds the vocab head —
        # skips the O(T·vocab) logits tensor a full head would materialize
        x = x[:, -1:]
    elif head == "gather":
        # slot prefill: the prompt is right-padded to a bucket length, so
        # the position feeding the vocab head is the TRACED index
        # ``gather_pos`` [B] (the true last prompt token), not -1. Same
        # O(vocab) head as "last"; causality already isolates the real
        # prefix from the padding, so no attention mask is needed and the
        # gathered activations are bitwise the unpadded forward's.
        x = jnp.take_along_axis(x, gather_pos.reshape(-1, 1, 1), axis=1)
    x = _layer_norm(x, params["ln_f"], params["ln_f_b"])
    logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    if return_kv and return_aux:
        return logits, kvs, aux_total
    if return_kv:
        return logits, kvs
    if return_aux:
        return logits, aux_total
    return logits


def lm_loss(params, tokens, targets, cfg: TransformerConfig, *,
            mesh: Optional[Mesh] = None,
            lengths: Optional[jax.Array] = None,
            dropout_key: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross-entropy over valid positions (+ the MoE
    load-balance aux loss for moe_experts configs)."""
    logits, aux = forward(params, tokens, cfg, mesh=mesh, lengths=lengths,
                          dropout_key=dropout_key, return_aux=True)
    tok_ce = ops_loss.softmax_cross_entropy(logits, targets)
    if lengths is not None:
        mask = (jnp.arange(tokens.shape[1])[None, :] <
                lengths[:, None]).astype(jnp.float32)
    else:
        mask = jnp.ones_like(tok_ce)
    ce = jnp.sum(tok_ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + aux


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Per-layer KV cache for incremental decoding:
    [L, B, max_len, kv_heads, Dh] (kv_heads < n_heads under GQA)
    (the serving-side analog of the reference's recurrent generation
    machinery, trainer/tests/test_recurrent_machine_generation.cpp slot)."""
    require_gpt2(cfg, "init_cache (the lockstep KV cache)")
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


# The pool's array layout generation — stamped into v4/v5 artifacts
# (io/lm_serving) so a loader never schedules programs compiled against
# a different layout, and the key prefix of the Pallas MEASURED_*
# tuning tables. "head_major" is [L, Hkv, M, Dh]: the kv-head axis
# leads so every Pallas grid program's pool block is a Mosaic-legal
# (1, block_size, Dh) slab (the pre-relayout "slot_major"
# [L, M, Hkv, Dh] forced per-head column blocks (M, 1, Dh), which the
# TPU last-two-dims tiling rule rejects). ONE definition — the kernels
# own it (ops/pallas/decode.py); this re-export is what the artifact
# stamping and the engine read, so a future layout bump cannot fence
# artifacts and key the tuning tables with different strings.
from paddle_tpu.ops.pallas.decode import POOL_LAYOUT  # noqa: E402


def init_block_pool(cfg: TransformerConfig, num_blocks: int,
                    block_size: int, kv_dtype: Optional[str] = None,
                    slots: Optional[int] = None):
    """Paged KV pool for the block-table decode engine, HEAD-MAJOR:
    [L, kv_heads, num_blocks * block_size, Dh] per k/v — the standard
    TPU paged-KV layout (kv-head leading). Block ``i`` owns the aligned
    span ``[i*block_size, (i+1)*block_size)`` of the flat position axis
    (now the SECOND-to-last axis); per-slot page tables
    (``serving/blocks.BlockPool``) map logical positions onto blocks,
    so HBM is committed per BLOCK actually written instead of
    ``cache_len`` per arena row. Head-major is what makes every Pallas
    serving kernel's pool block a tiling-legal ``(1, block_size, Dh)``
    slab placeable by scalar-prefetched page indexing — see
    ``POOL_LAYOUT`` and ops/pallas/decode.py.

    ``kv_dtype`` picks the pool storage width. ``None`` keeps the model
    dtype ({"k","v"} only). ``"int8"`` stores k/v as symmetric int8
    with one fp32 scale per (layer, head, position) in
    ``k_scale``/``v_scale`` tables [L, kv_heads, M] that ride beside
    the pool — the page table indexes values and scales alike, so
    scales travel with their block under any paging. ``"int4"`` packs
    two nibbles per byte ([..., Dh//2] storage, same scale layout).
    Scales are per pool ROW (write-local): a decode step writing one
    token never rescales a block's resident neighbours, which is what
    keeps hit-replay bitwise and blocks relocatable."""
    if cfg.skeleton != "gpt2":
        # the skeleton's own page tables (``serving/transfer``'s
        # description holds for them: gated_hybrid pages its
        # full-attention layers only, latent_moe keeps one latent row a
        # token) and, where it has any, per slot the rows beside them
        # (``slots``: the engine's batch)
        mod = skeleton_module(cfg)
        if kv_dtype not in (None, "none"):
            mod.refuse(f"an {kv_dtype} KV pool")
        if mod.SLOT_STATE and slots is None:
            raise ValueError(f"init_block_pool(slots=...): a "
                             f"{cfg.skeleton} pool holds rows per engine "
                             f"slot")
        return mod.init_block_pool(cfg, num_blocks, block_size, slots)
    M = int(num_blocks) * int(block_size)
    if kv_dtype in (None, "none"):
        shape = (cfg.n_layers, cfg.kv_heads, M, cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
    from paddle_tpu.ops import q8 as ops_q8
    if kv_dtype not in ops_q8.KV_DTYPES:
        raise ValueError(f"kv_dtype {kv_dtype!r}: one of "
                         f"{(None,) + ops_q8.KV_DTYPES}")
    Dh = cfg.head_dim
    if kv_dtype == "int4":
        if Dh % 2:
            raise ValueError(f"int4 KV packs nibble pairs: head_dim "
                             f"{Dh} must be even")
        Dh = Dh // 2
    shape = (cfg.n_layers, cfg.kv_heads, M, Dh)
    sshape = (cfg.n_layers, cfg.kv_heads, M)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def pool_kv_dtype(cache, cfg: TransformerConfig) -> str:
    """The KV storage width a pool pytree carries: ``"none"`` (model
    dtype), ``"int8"``, or ``"int4"`` — inferred from the pytree
    structure so the step functions need no extra argument and jit
    re-specializes automatically when the pool layout changes."""
    if "k_scale" not in cache:
        return "none"
    return "int4" if cache["k"].shape[-1] == cfg.head_dim // 2 \
        and cfg.head_dim > 1 else "int8"


def kv_pool_bytes_per_token(cfg: TransformerConfig,
                            kv_dtype: Optional[str] = None) -> int:
    """HBM bytes ONE resident token costs across all layers (k + v +
    scale rows, or whatever page tables the skeleton keeps) — the
    ``engine_kv_bytes_per_token`` gauge and the slots-at-equal-HBM
    arithmetic in ``serving_bench``: read off the shapes of a pool of
    ONE row by the description every pool satisfies
    (``serving/transfer.bytes_per_token``), as the engine reads it off
    the pool it is handed."""
    from paddle_tpu.serving import transfer
    return transfer.bytes_per_token(jax.eval_shape(
        lambda: init_block_pool(cfg, 1, 1, kv_dtype, slots=1)))


def kv_rel_l2_budget(cfg: TransformerConfig, kv_dtype: str) -> float:
    """Global rel-L2 budget for decode logits off a quantized pool vs
    the fp32 pool — the PR-5 tolerance-contract recipe. Symmetric
    rounding injects at most ``0.5/qmax`` relative noise per KV element
    (0.5/127 for int8, 0.5/7 for int4); each layer reads quantized K
    (score perturbation, softmax-damped) and quantized V (weighted-sum
    perturbation) — 2L independent noise injections that compound in
    quadrature through the residual stream, so the noise reaching the
    logits is ~``sqrt(2L) * 0.5/qmax``. Budget = 2x that (slack for
    unlucky alignment and the softmax nonlinearity, never enough to
    excuse a wrong-scale bug, which lands at O(1) — measured on the
    test config: int8 ~0.2% vs budget 1.6%, int4 ~4% vs 29%)."""
    from paddle_tpu.ops import q8 as ops_q8
    half_step = 0.5 / ops_q8.KV_QMAX[kv_dtype]
    return min(0.5, 2.0 * math.sqrt(2 * cfg.n_layers) * half_step)


def _gather_pages(tab, groups, pages, block_size: int, num_blocks: int):
    """Whole pages of one pool table, read where they lie. ``tab`` is
    the table as flat rows ``[G * num_blocks * block_size, ...]`` (G =
    layers x kv-heads: a bitcast of the head-major array), ``groups``
    the (layer, head) row groups wanted (``layer * Hkv + head``) and
    ``pages`` the block ids, shaped to broadcast against
    ``groups[..., None]`` -> ``[*broadcast dims, P * block_size, ...]``.
    Layer and head ride in the gather's index, so no layer slab is
    sliced out of the pool and the pool keeps its layout (a gather on
    the position axis of the 4-D array makes the TPU compiler re-lay
    the WHOLE pool out first, position-major). Block ids are the
    engine's own, always in range: ``clip`` is the gather's native
    mode and spares the fill mask."""
    bs = int(block_size)
    idx = groups[..., None] * num_blocks + pages
    g = jnp.take(tab.reshape((-1, bs) + tab.shape[1:]), idx, axis=0,
                 mode="clip")
    return g.reshape(idx.shape[:-1] + (idx.shape[-1] * bs,)
                     + tab.shape[1:])


def _skeleton_step(params, cache, cfg, pallas, program: str, *args, **kw):
    """A paged step program of a skeleton other than "gpt2" (its
    module's ``prefill_chunk`` / ``decode_step``): XLA path, weights
    and pool in the model's dtype; ``(logits, pool)`` and with
    ``return_stats`` the expert layer's counts as a third."""
    mod = skeleton_module(cfg)
    if _pallas_policy.pallas_mode(pallas) != "off":
        mod.refuse("PADDLE_TPU_PALLAS other than 'off' (no kernel takes "
                   "its head widths)")
    if "k_scale" in cache:
        mod.refuse("an int8 / int4 KV pool")
    if _blocks_quantized({"blocks": params}):
        mod.refuse("int8 weights")
    return getattr(mod, program)(params, cache, *args, cfg, **kw)


def prefill(params, tokens: jax.Array, cfg: TransformerConfig,
            cache_len: int, *, mesh: Optional[Mesh] = None):
    """Batched prompt ingestion: the SAME traced block the training path
    runs (flash/ring dispatch included when ``mesh`` is passed) with the
    vocab head applied to the last position only, plus cache padding to
    ``cache_len``. Returns (last-position logits [B, vocab] fp32, cache).
    Packed (equal-length) prompts only — the decode loop's position
    counter is shared across the batch."""
    require_gpt2(cfg, "prefill (the lockstep path)")
    T = tokens.shape[1]
    logits, (kc, vc) = _forward_impl(params, tokens, cfg, mesh, None,
                                     True, head="last")
    pad = ((0, 0), (0, 0), (0, cache_len - T), (0, 0), (0, 0))
    return logits[:, 0], {"k": jnp.pad(kc, pad), "v": jnp.pad(vc, pad)}


def decode_step(params, cache, tokens: jax.Array, pos: jax.Array,
                cfg: TransformerConfig):
    """One incremental step: tokens [B] at position ``pos`` (scalar int32)
    → (logits [B, vocab] fp32, updated cache). All shapes static; the
    cache updates via dynamic_update_slice so the step compiles once and
    is replayed for every position (lax.scan-friendly)."""
    require_gpt2(cfg, "decode_step (the lockstep path)")
    B = tokens.shape[0]
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    kvd = Hkv * Dh
    max_len = cache["k"].shape[2]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if not cfg.use_rope:
        x = x + jax.lax.dynamic_index_in_dim(
            params["pos"], pos, keepdims=False).astype(cfg.dtype)
    rope_tabs = _rope_tables(jnp.asarray(pos, jnp.int32).reshape(1), Dh,
                             cfg.rope_theta) if cfg.use_rope else None

    def block(x, scanned):
        w, kc, vc = scanned                  # kc/vc [B, max_len, Hkv, Dh]
        h = _layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = h @ w["qkv"].astype(h.dtype)   # [B, D + 2*kvd]
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        if cfg.use_rope:
            q = _rope(q.reshape(B, 1, H, Dh), rope_tabs).reshape(B, H * Dh)
            k = _rope(k.reshape(B, 1, Hkv, Dh), rope_tabs).reshape(B, kvd)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k.reshape(B, 1, Hkv, Dh).astype(kc.dtype), pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v.reshape(B, 1, Hkv, Dh).astype(vc.dtype), pos, axis=1)
        # grouped attention: q [B, Hkv, G, Dh] against the Hkv-head cache
        g = H // Hkv
        q32 = q.reshape(B, Hkv, g, Dh).astype(jnp.float32)
        s = jnp.einsum("bkgd,btkd->bkgt", q32,
                       kc.astype(jnp.float32)) / math.sqrt(Dh)
        mask = jnp.arange(max_len) <= pos
        s = jnp.where(mask[None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bkgt,btkd->bkgd", p, vc.astype(jnp.float32))
        attn = attn.reshape(B, cfg.d_model).astype(cfg.dtype)
        x = x + attn @ w["attn_out"].astype(attn.dtype)
        h2 = _layer_norm(x, w["ln2"], w["ln2_b"])
        if cfg.moe_experts:
            import dataclasses as _dc

            from paddle_tpu.parallel import moe
            # decode capacity = full batch (cf = E/k): inference must
            # not drop tokens the way Switch training capacity does
            mc = _dc.replace(cfg.moe_cfg(), capacity_factor=float(
                cfg.moe_experts) / cfg.moe_top_k)
            out, _ = moe.moe_ffn(
                {"gate": w["gate"], "w_in": w["moe_w_in"],
                 "w_out": w["moe_w_out"]}, h2, mc)
            x = x + out.astype(x.dtype)
        else:
            ff = jax.nn.gelu(h2 @ w["mlp_in"].astype(h2.dtype))
            x = x + ff @ w["mlp_out"].astype(ff.dtype)
        return x, (kc, vc)

    x, (kn, vn) = jax.lax.scan(block, x,
                               (params["blocks"], cache["k"], cache["v"]))
    x = _layer_norm(x, params["ln_f"], params["ln_f_b"])
    logits = jnp.einsum("bd,vd->bv", x.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    return logits, {"k": kn, "v": vn}


def decode_step_paged(params, cache, tokens: jax.Array, pos: jax.Array,
                      active: jax.Array, pages: jax.Array,
                      cfg: TransformerConfig, *, block_size: int,
                      pallas: Optional[str] = None,
                      return_stats: bool = False):
    """One incremental step over the PAGED block pool: tokens [B] int32,
    ``pos`` [B] int32, ``active`` [B] bool, ``pages`` [B, P] int32 block
    ids → (logits [B, vocab] fp32, updated pool).

    The block-table decode step, each row at its own position: the
    cache is the head-major flat pool ``init_block_pool`` builds ([L, Hkv, M, Dh]
    with M = num_blocks·block_size) and each slot reads its KV through
    a gathered logical view ``[B, T]`` (T = P·block_size) built from
    its page vector — every shape static, so the engine still compiles
    the decode step exactly ONCE for any paging. Row b writes its new
    k/v at the physical position ``pages[b, pos[b]//bs]·bs + pos[b]%bs``
    via a scatter whose inactive rows target an out-of-bounds index and
    are DROPPED (mode="drop") — admission/recycling can't perturb
    in-flight neighbours.

    THE POOL IS UPDATED IN PLACE. It rides the layer loop as the CARRY
    (never as scan ``xs``/``ys``: those are two buffers, and every
    layer's slab would be sliced out, re-laid-out and update-sliced
    back), each table viewed as rows ``[L·Hkv·M, ...]`` — a bitcast of
    the head-major array for head widths of whole lane rows (Dh a
    multiple of 128; narrower heads are stored position-minor by the
    device and pay one re-layout in and out per call, around the loop,
    not in it). Layer and head are folded into the row index, so the
    scatter writes ``Hkv·B`` rows at ``(l·Hkv + h)·M + position`` into
    the carried buffer and the read gathers whole pages at
    ``(l·Hkv + h)·(M/bs) + page`` (``_gather_pages``): the loop touches
    the rows it writes and the pages attention reads, nothing else.
    A caller that DONATES the pool (the engine does, at ``jax.jit`` and
    around the exported call) gets the writes in its own buffer; the
    pool it passed is dead after the call. The gathered view
    transposes to the ``[B, T, Hkv, Dh]`` shape the lockstep cache
    (``init_cache``) has, so the attention arithmetic is that of
    ``decode_step``.

    For slots whose pages tile a contiguous span (the identity mapping)
    and that share one position, the gathered view IS the lockstep
    cache row, T equals its cache_len, and every elementwise/reduction
    shape matches ``decode_step`` — active rows' logits and written
    cache values are bitwise identical on the CPU (pinned in
    tests/test_paged_engine.py), so the reference and the served path
    cannot drift.

    ``pallas`` picks the attention engine through the package-wide
    ``PADDLE_TPU_PALLAS`` policy (explicit arg > env > auto): when it
    resolves ``on``/``interpret``, the gather + score + softmax +
    weighted sum above is replaced by
    ``ops.pallas.decode.flash_decode_attention`` (which raises if the
    chip cannot take the geometry — nothing degrades to XLA) — page
    indices resolved inside the kernel, K/V streamed from the pool, no
    gathered ``[B, T, Hkv, Dh]`` view or ``[B, H, T]`` score tensor in
    HBM, bitwise the XLA path's logits on aligned fp32 shapes (pinned
    in tests/test_pallas_decode.py). The pool WRITE of the step's new
    k/v stays the same scatter on either engine; the kernel's signature
    takes ONE layer's tables, which are sliced out of the carry for it
    (one slab read and written per table and layer — until the kernel
    learns to take the whole pool and a layer index). ``params`` may
    carry int8 weights ({"q8","scale"} nodes): they ride the layer scan
    as int8 xs and dequantize inside the body (``_live_layer_weights``
    anti-hoist defenses), so serving reads weights at 1 byte/elt.

    QUANTIZED pools (``init_block_pool(kv_dtype="int8"/"int4")``,
    detected from the pytree): the step quantizes its new k/v row at
    write time (one scale per (row, head) — ``ops/q8.quantize_kv``)
    and scatters values AND scale rows with the same mode="drop"
    isolation; reads gather int8/nibble-packed rows plus their scales
    and widen in the consumer (XLA path) or in-register inside the
    kernel's gather loop (Pallas path) — history crosses HBM at 1 or
    1/2 byte/elt, and the fused-dequant kernel stays bitwise the XLA
    quantized path (tests/test_kv_quant.py)."""
    from paddle_tpu.ops import q8 as ops_q8
    from paddle_tpu.ops.pallas import decode as _pallas_decode
    if cfg.skeleton != "gpt2":
        return _skeleton_step(params, cache, cfg, pallas, "decode_step",
                              tokens, pos, active, pages,
                              block_size=block_size,
                              return_stats=return_stats)
    B = tokens.shape[0]
    P = pages.shape[1]
    bs = int(block_size)
    T = P * bs
    L = cfg.n_layers
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    kvd = Hkv * Dh
    M = cache["k"].shape[2]
    NB = M // bs                          # pages of one (layer, head)
    quantized = _blocks_quantized(params)
    kvq = pool_kv_dtype(cache, cfg)       # "none" | "int8" | "int4"
    mode = _pallas_policy.pallas_mode(pallas)
    # only "off" takes the XLA path: a kernel the chip cannot take
    # raises inside flash_decode_attention, it does not degrade
    use_pallas = mode != "off"
    _pallas_policy.note_path("attention",
                             _pallas_policy.kernel_path(mode))
    pos = jnp.asarray(pos, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    x = _embed_rows(params, tokens, cfg)
    if not cfg.use_rope:
        x = x + jnp.take(params["pos"], pos, axis=0).astype(cfg.dtype)
    rope_tabs = _rope_tables(pos, Dh, cfg.rope_theta) \
        if cfg.use_rope else None
    # physical write position per row, within one (layer, head) span
    wpos = jnp.take_along_axis(pages, (pos // bs)[:, None],
                               axis=1)[:, 0] * bs + pos % bs
    heads = jnp.arange(Hkv, dtype=jnp.int32)
    attend = (jnp.arange(T, dtype=jnp.int32)[None, :]
              <= pos[:, None])                           # [B, T] logical

    def write(tab, rows, new):
        """Scatter the step's rows ``new [B, Hkv, ...]`` into a flat
        table at ``rows [Hkv * B]``. Layer and head are folded into
        the row index so the scattered axis is the table's major one:
        the compiler then updates the carried buffer in place."""
        new = jnp.swapaxes(new, 0, 1)
        return tab.at[rows].set(
            new.reshape((Hkv * B,) + new.shape[2:]).astype(tab.dtype),
            mode="drop")

    def view(tab, groups):
        """One layer's logical view ``[B, T, Hkv, ...]`` of a flat
        table (``groups [Hkv]``: the layer's heads) — the only rows of
        the pool the XLA attention reads."""
        g = _gather_pages(tab, groups[:, None], pages, bs, NB)
        return jnp.transpose(g, (1, 2, 0) + tuple(range(3, g.ndim)))

    def block(carry, scanned):
        x, pool = carry       # pool: flat tables [L*Hkv*M, ...], carried
        w, li = scanned
        if quantized:
            w = _live_layer_weights(w, li)
        h = _layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = h @ w["qkv"].astype(h.dtype)   # [B, D + 2*kvd]
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        if cfg.use_rope:
            q = _rope_rows(q.reshape(B, H, Dh), rope_tabs).reshape(
                B, H * Dh)
            k = _rope_rows(k.reshape(B, Hkv, Dh), rope_tabs).reshape(
                B, kvd)
        new = {"k": k.reshape(B, Hkv, Dh), "v": v.reshape(B, Hkv, Dh)}
        if kvq != "none":
            # write-time quantization: one scale per (row, head); the
            # same scatter drops inactive rows for values AND scales,
            # so isolation holds for both tables
            new["k"], new["k_scale"] = ops_q8.quantize_kv(new["k"], kvq)
            new["v"], new["v_scale"] = ops_q8.quantize_kv(new["v"], kvq)
        # inactive rows aim past the table's end and are dropped (the
        # paged analog of the where()-write)
        groups = li * Hkv + heads                  # this layer's heads
        rows = jnp.where(active[None, :],
                         (groups * M)[:, None] + wpos[None, :],
                         L * Hkv * M).reshape(Hkv * B)
        pool = {n: write(pool[n], rows, new[n]) for n in pool}
        g = H // Hkv
        if use_pallas:
            # the kernel reads the just-written pool (pos attends to
            # itself) and resolves the page walk via scalar prefetch;
            # for quantized pools the dequant multiply runs in-register
            # on the streamed blocks (int8/int4 HBM reads). Its
            # signature takes ONE layer's tables: a slice of the carry
            slab = {n: jax.lax.dynamic_index_in_dim(
                t.reshape((L, Hkv, M) + t.shape[1:]), li, 0,
                keepdims=False) for n, t in pool.items()}
            attn = _pallas_decode.flash_decode_attention(
                q.reshape(B, Hkv, g, Dh), slab["k"], slab["v"], pages,
                pos, block_size=bs, k_scale=slab.get("k_scale"),
                v_scale=slab.get("v_scale"), kv_dtype=kvq,
                interpret=(mode == "interpret"))
        else:
            # the barrier pins the widening beside its consumer: left
            # free, the compiler hoists the fp32 convert up to the
            # gather and the view crosses HBM twice more at 4 bytes/elt
            seen = jax.lax.optimization_barrier(
                {n: view(t, groups) for n, t in pool.items()})
            if kvq != "none":
                kt = ops_q8.dequantize_kv(seen["k"], seen["k_scale"], kvq)
                vt = ops_q8.dequantize_kv(seen["v"], seen["v_scale"], kvq)
            else:
                kt = seen["k"].astype(jnp.float32)
                vt = seen["v"].astype(jnp.float32)
            q32 = q.reshape(B, Hkv, g, Dh).astype(jnp.float32)
            s = jnp.einsum("bkgd,btkd->bkgt", q32, kt) / math.sqrt(Dh)
            s = jnp.where(attend[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("bkgt,btkd->bkgd", p, vt)
        attn = attn.reshape(B, cfg.d_model).astype(cfg.dtype)
        x = x + attn @ w["attn_out"].astype(attn.dtype)
        h2 = _layer_norm(x, w["ln2"], w["ln2_b"])
        if cfg.moe_experts:
            import dataclasses as _dc

            from paddle_tpu.parallel import moe
            mc = _dc.replace(cfg.moe_cfg(), capacity_factor=float(
                cfg.moe_experts) / cfg.moe_top_k)
            out, _ = moe.moe_ffn(
                {"gate": w["gate"], "w_in": w["moe_w_in"],
                 "w_out": w["moe_w_out"]}, h2, mc)
            x = x + out.astype(x.dtype)
        else:
            ff = jax.nn.gelu(h2 @ w["mlp_in"].astype(h2.dtype))
            x = x + ff @ w["mlp_out"].astype(ff.dtype)
        return (x, pool), None

    # every table of the pool as rows [L*Hkv*M, ...]: a bitcast of the
    # head-major array (POOL_LAYOUT unchanged)
    flat = {n: t.reshape((L * Hkv * M,) + t.shape[3:])
            for n, t in cache.items()}
    (x, flat), _ = jax.lax.scan(
        block, (x, flat),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
    x = _layer_norm(x, params["ln_f"], params["ln_f_b"])
    logits = _vocab_logits(x, params)
    return logits, {n: t.reshape(cache[n].shape) for n, t in flat.items()}


def verify_step_paged(params, cache, tokens: jax.Array, pos: jax.Array,
                      valid: jax.Array, active: jax.Array,
                      pages: jax.Array, cfg: TransformerConfig, *,
                      block_size: int):
    """W tokens of EVERY slot in one pass over the paged pool — the
    speculative-decoding verify step. tokens [B, W] int32 (row b holds
    ``[last_token, draft_1, ..., draft_{W-1}]``), ``pos`` [B] int32 (the
    position row b's FIRST token writes — decode_step_paged's ``pos``
    semantics), ``valid`` [B] int32 (window rows beyond it neither write
    nor matter), ``active`` [B] bool, ``pages`` [B, P] the FULL page
    table → (logits [B, W, vocab] fp32, updated pool).

    This is ``decode_step_paged`` with a W axis, and deliberately
    nothing more: every reduction an output element depends on keeps
    the decode step's axis LENGTH — attention scores/softmax/weighted
    sum run over the same gathered ``T = P·block_size`` logical view
    (full page table, not a trimmed span), layer norms over d_model,
    the vocab head over d_model — and every dense op is row-wise over
    a flattened ``[B·W, ...]`` batch. XLA's CPU/TPU reductions split
    lanes by axis length, so equal lengths (plus row-independent
    matmuls) make window row (b, j) BITWISE the decode step this slot
    would have run at position ``pos+j`` — the property that lets a
    spec-decode engine promise greedy output bitwise-identical to the
    target-only engine (pinned in tests/test_spec_decode.py). A
    chunk-prefill-shaped verify could not promise this: its
    concat(context, chunk) softmax axis changes length with the span.
    One backend caveat: the bitwise claim is the GEMM regime's — a
    one-row decode batch ([1, D] @ W) may lower as a matvec whose
    accumulation differs from the window's multi-row gemm at the ulp
    level, so B >= 2 engines carry the pinned guarantee and B = 1 is
    near-exact (greedy ids still agree except on sub-ulp logit ties).

    Window causality: all W rows' k/v are scattered BEFORE the gather,
    and row j masks the view at ``t <= pos+j`` — so row j attends to
    rows < j of its own window plus itself, exactly the sequential
    decode semantics (row i's activations depend only on positions
    <= i, so recomputing them batched is the chunked-prefill argument).
    Rows >= valid (and inactive slots) scatter to the out-of-bounds
    index and are DROPPED, preserving the inactive-row isolation
    contract. Rejected draft rows' k/v DO land in the pool — the
    engine simply rewinds ``pos``, the attend mask hides them, and the
    next window overwrites them (positions above ``pos`` are never
    read, the same discipline as a freed slot's stale bytes).

    Quantized pools and int8 {"q8","scale"} weight trees ride exactly
    as in ``decode_step_paged`` (write-time KV quantization with
    mode="drop" on values AND scales, in-scan weight dequant). The
    pool itself still rides the layer scan as ``xs``/``ys`` here (the
    decode step carries it and updates it in place; ROADMAP Speed 4
    lists this program as left): same values written and read."""
    require_gpt2(cfg, "verify_step_paged (speculative decoding)")
    from paddle_tpu.ops import q8 as ops_q8
    B, W = tokens.shape
    N = B * W
    P = pages.shape[1]
    bs = int(block_size)
    T = P * bs
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    kvd = Hkv * Dh
    M = cache["k"].shape[2]
    quantized = _blocks_quantized(params)
    kvq = pool_kv_dtype(cache, cfg)
    # the verify window runs the XLA gather attention under every mode
    _pallas_policy.note_path("attention", _pallas_policy.PATH_XLA)
    pos = jnp.asarray(pos, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    gpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    flat = tokens.reshape(N)
    x = _embed_rows(params, flat, cfg)                  # [N, D]
    if not cfg.use_rope:
        # clip keeps rows past `valid` (whose writes drop) in range;
        # valid rows clip to themselves, bitwise the decode-step take
        x = x + jnp.take(params["pos"],
                         jnp.minimum(gpos.reshape(N),
                                     params["pos"].shape[0] - 1),
                         axis=0).astype(cfg.dtype)
    rope_tabs = _rope_tables(gpos.reshape(N), Dh, cfg.rope_theta) \
        if cfg.use_rope else None
    # logical->physical map per slot [B, T]: the same rows the decode
    # step gathers by whole pages
    gidx = (pages[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
            ).reshape(B, T)
    # physical write index per window row; rows >= valid and inactive
    # slots aim out of bounds so the scatter drops them
    wpage = jnp.take_along_axis(pages, gpos // bs, axis=1)     # [B, W]
    live = active[:, None] & (jnp.arange(W, dtype=jnp.int32)[None, :]
                              < valid[:, None])
    widx = jnp.where(live, wpage * bs + gpos % bs, M).reshape(N)
    # row (b, j) sees logical positions t <= pos_b + j — the decode
    # mask at that position, so axis length AND boundary match
    attend = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
              <= gpos[:, :, None])                       # [B, W, T]

    def block(x, scanned):
        if kvq != "none":
            w, li, kc, vc, ksc, vsc = scanned
        else:
            w, li, kc, vc = scanned
            ksc = vsc = None
        if quantized:
            w = _live_layer_weights(w, li)
        h = _layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = h @ w["qkv"].astype(h.dtype)              # [N, D + 2*kvd]
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        if cfg.use_rope:
            q = _rope_rows(q.reshape(N, H, Dh), rope_tabs).reshape(
                N, H * Dh)
            k = _rope_rows(k.reshape(N, Hkv, Dh), rope_tabs).reshape(
                N, kvd)
        if kvq != "none":
            kq, ks_new = ops_q8.quantize_kv(k.reshape(N, Hkv, Dh), kvq)
            vq, vs_new = ops_q8.quantize_kv(v.reshape(N, Hkv, Dh), kvq)
            kc = kc.at[:, widx].set(jnp.swapaxes(kq, 0, 1),
                                    mode="drop")
            vc = vc.at[:, widx].set(jnp.swapaxes(vq, 0, 1),
                                    mode="drop")
            ksc = ksc.at[:, widx].set(jnp.swapaxes(ks_new, 0, 1),
                                      mode="drop")
            vsc = vsc.at[:, widx].set(jnp.swapaxes(vs_new, 0, 1),
                                      mode="drop")
        else:
            kc = kc.at[:, widx].set(
                jnp.swapaxes(k.reshape(N, Hkv, Dh), 0,
                             1).astype(kc.dtype), mode="drop")
            vc = vc.at[:, widx].set(
                jnp.swapaxes(v.reshape(N, Hkv, Dh), 0,
                             1).astype(vc.dtype), mode="drop")
        g = H // Hkv
        # head-major gather transposed back to the [B, T, Hkv, ...]
        # logical view (same values/shape as the slot-major path — the
        # verify rows' bitwise contract vs decode_step_paged rides on
        # the arithmetic downstream being identical)
        if kvq != "none":
            kt = ops_q8.dequantize_kv(
                jnp.transpose(jnp.take(kc, gidx, axis=1),
                              (1, 2, 0, 3)),
                jnp.transpose(jnp.take(ksc, gidx, axis=1),
                              (1, 2, 0)), kvq)
            vt = ops_q8.dequantize_kv(
                jnp.transpose(jnp.take(vc, gidx, axis=1),
                              (1, 2, 0, 3)),
                jnp.transpose(jnp.take(vsc, gidx, axis=1),
                              (1, 2, 0)), kvq)
        else:
            kt = jnp.transpose(jnp.take(kc, gidx, axis=1),
                               (1, 2, 0, 3)).astype(jnp.float32)
            vt = jnp.transpose(jnp.take(vc, gidx, axis=1),
                               (1, 2, 0, 3)).astype(jnp.float32)
        q32 = q.reshape(B, W, Hkv, g, Dh).astype(jnp.float32)
        s = jnp.einsum("bwkgd,btkd->bwkgt", q32, kt) / math.sqrt(Dh)
        s = jnp.where(attend[:, :, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bwkgt,btkd->bwkgd", p, vt)
        attn = attn.reshape(N, cfg.d_model).astype(cfg.dtype)
        x = x + attn @ w["attn_out"].astype(attn.dtype)
        h2 = _layer_norm(x, w["ln2"], w["ln2_b"])
        if cfg.moe_experts:
            import dataclasses as _dc

            from paddle_tpu.parallel import moe
            mc = _dc.replace(cfg.moe_cfg(), capacity_factor=float(
                cfg.moe_experts) / cfg.moe_top_k)
            out, _ = moe.moe_ffn(
                {"gate": w["gate"], "w_in": w["moe_w_in"],
                 "w_out": w["moe_w_out"]}, h2, mc)
            x = x + out.astype(x.dtype)
        else:
            ff = jax.nn.gelu(h2 @ w["mlp_in"].astype(h2.dtype))
            x = x + ff @ w["mlp_out"].astype(ff.dtype)
        if kvq != "none":
            return x, (kc, vc, ksc, vsc)
        return x, (kc, vc)

    li = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if kvq != "none":
        x, (kn, vn, ksn, vsn) = jax.lax.scan(
            block, x, (params["blocks"], li, cache["k"], cache["v"],
                       cache["k_scale"], cache["v_scale"]))
        new_cache = {"k": kn, "v": vn, "k_scale": ksn, "v_scale": vsn}
    else:
        x, (kn, vn) = jax.lax.scan(block, x, (params["blocks"], li,
                                              cache["k"], cache["v"]))
        new_cache = {"k": kn, "v": vn}
    x = _layer_norm(x, params["ln_f"], params["ln_f_b"])
    logits = _vocab_logits(x, params)
    return logits.reshape(B, W, cfg.vocab), new_cache


def prefill_into_blocks(params, cache, tokens: jax.Array,
                        length: jax.Array, pages: jax.Array,
                        cfg: TransformerConfig, *, block_size: int,
                        pallas: Optional[str] = None,
                        slot: Optional[jax.Array] = None,
                        return_stats: bool = False):
    """Prefill ONE CHUNK of one request's prompt into its pages of the
    block pool.

    tokens [1, C] is a chunk of the prompt right-padded to a chunk
    bucket C; ``length`` (scalar int32, traced) counts its valid
    tokens; ``pages`` [P] int32 is the PREFIX of the slot's page vector
    covering context + chunk — the chunk occupies the LAST
    ``ceil(C/block_size)`` pages, so the tokens already resident for
    this slot (prefix-cache hits + earlier chunks) number
    ``ctx = (P - ceil(C/block_size)) * block_size``, a STATIC property
    of the argument shapes. The engine keeps ctx block-aligned by
    construction (hits and chunk boundaries are multiples of the chunk
    size). Returns (logits at global position ``ctx + length - 1``
    [1, vocab] fp32, updated pool).

    The layer scan carries NOTHING pool-sized: the context KV is
    gathered ONCE up front ([L, ctx, Hkv, Dh], read-only per-layer
    inputs), each layer attends over ``concat(context, chunk)`` with the
    context fully visible and the chunk causally masked, and the chunk's
    KV lands in the pool post-scan as one masked contiguous-span
    ``dynamic_update_slice`` per chunk page (padded rows write back the
    span's old bytes). Cold prompts (ctx = 0) therefore cost
    the same as a slot prefill of the same bucket instead of dragging
    the whole arena view through every layer, and the per-chunk price
    scales with ``C · (ctx + C)``, not ``C · cache_len``.

    Compile discipline: one compile per (chunk bucket, context pages)
    shape pair — a fixed chunk grid, so a prompt of any length costs
    ``ceil(Tp/chunk)`` compiled calls interleaved with decode steps
    instead of one monolithic stall. Because the engine's chunk grid is
    deterministic and prefix-cache hits are chunk-aligned, a hit replay
    runs bitwise the cold prefill's programs on bitwise the cold
    prefill's values (pinned in tests/test_paged_engine.py).

    Quantized pools (``init_block_pool(kv_dtype=...)``): the context
    gathers int8/int4 rows + their scales (1 byte/elt of history
    through the scan) and dequantizes in the consumer; the chunk's own
    KV is quantized at write time, per (layer, token, head), with the
    same masked-span RMW covering values AND scales. In-chunk attention
    uses the exact (pre-quantization) chunk values — only what decode
    reads LATER is rounded, matching the decode-write discipline.

    ``pallas`` resolves the ``PADDLE_TPU_PALLAS`` policy: when on, each
    layer's chunk attention runs ``ops.pallas.prefill.flash_chunk_prefill``
    (pages resolved inside the kernel, context streamed from the pool
    with the dequant fused, one exact softmax over the concat — no
    gathered context or [C, S+C] score tensor in HBM) and the span
    writes run the ``paged_span_write`` kernel (block-mapped through
    the page vector via scalar prefetch). The XLA path above is what
    ``off`` selects, and the numerics reference."""
    if cfg.skeleton != "gpt2":
        # ``slot``: whose rows beside the pages the chunk reads and
        # writes, for a skeleton that keeps any
        whose = ()
        if skeleton_module(cfg).SLOT_STATE:
            if slot is None:
                raise ValueError(f"prefill_into_blocks(slot=...): a "
                                 f"{cfg.skeleton} chunk updates its "
                                 f"slot's recurrent rows")
            whose = (slot,)
        return _skeleton_step(params, cache, cfg, pallas, "prefill_chunk",
                              tokens, length, pages, *whose,
                              block_size=block_size,
                              return_stats=return_stats)
    from paddle_tpu.ops import q8 as ops_q8
    if tokens.shape[0] != 1:
        raise ValueError(f"prefill_into_blocks takes one request "
                         f"([1, C] tokens), got {tokens.shape}")
    C = tokens.shape[1]
    bs = int(block_size)
    P = pages.shape[0]
    pc = -(-C // bs)                    # pages the chunk itself spans
    S = (P - pc) * bs                   # static context length
    if S < 0:
        raise ValueError(f"pages vector ({P}) shorter than the chunk's "
                         f"own span ({pc} pages for C={C})")
    H, Dh = cfg.n_heads, cfg.head_dim
    Hkv = cfg.kv_heads
    kvd = Hkv * Dh
    kvq = pool_kv_dtype(cache, cfg)
    mode = _pallas_policy.pallas_mode(pallas)
    # only "off" takes the XLA path (see decode_step_paged)
    use_pallas = mode != "off"
    for site in ("attention", "span_write"):
        _pallas_policy.note_path(site, _pallas_policy.kernel_path(mode))
    length = jnp.asarray(length, jnp.int32)
    pages = jnp.asarray(pages, jnp.int32)
    gpos = S + jnp.arange(C, dtype=jnp.int32)            # [C] global
    x = jnp.take(params["embed"], tokens[0], axis=0).astype(cfg.dtype)
    if not cfg.use_rope:
        # clip keeps padded rows (whose writes drop anyway) in range
        x = x + jnp.take(params["pos"],
                         jnp.minimum(gpos, params["pos"].shape[0] - 1),
                         axis=0).astype(cfg.dtype)
    rope_tabs = _rope_tables(gpos, Dh, cfg.rope_theta) \
        if cfg.use_rope else None
    valid = jnp.arange(C, dtype=jnp.int32) < length
    if use_pallas:
        # the kernel resolves the page walk itself: the pool rides the
        # layer scan as xs (a per-layer view, no gather/copy) and only
        # the slot's MAPPED context blocks ever stream into VMEM
        if kvq != "none":
            ctx_xs = (cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"])
        else:
            ctx_xs = (cache["k"], cache["v"])
    else:
        # context gather (once, all layers): every context position is
        # real (ctx tokens were written by hits/earlier chunks), no
        # mask needed. Whole pages are gathered by (layer, head, page)
        # off the flat rows, then the view transposes to the
        # position-leading [L, S, Hkv, ...] shape the scan body reads
        groups = jnp.arange(cfg.n_layers * Hkv, dtype=jnp.int32).reshape(
            cfg.n_layers, Hkv)

        def _ctx(n):
            tab = cache[n]
            g = _gather_pages(
                tab.reshape((-1,) + tab.shape[3:]), groups,
                pages[:P - pc], bs, tab.shape[2] // bs)  # [L,Hkv,S,..]
            perm = (0, 2, 1) + tuple(range(3, g.ndim))
            return jnp.transpose(g, perm)          # [L, S, Hkv, ...]

        ctx_xs = tuple(_ctx(n)
                       for n in (("k", "v", "k_scale", "v_scale")
                                 if kvq != "none" else ("k", "v")))
    # [C, S+C] mask: context fully visible, chunk causally masked
    attend = jnp.concatenate(
        [jnp.ones((C, S), bool),
         jnp.tril(jnp.ones((C, C), bool))], axis=1)

    def block(x, scanned):
        w = scanned[0]
        ctx = scanned[1:]       # per-layer pool view (pallas) or the
        #                         gathered [S, ...] context (XLA)
        h = _layer_norm(x, w["ln1"], w["ln1_b"])
        qkv = h @ w["qkv"].astype(h.dtype)   # [C, D + 2*kvd]
        q, k, v = jnp.split(qkv, [H * Dh, H * Dh + kvd], axis=-1)
        if cfg.use_rope:
            q = _rope_rows(q.reshape(C, H, Dh), rope_tabs).reshape(
                C, H * Dh)
            k = _rope_rows(k.reshape(C, Hkv, Dh), rope_tabs).reshape(
                C, kvd)
        kck = k.reshape(C, Hkv, Dh)
        vck = v.reshape(C, Hkv, Dh)
        g = H // Hkv
        if use_pallas:
            from paddle_tpu.ops.pallas import prefill as _pp
            kc, vc = ctx[0], ctx[1]
            ksc, vsc = (ctx[2], ctx[3]) if kvq != "none" else (None,
                                                               None)
            attn = _pp.flash_chunk_prefill(
                q.reshape(C, Hkv, g, Dh), kck, vck, kc, vc,
                pages[:P - pc], block_size=bs, k_scale=ksc,
                v_scale=vsc, kv_dtype=kvq,
                interpret=(mode == "interpret"))
            attn = attn.reshape(C, Hkv, g, Dh)
        else:
            if kvq != "none":
                ck = ops_q8.dequantize_kv(ctx[0], ctx[2], kvq)
                cv = ops_q8.dequantize_kv(ctx[1], ctx[3], kvq)
            else:
                ck = ctx[0].astype(jnp.float32)
                cv = ctx[1].astype(jnp.float32)
            kall = jnp.concatenate([ck, kck.astype(jnp.float32)],
                                   axis=0)
            vall = jnp.concatenate([cv, vck.astype(jnp.float32)],
                                   axis=0)
            q32 = q.reshape(C, Hkv, g, Dh).astype(jnp.float32)
            s = jnp.einsum("ckgd,tkd->ckgt", q32, kall) / math.sqrt(Dh)
            s = jnp.where(attend[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("ckgt,tkd->ckgd", p, vall)
        attn = attn.reshape(C, cfg.d_model).astype(cfg.dtype)
        x = x + attn @ w["attn_out"].astype(attn.dtype)
        h2 = _layer_norm(x, w["ln2"], w["ln2_b"])
        if cfg.moe_experts:
            import dataclasses as _dc

            from paddle_tpu.parallel import moe
            # inference capacity (cf = E/k): prefill must not drop
            # tokens the way Switch training capacity does
            mc = _dc.replace(cfg.moe_cfg(), capacity_factor=float(
                cfg.moe_experts) / cfg.moe_top_k)
            out, _ = moe.moe_ffn(
                {"gate": w["gate"], "w_in": w["moe_w_in"],
                 "w_out": w["moe_w_out"]}, h2, mc)
            x = x + out.astype(x.dtype)
        else:
            ff = jax.nn.gelu(h2 @ w["mlp_in"].astype(h2.dtype))
            x = x + ff @ w["mlp_out"].astype(ff.dtype)
        if kvq != "none":
            # fp values out of the scan; quantized post-scan in one
            # pass so values and scales stack [L, C, ...] together
            return x, (kck, vck)
        return x, (kck.astype(cache["k"].dtype),
                   vck.astype(cache["v"].dtype))

    x, (ks, vs) = jax.lax.scan(block, x, (params["blocks"],) + ctx_xs)
    # pool write for the whole chunk, all layers: the scan stacks the
    # spans position-major ([L, C, Hkv, Dh]); quantization (per
    # (layer, token, head)) runs on that layout — the same values as
    # ever — and the spans then transpose to the pool's head-major
    # [L, Hkv, C, ...] for one masked read-modify-write of the
    # CONTIGUOUS bs-token span per chunk page — dynamic_update_slice,
    # not a scatter (a [C]-index scatter into the flat pool is several
    # ms slower per call on CPU). Padded rows write back the span's
    # old bytes, the RMW equivalent of the scatter's mode="drop".
    if kvq != "none":
        kq, kscl = ops_q8.quantize_kv(ks, kvq)   # [L,C,Hkv,Dh'], [L,C,Hkv]
        vq, vscl = ops_q8.quantize_kv(vs, kvq)
        spans = {"k": kq, "v": vq, "k_scale": kscl, "v_scale": vscl}
    else:
        spans = {"k": ks, "v": vs}
    spans = {n: jnp.transpose(a, (0, 2, 1) + tuple(range(3, a.ndim)))
             for n, a in spans.items()}          # [L, Hkv, C, ...]
    pad = pc * bs - C
    if pad:
        spans = {n: jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),)
                            * (a.ndim - 3)) for n, a in spans.items()}
        vfull = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    else:
        vfull = valid
    new_cache = dict(cache)
    tail_pages = pages[P - pc:]
    if use_pallas:
        from paddle_tpu.ops.pallas import prefill as _pallas_prefill
        new_cache.update(_pallas_prefill.paged_span_write(
            {n: cache[n] for n in spans}, spans, tail_pages, vfull,
            block_size=bs, interpret=(mode == "interpret")))
    else:
        for j in range(pc):
            dst = tail_pages[j] * bs
            for n, a in spans.items():
                vmask = vfull[j * bs:(j + 1) * bs].reshape(
                    (1, 1, bs) + (1,) * (a.ndim - 3))
                aj = a[:, :, j * bs:(j + 1) * bs]
                old = jax.lax.dynamic_slice(
                    new_cache[n], (0, 0, dst) + (0,) * (a.ndim - 3),
                    a.shape[:2] + (bs,) + a.shape[3:])
                new_cache[n] = jax.lax.dynamic_update_slice(
                    new_cache[n], jnp.where(vmask, aj, old),
                    (0, 0, dst) + (0,) * (a.ndim - 3))
    # only the last VALID chunk position feeds the vocab head
    x = jnp.take(x, jnp.reshape(jnp.maximum(length - 1, 0), (1,)), axis=0)
    x = _layer_norm(x, params["ln_f"], params["ln_f_b"])
    logits = jnp.einsum("td,vd->tv", x.astype(jnp.float32),
                        params["embed"].astype(jnp.float32))
    return logits, new_cache


def generate(params, prompt: jax.Array, cfg: TransformerConfig, *,
             max_new: int, temperature: float = 0.0,
             key: Optional[jax.Array] = None,
             mesh: Optional[Mesh] = None) -> jax.Array:
    """Autoregressive generation: prompt [B, Tp] → [B, Tp + max_new].

    Batched prefill fills the KV cache in one forward pass, then a
    ``lax.scan`` replays the compiled single-token step ``max_new`` times
    — the TPU-idiomatic decode loop (no per-step retracing, no growing
    shapes). temperature=0 is greedy argmax; otherwise categorical
    sampling with ``key``.

    ``params`` may contain int8-quantized weights
    (io/lm_serving.quantize_lm_params {"q8","scale"} nodes): they are
    threaded through the SCAN CARRY and dequantized inside each step, so
    XLA cannot hoist the dequant out of the loop — every decoded token
    reads the weights from HBM at 1 byte/elt with the dequant multiply
    fused into the matmul operand reads (decode is weight-read-bound;
    a loop-invariant dequant would silently restore 4-byte reads)."""
    require_gpt2(cfg, "generate (the lockstep path)")
    from paddle_tpu.ops import q8 as ops_q8

    B, Tp = prompt.shape
    if max_new < 1:
        raise ValueError(f"generate: max_new must be >= 1, got {max_new}")
    cache_len = Tp + max_new
    if cache_len > cfg.max_len:
        raise ValueError(f"generate: {cache_len} positions exceed "
                         f"cfg.max_len={cfg.max_len}")
    if temperature > 0 and key is None:
        raise ValueError("generate: sampling (temperature>0) needs a key")
    quantized = any(ops_q8.is_quantized_weight(n) for n in
                    jax.tree_util.tree_leaves(
                        params, is_leaf=ops_q8.is_quantized_weight))
    live = ops_q8.dequantize_tree(params) if quantized else params
    logits, cache = prefill(live, prompt, cfg, cache_len, mesh=mesh)
    del live
    key = key if key is not None else jax.random.PRNGKey(0)

    def sample(logits, k):
        if temperature > 0:
            return jax.random.categorical(k, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    key, k0 = jax.random.split(key)
    first = sample(logits, k0).astype(jnp.int32)

    # one step function serves both paths: quantized weights ride the
    # carry as `extra` and are rebuilt INSIDE the body behind an
    # optimization barrier — XLA's while-loop simplifier + LICM would
    # otherwise hoist the loop-invariant dequant and materialize fp32
    # weights once, silently restoring 4-byte weight reads per token
    extra0 = (params,) if quantized else ()

    def step(carry, i):
        extra, cache, tok, key = carry
        key, ks = jax.random.split(key)
        if quantized:
            # three hoist defenses so the dequant stays inside the loop
            # (int8 weight reads per token, the point of the feature):
            # the weights ride the CARRY, sit behind an optimization
            # BARRIER, and the scales fold in a float zero derived from
            # the loop counter (loop-variant by data dependence). The
            # CPU backend deletes barriers and folds the zero, hoisting
            # anyway (one fp32 materialization per generate call —
            # amortized over max_new tokens, so never WORSE than fp32
            # decode); whether TPU keeps the in-loop int8 reads is an
            # on-chip measurement (queue_r4d [3d]). The exported
            # LMServer path dequantizes per HOST call and cannot be
            # hoisted regardless.
            p8 = jax.lax.optimization_barrier(extra[0])
            i_eps = i.astype(jnp.float32) * 0.0

            def _leaf(n):
                if ops_q8.is_quantized_weight(n):
                    return {"q8": n["q8"], "scale": n["scale"] + i_eps}
                return n

            p = ops_q8.dequantize_tree(jax.tree_util.tree_map(
                _leaf, p8, is_leaf=ops_q8.is_quantized_weight))
        else:
            p = params
        logits, cache = decode_step(p, cache, tok, Tp + i, cfg)
        nxt = sample(logits, ks).astype(jnp.int32)
        return (extra, cache, nxt, key), tok

    (_, _, last, _), toks = jax.lax.scan(
        step, (extra0, cache, first, key),
        jnp.arange(max_new - 1, dtype=jnp.int32))
    generated = jnp.concatenate(
        [jnp.swapaxes(toks, 0, 1), last[:, None]], axis=1) \
        if max_new > 1 else first[:, None]
    return jnp.concatenate([prompt, generated], axis=1)


def beam_search(params, prompt: jax.Array, cfg: TransformerConfig, *,
                max_new: int, beam_size: int = 4,
                mesh: Optional[Mesh] = None) -> tuple:
    """Beam-search decoding over the KV cache: prompt [B, Tp] →
    (tokens [B, beam, Tp + max_new], scores [B, beam], best first.

    The transformer-flagship analog of the recurrent DSL's beam_search
    (recurrent.py; reference: RecurrentGradientMachine generation,
    GradientMachine::eval beam path). The cache carries B·beam hypotheses
    flattened on the batch axis; each step scores beam·vocab expansions,
    keeps the top ``beam_size``, and GATHERS the cache rows of the
    surviving hypotheses — all static shapes under one lax.scan. (No
    length penalty: all hypotheses here have identical length max_new,
    so any GNMT-style α rescales every score equally; EOS-terminated
    variable-length decoding is the recurrent DSL's beam_search domain.)"""
    require_gpt2(cfg, "beam_search (the lockstep path)")
    B, Tp = prompt.shape
    if max_new < 1:
        raise ValueError(f"beam_search: max_new must be >= 1, got {max_new}")
    cache_len = Tp + max_new
    if cache_len > cfg.max_len:
        raise ValueError(f"beam_search: {cache_len} positions exceed "
                         f"cfg.max_len={cfg.max_len}")
    if beam_size < 1 or beam_size > cfg.vocab:
        raise ValueError(f"beam_search: beam_size {beam_size} must be in "
                         f"[1, vocab={cfg.vocab}]")
    K, V = beam_size, cfg.vocab

    logits, cache = prefill(params, prompt, cfg, cache_len, mesh=mesh)
    logp0 = jax.nn.log_softmax(logits, axis=-1)            # [B, V]
    top0, tok0 = jax.lax.top_k(logp0, K)                   # [B, K]
    # replicate the cache per beam: [L, B, T, H, Dh] -> [L, B*K, T, H, Dh]
    cache = jax.tree_util.tree_map(lambda c: jnp.repeat(c, K, axis=1),
                                   cache)
    scores = top0                                          # [B, K]
    toks = tok0.astype(jnp.int32)                          # [B, K] step-0 pick
    batch_base = (jnp.arange(B, dtype=jnp.int32)[:, None] * K)  # [B, 1]

    def step(carry, i):
        cache, toks, scores = carry
        flat = toks.reshape(B * K)
        logits, cache = decode_step(params, cache, flat, Tp + i, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, V)
        total = scores[:, :, None] + logp                  # [B, K, V]
        top, idx = jax.lax.top_k(total.reshape(B, K * V), K)
        beam_src = (idx // V).astype(jnp.int32)            # [B, K]
        nxt = (idx % V).astype(jnp.int32)
        # reindex the cache rows to the surviving hypotheses
        flat_src = (batch_base + beam_src).reshape(B * K)
        cache = jax.tree_util.tree_map(
            lambda c: jnp.take(c, flat_src, axis=1), cache)
        return (cache, nxt, top), (toks, beam_src)

    (cache, last, scores), (hist_toks, hist_src) = jax.lax.scan(
        step, (cache, toks, scores),
        jnp.arange(max_new - 1, dtype=jnp.int32))

    # backtrack: hist_toks[i] holds position-i tokens in the beam order
    # BEFORE step i's reshuffle (O_i) while hist_src[i] maps the
    # post-reshuffle order O_{i+1} back to O_i — so the survivor pointer
    # must step through src FIRST, then gather the token row
    def back(carry, xs):
        ptr = carry                                        # [B, K] in O_{i+1}
        t, src = xs
        ptr = jnp.take_along_axis(src, ptr, axis=1)        # now in O_i
        tok = jnp.take_along_axis(t, ptr, axis=1)
        return ptr, tok

    ptr0 = jnp.tile(jnp.arange(K, dtype=jnp.int32)[None], (B, 1))
    _, rev = jax.lax.scan(back, ptr0, (hist_toks, hist_src), reverse=True)
    seq = jnp.concatenate([jnp.moveaxis(rev, 0, 2), last[:, :, None]],
                          axis=2) if max_new > 1 else toks[:, :, None]
    prompt_rep = jnp.repeat(prompt[:, None, :], K, axis=1)
    out = jnp.concatenate([prompt_rep, seq], axis=2)       # [B, K, Tp+new]
    return out, scores

"""Plain reference: the GLM-4.7-Flash block (``model_type``
``glm4_moe_lite``): latent attention in every layer, a leading dense
SwiGLU layer, then sparse experts routed by sigmoid scores plus a
selection bias with an ungated shared expert, and the
multi-token-prediction module; float32.

Written from the layer equations of the published ``config.json``
(``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``first_k_dense_replace``,
``n_routed_experts``, ``topk_method`` ``noaux_tc``, ``norm_topk_prob``,
``routed_scaling_factor``, ``n_group`` = ``topk_group`` = 1,
``num_nextn_predict_layers``):

    N(x)  = x / sqrt(mean(x^2) + eps) * w
    x     = x + MLA(N(x));  x = x + FFN(N(x))
    logit = N(x_L) W_head^T                                  untied head

Latent attention (H heads; ranks rq, rkv; head widths dn, dr, dv):
    c_q = N_rq(x W_qa);  [q_nope_i | q_rope_i] = c_q W_qb
    [c_kv | k_rope] = x W_kva;  c_kv = N_rkv(c_kv)
    k_rope = RoPE(k_rope), one for all heads;  q_rope_i = RoPE(q_rope_i)
    (all dr dims rotate, halves paired, inv_freq_j = theta^(-2j/dr))
    [k_nope_i | v_i] = c_kv W_kvb,i
    s_ij = (q_nope_i . k_nope_ij + q_rope_i . k_rope_j) / sqrt(dn + dr)
    o_i = softmax_causal(s_i) v_i;  out = [o_1 .. o_H] W_o
  Every position's keys and values are UP-PROJECTED here (the published
  form); the program's decode path absorbs W_kvb into the query and the
  output instead, so the comparison is between the two forms.
FFN, layers < first_k_dense_replace: W_down (silu(x W_gate) * x W_up).
FFN, the others: s = sigmoid(x W_g) over ALL experts (float32);
    T = top_k(s + b);  g_e = scale * s_e / sum_{T} s  (e in T)
    y = sum_{e in T} g_e SwiGLU_e(x) + SwiGLU_shared(x)  (shared ungated)
  Only the experts ``held = (first, count)`` are summed (all of them
  in the configuration the benchmark runs).
MTP, one module: h' = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)] (h_i the last
    layer's output before the final norm) -> one block of the sparse
    kind at positions 0 .. T-2 -> N_out -> the model's head: the logits
    of t_{i+2}.

Assumed: the rotary pairing (halves, as above; an interleaved pairing
is this one under a fixed permutation of W_qb's and W_kva's rope
columns); the prediction module's block attends at the positions of
its h_i.

One sequence at a time, layer by layer, attention in blocks of query
rows and experts as a loop over the held ones in blocks of token rows,
so that 13.8k positions fit; no cache, no kernels, nothing imported
from the program. ``precision`` 'f32' is the reference proper (every
matmul at ``highest``); 'fp8' and 'int8' are the controls of the
precision: both operands of every linear map (the float32 router
apart) rounded to the lower format, per-row scales. ``fault`` plants
one of ``FAULTS``, the mistake a new part would make, for the limits'
controls.
"""

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ROWS = 512          # rows of a block (attention queries, expert tokens)
FAULTS = ("rope_key_unrotated",     # the cached key stored unrotated
          "bias_in_weights",        # expert weights taken from s + b
          "scale_dropped",          # routed_scaling_factor left out
          "latent_norm_skipped")    # c_kv cached without its norm


def dims_of(config: dict) -> dict:
    """The published ``config.json`` keys under the names used here."""
    m = config
    first = int(m.get("expert_first", 0))
    return {
        "D": m["hidden_size"], "L": m["num_hidden_layers"],
        "Ld": m["first_k_dense_replace"], "H": m["num_attention_heads"],
        "rq": m["q_lora_rank"], "rkv": m["kv_lora_rank"],
        "dn": m["qk_nope_head_dim"], "dr": m["qk_rope_head_dim"],
        "dv": m["v_head_dim"], "theta": float(m["rope_theta"]),
        "eps": m["rms_norm_eps"], "E": m["n_routed_experts"],
        "top_k": m["num_experts_per_tok"],
        "scale": float(m["routed_scaling_factor"]),
        "held": (first, int(m.get("num_experts_held",
                                  m["n_routed_experts"]))),
        "V": m["vocab_size"]}


def _round_to(x, fmt: str, axis: int):
    if fmt == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if fmt == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if fmt == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(f"unknown precision {fmt!r}")


def _linear(x, w, fmt):
    return jnp.matmul(_round_to(x, fmt, -1),
                      _round_to(w.astype(jnp.float32), fmt, 0), precision=HI)


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotary(x, theta):
    """x [T, H, dr]: every dim rotates, halves paired."""
    T, _, dr = x.shape
    half = dr // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=("d", "fmt", "fault"))
def _attention(x, w, *, d, fmt, fault):
    T = x.shape[0]
    H, rkv, dn, dr, dv = d["H"], d["rkv"], d["dn"], d["dr"], d["dv"]
    h = _norm(x, w["ln1"], d["eps"])
    cq = _norm(_linear(h, w["q_a"], fmt), w["q_a_norm"], d["eps"])
    q = _linear(cq, w["q_b"], fmt).reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rotary(q[..., dn:], d["theta"])
    kv = _linear(h, w["kv_a"], fmt)
    c = kv[:, :rkv]
    if fault != "latent_norm_skipped":
        c = _norm(c, w["kv_a_norm"], d["eps"])
    k_rope = kv[:, None, rkv:]
    if fault != "rope_key_unrotated":
        k_rope = _rotary(k_rope, d["theta"])
    up = _linear(c, w["kv_b"], fmt).reshape(T, H, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    outs = []
    for r0 in range(0, T, ROWS):                # blocks of query rows
        s = (jnp.einsum("thd,shd->hts", q_nope[r0:r0 + ROWS], k_nope,
                        precision=HI)
             + jnp.einsum("thd,sd->hts", q_rope[r0:r0 + ROWS],
                          k_rope[:, 0], precision=HI)) / math.sqrt(dn + dr)
        seen = (r0 + jnp.arange(s.shape[1]))[:, None] \
            >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    a = jnp.concatenate(outs, axis=0)
    return x + _linear(a.reshape(T, H * dv), w["o"], fmt)


@functools.partial(jax.jit, static_argnames=("eps", "fmt"))
def _dense(x, w, *, eps, fmt):
    h = _norm(x, w["ln2"], eps)
    return x + _linear(_silu(_linear(h, w["gate"], fmt))
                       * _linear(h, w["up"], fmt), w["down"], fmt)


@functools.partial(jax.jit, static_argnames=("d", "fmt", "held", "fault"))
def _experts(x, w, *, d, fmt, held, fault):
    """x [T, D] (T <= ROWS) -> x + the held experts' part + the shared
    expert."""
    E, k = d["E"], d["top_k"]
    first, count = held
    h = _norm(x, w["ln2"], d["eps"])
    m = w["moe"]
    s = jax.nn.sigmoid(jnp.matmul(h, m["router"].astype(jnp.float32),
                                  precision=HI))
    biased = s + m["router_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(biased, k)
    top = jnp.take_along_axis(biased if fault == "bias_in_weights" else s,
                              idx, axis=-1)
    top = top / jnp.sum(top, -1, keepdims=True)
    if fault != "scale_dropped":
        top = top * d["scale"]
    gates = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32)
                    * top[..., None], axis=1)                   # [T, E]
    gates = gates[:, first:first + count]

    def one(y, e):
        w1, w3, w2, ge = e
        out = _linear(_silu(_linear(h, w1, fmt)) * _linear(h, w3, fmt),
                      w2, fmt)
        return y + ge[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (m["w1"], m["w3"], m["w2"], gates.T))
    shared = _linear(_silu(_linear(h, m["s_w1"], fmt))
                     * _linear(h, m["s_w3"], fmt), m["s_w2"], fmt)
    return x + y + shared


@functools.partial(jax.jit, static_argnames=("eps", "fmt"))
def _head(x, gf, head, *, eps, fmt):
    return _linear(_norm(x, gf, eps), head.astype(jnp.float32).T, fmt)


def _layer(tree, idx):
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _moe(x, w, d, fmt, held, fault):
    return jnp.concatenate(
        [_experts(x[r0:r0 + ROWS], w, d=d, fmt=fmt, held=held, fault=fault)
         for r0 in range(0, x.shape[0], ROWS)], axis=0)


class _Dims(tuple):
    """The dims as a hashable static argument that still reads by key."""

    def __getitem__(self, key):
        if isinstance(key, str):
            return dict(self)[key]
        return tuple.__getitem__(self, key)


def _static(d: dict):
    return _Dims(sorted((k, v) for k, v in d.items() if k != "held"))


def hidden_states(weights: dict, tokens, *, dims: dict,
                  precision: str = "f32", fault: str = None):
    """The residual stream [T, D] after the last layer, before the
    final norm."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    tokens = jnp.asarray(tokens, jnp.int32)
    held = tuple(dims["held"])
    d = _static(dims)
    x = weights["embed"][tokens].astype(jnp.float32)
    for i in range(dims["Ld"]):
        w = _layer(weights["dense"], i)
        x = _attention(x, w, d=d, fmt=precision, fault=fault)
        x = _dense(x, w, eps=dims["eps"], fmt=precision)
    for i in range(dims["L"] - dims["Ld"]):
        w = _layer(weights["sparse"], i)
        x = _attention(x, w, d=d, fmt=precision, fault=fault)
        x = _moe(x, w, d, precision, held, fault)
    return x


def logits_at(weights: dict, tokens, rows, *, dims: dict,
              precision: str = "f32", fault: str = None):
    """float32 logits [len(rows), V] of the sequence ``tokens`` (1-D,
    padded freely at the END: every layer is causal) at positions
    ``rows``."""
    x = hidden_states(weights, tokens, dims=dims, precision=precision,
                      fault=fault)
    return _head(x[jnp.asarray(rows, jnp.int32)], weights["ln_f"],
                 weights["head"], eps=dims["eps"], fmt=precision)


def mtp_logits_at(weights: dict, tokens, rows, *, dims: dict,
                  precision: str = "f32"):
    """The prediction module's float32 logits [len(rows), V]: row i
    scores token i + 2 from the model's h_i and the embedding of token
    i + 1 (``rows`` within 0 .. T-2)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    m = weights["mtp"]
    d = _static(dims)
    eps = dims["eps"]
    h = hidden_states(weights, tokens, dims=dims, precision=precision)
    e = weights["embed"][tokens[1:]].astype(jnp.float32)
    x = _linear(jnp.concatenate([_norm(e, m["enorm"], eps),
                                 _norm(h[:-1], m["hnorm"], eps)], axis=-1),
                m["eh_proj"], precision)
    for i in range(m["block"]["ln1"].shape[0]):
        w = _layer(m["block"], i)
        x = _attention(x, w, d=d, fmt=precision, fault=None)
        x = _moe(x, w, d, precision, tuple(dims["held"]), None)
    return _head(x[jnp.asarray(rows, jnp.int32)], m["norm"],
                 weights["head"], eps=eps, fmt=precision)

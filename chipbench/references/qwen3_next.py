"""Plain reference: the Qwen3-Next block (Gated DeltaNet layers, gated
attention, sparse experts with a shared expert), float32.

Written from the layer equations of ``Qwen3-Next-80B-A3B``'s published
``config.json`` and its public modelling code; layer ``i`` of ``n`` is
full attention if ``(i + 1) % full_attention_interval == 0``, else Gated
DeltaNet.

    N(x)  = x / sqrt(mean(x^2) + eps) * (1 + w)            zero-centred
    x     = x + mixer(N(x));  x = x + moe(N(x))
    logit = N(x_L) W_head^T                                  untied head

Gated attention (H heads, Hkv KV heads, Dh):
    [q | gate] = x Wq per head; k = x Wk; v = x Wv
    q, k = N_Dh(q), N_Dh(k); rotary on the first rot = Dh *
    partial_rotary_factor dims (halves paired, inv_freq_j =
    theta^(-2j/rot)); out = (softmax(causal(q k^T / sqrt(Dh))) v *
    sigmoid(gate)) Wo
Gated DeltaNet (Hk key heads, Hv value heads, dk, dv):
    [q, k, v, z] = x W_qkvz, [b, a] = x W_ba (columns grouped per key
    head); [q, k, v] = silu(causal depthwise conv1d, kernel K, no bias)
    beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias)
    q, k = x / sqrt(sum x^2 + 1e-6) per head; q = q / sqrt(dk); key head
    j // (Hv / Hk) serves value head j
    S = exp(g_t) S; d = beta_t (v_t - S^T k_t); S = S + k_t d^T;
    o_t = S^T q_t; out = (o_t / sqrt(mean o_t^2 + eps) * w * silu(z_t)) W_out
Experts: p = softmax(x Wg) over ALL experts; top k; weights renormalised
    over the k; expert e: (silu(x W1_e) * (x W3_e)) W2_e; plus
    sigmoid(x . w_s) * shared(x). Only the experts ``held = (first,
    count)`` are summed: an assignment to an absent expert adds nothing
    (its weight still took part in the renormalisation), as on one chip
    of the deployment the configuration states.

Departures: the multi-token-prediction module is not modelled (the
config has no key for it). Assumed: the per-key-head grouping of
``W_qkvz`` / ``W_ba`` columns, as the public modelling code has it.

One sequence at a time, layer by layer, the recurrence as a plain scan
over tokens, the experts as a loop over the held ones, attention and
experts in blocks of rows so that a few thousand tokens fit; no cache,
no kernels, nothing imported from the program. ``precision`` 'f32' is
the reference proper (every matmul at ``highest``); 'fp8' and 'int8' are
the controls: both operands of every linear map (the float32 router
apart) rounded to the lower format, per-row scales.
"""

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
ROWS = 1024         # rows of a block (attention queries, expert tokens)


def dims_of(config: dict) -> dict:
    """The published ``config.json`` keys under the names used here."""
    m = config
    first = int(m.get("expert_first", 0))
    return {
        "D": m["hidden_size"], "L": m["num_hidden_layers"],
        "interval": m["full_attention_interval"],
        "H": m["num_attention_heads"], "Hkv": m["num_key_value_heads"],
        "Dh": m["head_dim"],
        "rot": int(m["head_dim"] * m["partial_rotary_factor"]),
        "theta": float(m["rope_theta"]), "eps": m["rms_norm_eps"],
        "Hk": m["linear_num_key_heads"], "Hv": m["linear_num_value_heads"],
        "dk": m["linear_key_head_dim"], "dv": m["linear_value_head_dim"],
        "K": m["linear_conv_kernel_dim"],
        "E": m["num_experts"], "top_k": m["num_experts_per_tok"],
        "held": (first, int(m.get("num_experts_held", m["num_experts"]))),
        "F": m["moe_intermediate_size"],
        "Fs": m["shared_expert_intermediate_size"],
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


def _norm(x, w, eps, centred=True):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotary(x, rot, theta):
    """x [T, H, Dh]: the first ``rot`` dims rotate, halves paired."""
    T = x.shape[0]
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _attention(x, w, *, d, fmt):
    T = x.shape[0]
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    h = _norm(x, w["ln1"], d["eps"])
    qg = _linear(h, w["q"], fmt).reshape(T, H, 2 * Dh)
    q, gate = qg[..., :Dh], qg[..., Dh:]
    k = _linear(h, w["k"], fmt).reshape(T, Hkv, Dh)
    v = _linear(h, w["v"], fmt).reshape(T, Hkv, Dh)
    q = _rotary(_norm(q, w["q_norm"], d["eps"]), d["rot"], d["theta"])
    k = _rotary(_norm(k, w["k_norm"], d["eps"]), d["rot"], d["theta"])
    grp = H // Hkv
    k = jnp.repeat(k, grp, axis=1)
    v = jnp.repeat(v, grp, axis=1)
    outs = []
    for r0 in range(0, T, ROWS):                # blocks of query rows
        qb = q[r0:r0 + ROWS]
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HI) / math.sqrt(Dh)
        seen = (r0 + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v, precision=HI))
    a = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(gate)
    return x + _linear(a.reshape(T, H * Dh), w["o"], fmt)


@functools.partial(jax.jit, static_argnames=("d", "fmt"))
def _delta_net(x, w, *, d, fmt):
    T = x.shape[0]
    Hk, Hv, dk, dv, K = d["Hk"], d["Hv"], d["dk"], d["dv"], d["K"]
    r = Hv // Hk
    h = _norm(x, w["ln1"], d["eps"])
    p = _linear(h, w["in_qkvz"], fmt).reshape(T, Hk, 2 * dk + 2 * r * dv)
    q, k = p[..., :dk], p[..., dk:2 * dk]
    v = p[..., 2 * dk:2 * dk + r * dv]
    z = p[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    ba = _linear(h, w["in_ba"], fmt).reshape(T, Hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(T, Hv))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(T, Hv) + w["dt_bias"])
    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], axis=-1)        # [T, ch]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, mixed.shape[1]), jnp.float32), mixed], axis=0)
    cw = w["conv"].astype(jnp.float32)                          # [ch, K]
    u = _silu(sum(padded[j:j + T] * cw[:, j] for j in range(K)))
    key = Hk * dk

    def l2(t):
        return t / jnp.sqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                            + 1e-6)

    q = jnp.repeat(l2(u[:, :key].reshape(T, Hk, dk)) / math.sqrt(dk),
                   r, axis=1)
    k = jnp.repeat(l2(u[:, key:2 * key].reshape(T, Hk, dk)), r, axis=1)
    v = u[:, 2 * key:].reshape(T, Hv, dv)

    def step(S, t):                                  # S [Hv, dk, dv]
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        dlt = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                               precision=HI))
        S = S + k_t[:, :, None] * dlt[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    y = _norm(o, w["norm"], d["eps"], centred=False) * _silu(z)
    return x + _linear(y.reshape(T, Hv * dv), w["out"], fmt)


@functools.partial(jax.jit, static_argnames=("d", "fmt", "held"))
def _experts(x, w, *, d, fmt, held):
    """x [T, D] (T <= ROWS) -> x + the held experts' part + the shared
    expert."""
    E, k = d["E"], d["top_k"]
    first, count = held
    h = _norm(x, w["ln2"], d["eps"])
    m = w["moe"]
    p = jax.nn.softmax(jnp.matmul(h, m["router"].astype(jnp.float32),
                                  precision=HI), axis=-1)
    top, idx = jax.lax.top_k(p, k)
    top = top / jnp.sum(top, -1, keepdims=True)
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
    sg = jax.nn.sigmoid(jnp.matmul(h, m["s_gate"].astype(jnp.float32),
                                   precision=HI))
    return x + y + sg[:, None] * shared


@functools.partial(jax.jit, static_argnames=("eps", "fmt"))
def _head(x, gf, head, *, eps, fmt):
    return _linear(_norm(x, gf, eps), head.astype(jnp.float32).T, fmt)


def _layer(tree, idx):
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _moe(x, w, d, fmt, held):
    return jnp.concatenate(
        [_experts(x[r0:r0 + ROWS], w, d=d, fmt=fmt, held=held)
         for r0 in range(0, x.shape[0], ROWS)], axis=0)


def _static(d: dict):
    return tuple(sorted((k, v) for k, v in d.items() if k != "held"))


class _Dims(tuple):
    """The dims as a hashable static argument that still reads by key."""

    def __getitem__(self, key):
        if isinstance(key, str):
            return dict(self)[key]
        return tuple.__getitem__(self, key)


def hidden_states(weights: dict, tokens, *, dims: dict,
                  precision: str = "f32"):
    """The residual stream [T, D] after the last layer."""
    tokens = jnp.asarray(tokens, jnp.int32)
    held = tuple(dims["held"])
    d = _Dims(_static(dims))
    x = weights["embed"][tokens].astype(jnp.float32)
    per = weights["periods"]
    R = dims["interval"] - 1
    for p in range(dims["L"] // dims["interval"]):
        for j in range(R):
            w = _layer(per["rec"], (p, j))
            x = _delta_net(x, w, d=d, fmt=precision)
            x = _moe(x, w, d, precision, held)
        w = _layer(per["full"], p)
        x = _attention(x, w, d=d, fmt=precision)
        x = _moe(x, w, d, precision, held)
    return x


def logits_at(weights: dict, tokens, rows, *, dims: dict,
              precision: str = "f32"):
    """float32 logits [len(rows), V] of the sequence ``tokens`` (1-D,
    padded freely at the END: every layer is causal) at positions
    ``rows``."""
    x = hidden_states(weights, tokens, dims=dims, precision=precision)
    return _head(x[jnp.asarray(rows, jnp.int32)], weights["ln_f"],
                 weights["head"], eps=dims["eps"], fmt=precision)

"""Plain reference: a GPT-2 block without linear biases, float32.

Written from the layer equations (Radford et al. 2019; Cerebras-GPT,
Dey et al. 2023 keeps them), with the departures the configuration file
lists: no bias on the four linear maps, and the tanh form of GELU.

    x_0   = E[tok] + P[pos]
    a     = LN(x; g1, b1) Wqkv            -> q, k, v   (H heads of Dh)
    s     = softmax(causal(q k^T / sqrt(Dh)))
    x     = x + (s v) Wo
    x     = x + gelu(LN(x; g2, b2) W1) W2
    logit = LN(x_L; gf, bf) E^T            (tied head)

One sequence at a time, layer by layer, no cache, no kernels, nothing
imported from the program. ``precision`` 'f32' is the reference proper
(every matmul at ``highest``); 'fp8' and 'int8' are the controls: the
same equations with both operands of every linear map rounded to the
lower format (per-row scales), the step a later PR would be tempted by.
"""

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round_to(x, fmt: str, axis: int):
    """Fake-quantise ``x`` along ``axis`` groups to ``fmt``."""
    if fmt == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if fmt == "fp8":
        s = amax / 448.0          # largest finite e4m3 value
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if fmt == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(f"unknown precision {fmt!r}")


def _linear(x, w, fmt):
    # activations scaled per token (row), weights per output channel
    return jnp.matmul(_round_to(x, fmt, -1), _round_to(w, fmt, 0),
                      precision=HI)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_heads", "eps", "fmt"))
def _block(x, w, *, n_heads, eps, fmt):
    T, D = x.shape
    dh = D // n_heads
    qkv = _linear(_layer_norm(x, w["ln1"], w["ln1_b"], eps), w["qkv"], fmt)
    q, k, v = (t.reshape(T, n_heads, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("htd,hsd->hts", q, k, precision=HI) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hts,hsd->htd", p, v, precision=HI)
    x = x + _linear(a.transpose(1, 0, 2).reshape(T, D), w["attn_out"], fmt)
    h = _gelu_tanh(_linear(_layer_norm(x, w["ln2"], w["ln2_b"], eps),
                           w["mlp_in"], fmt))
    return x + _linear(h, w["mlp_out"], fmt)


@functools.partial(jax.jit, static_argnames=("eps", "fmt"))
def _head(x, gf, bf, embed, *, eps, fmt):
    return _linear(_layer_norm(x, gf, bf, eps), embed.T, fmt)


def logits_at(weights: dict, tokens, rows, *, n_heads: int, eps: float,
              precision: str = "f32"):
    """float32 logits [len(rows), V] of the sequence ``tokens`` (1-D
    int32, padded freely at the END: causality keeps the padding out of
    every earlier row) at positions ``rows``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    x = weights["embed"][tokens] + weights["pos"][:T]
    blocks = weights["blocks"]
    n_layers = blocks["qkv"].shape[0]
    for li in range(n_layers):
        w = {k: v[li] for k, v in blocks.items()}
        x = _block(x, w, n_heads=n_heads, eps=eps, fmt=precision)
    return _head(x[jnp.asarray(rows, jnp.int32)], weights["ln_f"],
                 weights["ln_f_b"], weights["embed"], eps=eps,
                 fmt=precision)

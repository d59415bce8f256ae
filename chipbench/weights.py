"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program; the plain
references are handed the same call's output again after the program's
state is freed. Nothing here imports the program.
"""

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A PRNG key for any whole-number seed (the driver's are larger
    than 32 signed bits hold)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    key = jax.random.fold_in(key, seed // (2 ** 31))
    return jax.random.fold_in(key, stream)


def lm_weight_shapes(dims: dict) -> dict:
    D, F, L, V = dims["d_model"], dims["d_ff"], dims["n_layers"], dims["vocab"]
    return {
        "embed": (V, D), "pos": (dims["max_len"], D),
        "blocks": {"ln1": (L, D), "ln1_b": (L, D), "qkv": (L, D, 3 * D),
                   "attn_out": (L, D, D), "ln2": (L, D), "ln2_b": (L, D),
                   "mlp_in": (L, D, F), "mlp_out": (L, F, D)},
        "ln_f": (D,), "ln_f_b": (D,)}


def lm_weights(seed: int, dims: dict) -> dict:
    """Normal weights at 1/sqrt(fan_in), chosen so that what is served
    depends on the whole computation. With the usual 1/sqrt(2L) on the
    residual projections the tied head sees mostly the input token's own
    embedding: greedy decoding falls onto a token that predicts itself
    and repeats it with a margin of ~2 (seen on the CPU and on the chip,
    PR 23), and a check of served tokens then checks nothing. So the
    residual projections keep 1/sqrt(fan_in) (the blocks' sum outweighs
    the embedding ten to one) and the q, k, v maps get twice that, which
    sharpens attention to a few keys so that the context (the KV cache)
    carries as much as the MLPs. LayerNorm gains and biases are jittered
    off 1 and 0 so that a path that drops them is caught. float32 leaves
    in the pytree layout the program's block consumes."""
    D, F = dims["d_model"], dims["d_ff"]
    s = 1.0 / math.sqrt(D)
    scales = {
        "embed": s, "pos": s,
        "blocks": {"ln1": 0.1, "ln1_b": 0.1, "qkv": 2.0 * s,
                   "attn_out": s, "ln2": 0.1, "ln2_b": 0.1, "mlp_in": s,
                   "mlp_out": 1.0 / math.sqrt(F)},
        "ln_f": 0.1, "ln_f_b": 0.1}
    offsets = {"ln1": 1.0, "ln2": 1.0, "ln_f": 1.0}
    shapes = lm_weight_shapes(dims)

    @jax.jit
    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            sc = scales
            for p in path:
                sc = sc[p.key]
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * sc
            out.append(w + offsets.get(name, 0.0))
        return jax.tree_util.tree_unflatten(tree, out)

    return make(seed_key(seed, 1))


RESIDUAL_GAIN = 0.2     # the last BN of a residual branch (see below)


def resnet_weights(seed: int, specs: dict) -> dict:
    """``specs``: name -> shape of every trainable leaf. Convolutions
    ([k, k, cin, cout]) and the classifier ([in, out]) get He-normal
    values, BN gains 1 +- 10%, every bias and BN shift +-0.1: all leaves
    differ from their defaults so none can be skipped unseen. The last
    BN gain of each residual branch (``*_c_bn.gamma``) starts at 0.2 +-
    10%, the usual small-residual start (Goyal et al. 2017 start it at
    0): with gains of 1 throughout, fifty layers amplify a rounding
    difference of 1e-7 to 3.5% of the first gradient (measured, CPU,
    float32 against float32), bf16 decorrelates it altogether, and no
    number can tell a sound step from a lower precision."""
    names = sorted(specs)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape = tuple(specs[name])
            k = jax.random.fold_in(key, i)
            if len(shape) >= 2:
                fan_in = 1
                for d in shape[:-1]:
                    fan_in *= d
                out[name] = jax.random.normal(k, shape, jnp.float32) \
                    * math.sqrt(2.0 / fan_in)
            elif name.endswith(".gamma"):
                base = RESIDUAL_GAIN if name.endswith("_c_bn.gamma") else 1.0
                out[name] = base * (1.0 + 0.1 * jax.random.normal(
                    k, shape, jnp.float32))
            else:
                out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
        return out

    return make(seed_key(seed, 2))

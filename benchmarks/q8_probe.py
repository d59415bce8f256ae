"""q8-pipeline feasibility probe — measures the real block machinery.

The round-4 fused-BN A/B taught that hand-written Pallas conv kernels
lose to XLA's conv fusions (190 vs 710 GB/s) because XLA already absorbs
elementwise ops into its convolutions. The q8 recipe (paddle_tpu/ops/q8.py)
is therefore expressed at the XLA level; this probe A/Bs a deep chain of
those actual blocks against the equivalent dense conv+BN+ReLU chain,
forward+backward, on whatever chip is attached:

  A. dense:  x -> [conv -> BN -> ReLU] * L     (what bench.py runs today)
  B. q8:     entry_stash -> [conv_q8] * L -> exit

Reports per-layer wall time, XLA cost_analysis bytes, and
memory_analysis temp size (the activation working set — the direct
evidence that only int8 stashes persist between blocks).

Run:  python benchmarks/q8_probe.py [L] [N H W C]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops import q8
from paddle_tpu.ops import conv as ops_conv
from paddle_tpu.utils.sync import host_sync

L = int(sys.argv[1]) if len(sys.argv) > 1 else 16
if len(sys.argv) > 5:
    N, H, W, C = map(int, sys.argv[2:6])
else:
    N, H, W, C = 128, 28, 28, 128


def dense_chain(x, ws, gs, bs):
    t = x
    for i in range(L):
        y = ops_conv.conv2d(t, ws[i], stride=1, padding=1).astype(jnp.float32)
        mu = y.mean((0, 1, 2))
        var = ((y - mu) ** 2).mean((0, 1, 2))
        t = jnp.maximum((y - mu) * lax.rsqrt(var + 1e-5) * gs[i] + bs[i],
                        0).astype(jnp.bfloat16)
    return t


def q8_chain(x, ws, gs, bs, st):
    mus, svs = st
    yh, q, mu_x, amax_x = q8.entry_stash(x, mus[0], svs[0])
    new_mu = [mu_x]
    new_s = [q8.scale_from_amax(amax_x)]
    M, B = q8.fold_identity(mus[0])
    relu_in = False
    for i in range(L):
        blk = q8.make_conv_q8(1, 1, relu_in)
        yh, q, mu, var, amax = blk(yh, q, ws[i], M, B, mus[i], svs[i],
                                   mus[i + 1], svs[i + 1])
        new_mu.append(mu)
        new_s.append(q8.scale_from_amax(amax))
        M, B = q8.fold_bn_affine(mu, var, gs[i], bs[i])
        relu_in = True
    out = q8.make_exit(True)(yh, q, M, B, mus[L], svs[L])
    return out, (jnp.stack(new_mu), jnp.stack(new_s))


def report(name, fn, args):
    jfn = jax.jit(fn)
    compiled = jfn.lower(*args).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ma = compiled.memory_analysis()
    out = jfn(*args)
    host_sync(out)
    n_it = 20
    t0 = time.perf_counter()
    for _ in range(n_it):
        out = jfn(*args)
    host_sync(out)
    dt = (time.perf_counter() - t0) / n_it
    gb = ca.get("bytes accessed", float("nan")) / 1e9
    temp = getattr(ma, "temp_size_in_bytes", 0) / 1e6
    print(f"{name:24s} wall={dt*1e3:8.3f} ms ({dt*1e3/L:6.3f}/layer)  "
          f"cost_bytes={gb:7.3f} GB  temp={temp:8.1f} MB")
    return dt


def main():
    print(f"devices: {jax.devices()}  chain L={L}  shape N{N} H{H} W{W} C{C}")
    act = N * H * W * C
    print(f"per-layer activation: bf16 {act*2/1e6:.1f} MB / int8 {act/1e6:.1f} MB\n")
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (N, H, W, C), jnp.bfloat16)
    ws = [jax.random.normal(jax.random.PRNGKey(i + 1), (3, 3, C, C),
                            jnp.bfloat16) * 0.05 for i in range(L)]
    gs = [jnp.ones((C,), jnp.float32) for _ in range(L)]
    bs = [jnp.zeros((C,), jnp.float32) for _ in range(L)]
    st = (jnp.zeros((L + 1, C), jnp.float32), jnp.ones((L + 1, C), jnp.float32))

    # calibrate scales once so the q8 chain runs in-range
    _, st = jax.jit(q8_chain)(x, ws, gs, bs, st)

    def loss_a(x, ws, gs, bs):
        return jnp.sum(dense_chain(x, ws, gs, bs).astype(jnp.float32))

    def loss_b(x, ws, gs, bs, st):
        out, _ = q8_chain(x, ws, gs, bs, st)
        return jnp.sum(out.astype(jnp.float32))

    report("A dense fwd", dense_chain, (x, ws, gs, bs))
    report("B q8    fwd", q8_chain, (x, ws, gs, bs, st))
    report("A dense fwd+bwd", jax.grad(loss_a, argnums=1), (x, ws, gs, bs))
    report("B q8    fwd+bwd", jax.grad(loss_b, argnums=1), (x, ws, gs, bs, st))


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()

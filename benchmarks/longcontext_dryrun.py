"""Long-context composition evidence: ring-CP × flash at seq 8192.

The on-chip single-device flash numbers exist (BENCHMARKS.md transformer
table: 67.2k tok/s at 8192 where plain attention can't compile). This
script evidences the COMPOSITION — ring context-parallelism over the
seq axis with the flash kernel running inside each ring step — at seq
8192 end-to-end on the 8-device CPU mesh (the in-process multi-device
strategy, SURVEY §4.6): forward matches the exact full-attention
reference, and a 2-layer LM train step executes with decreasing loss.
Flash runs in Pallas interpret mode off-TPU, so what is checked is the
real kernel's math at 8k, not a stand-in.

Run:  python benchmarks/longcontext_dryrun.py [--seq 8192]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core import place
    from paddle_tpu.parallel import ring
    from paddle_tpu.models import transformer

    T = args.seq
    mesh = place.make_mesh((1, 8, 1), (place.AXIS_DATA, place.AXIS_SEQ,
                                       place.AXIS_MODEL))
    rec = {"metric": "ring_flash_composition", "seq": T, "mesh_seq": 8}

    # 1) ring x flash forward == exact full attention at seq T
    rng = np.random.RandomState(0)
    B, H, D = 1, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    t0 = time.time()
    got = np.asarray(ring.ring_attention_spmd(q, k, v, mesh, causal=True,
                                              use_flash=True))
    t_ring = time.time() - t0
    want = np.asarray(ring.full_attention(q, k, v, causal=True))
    err = float(np.abs(got - want).max())
    rec["fwd_max_abs_err_vs_full"] = err
    rec["ring_flash_fwd_s"] = round(t_ring, 1)
    print(f"# ring x flash fwd at seq {T}: max|err| vs exact full "
          f"attention = {err:.2e} ({t_ring:.1f}s)", flush=True)
    assert err < 5e-4, err

    # the int8-wire variant: K/V hops carry int8 + per-shard scales
    got8 = np.asarray(ring.ring_attention_spmd(
        q, k, v, mesh, causal=True, use_flash=True, wire_int8=True))
    err8 = float(np.abs(got8 - want).max() / (np.abs(want).max() + 1e-9))
    rec["wire_int8_fwd_rel_err"] = err8
    print(f"# ring x flash x wire-int8 at seq {T}: rel err vs exact = "
          f"{err8:.2e}", flush=True)
    assert err8 < 0.05, err8

    # 2) 2-layer LM train steps, ring+flash, loss decreases
    cfg = transformer.TransformerConfig(
        vocab=256, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_len=T, dtype=jnp.float32, use_ring_attention=True,
        use_flash_attention=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    sharded = jax.tree_util.tree_map(
        jax.device_put, params, transformer.param_shardings(cfg, mesh))
    toks = jnp.asarray(rng.randint(0, 256, (1, T)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 256, (1, T)).astype(np.int32))

    @jax.jit
    def step(p, tk, tg):
        loss, g = jax.value_and_grad(transformer.lm_loss)(p, tk, tg, cfg,
                                                          mesh=mesh)
        return loss, jax.tree_util.tree_map(lambda w, gr: w - 0.1 * gr,
                                            p, g)

    t0 = time.time()
    l1, p2 = step(sharded, toks, tgt)
    l2, _ = step(p2, toks, tgt)
    rec["train_loss_step1"] = float(l1)
    rec["train_loss_step2"] = float(l2)
    rec["train_2steps_s"] = round(time.time() - t0, 1)
    print(f"# ring x flash LM train at seq {T}: loss {float(l1):.4f} -> "
          f"{float(l2):.4f} ({rec['train_2steps_s']}s)", flush=True)
    assert float(l2) < float(l1)
    rec["ok"] = True
    print(json.dumps(rec))
    out = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "runs",
        f"longcontext_ring_flash_seq{T}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()

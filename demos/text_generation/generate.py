#!/usr/bin/env python
"""Train a tiny character LM and generate with greedy / sampling / beam
search over the KV cache (reference workflow slot: seqToseq generation +
trainer/tests/test_recurrent_machine_generation.cpp — the transformer
flagship's serving loop).

Run: python demos/text_generation/generate.py [--steps N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    import paddle_tpu as paddle
    paddle.init(seed=3)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer as tfm

    # toy corpus: repeated pangram — enough structure for greedy decode
    # to reproduce it after a few hundred steps
    text = "the quick brown fox jumps over the lazy dog. " * 40
    chars = sorted(set(text))
    stoi = {c: i for i, c in enumerate(chars)}
    data = np.array([stoi[c] for c in text], np.int32)

    cfg = tfm.TransformerConfig(vocab=len(chars), d_model=64, n_layers=2,
                                n_heads=2, d_ff=128, max_len=128,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    adam = paddle.optimizer.Adam(learning_rate=3e-3)
    opt = adam.tree_init_state(params)

    T, B = 64, 8
    rng = np.random.RandomState(0)

    @jax.jit
    def step(p, o, toks, tgts, i):
        loss, g = jax.value_and_grad(tfm.lm_loss)(p, toks, tgts, cfg)
        p, o = adam.tree_update(i, g, p, o)
        return loss, p, o

    for i in range(args.steps):
        starts = rng.randint(0, len(data) - T - 1, B)
        toks = jnp.asarray(np.stack([data[s:s + T] for s in starts]))
        tgts = jnp.asarray(np.stack([data[s + 1:s + T + 1] for s in starts]))
        loss, params, opt = step(params, opt, toks, tgts,
                                 jnp.asarray(i, jnp.int32))
        if i % 50 == 0:
            print(f"step {i} loss {float(loss):.3f}")

    prompt_txt = "the quick "
    prompt = jnp.asarray([[stoi[c] for c in prompt_txt]], jnp.int32)

    def decode(ids):
        return "".join(chars[int(i)] for i in np.asarray(ids))

    greedy = tfm.generate(params, prompt, cfg, max_new=40)
    print("greedy :", repr(decode(greedy[0])))
    sampled = tfm.generate(params, prompt, cfg, max_new=40, temperature=0.8,
                           key=jax.random.PRNGKey(7))
    print("sampled:", repr(decode(sampled[0])))
    beams, scores = tfm.beam_search(params, prompt, cfg, max_new=40,
                                    beam_size=3)
    for j in range(3):
        print(f"beam[{j}] ({float(scores[0, j]):.2f}):",
              repr(decode(beams[0, j])))


if __name__ == "__main__":
    main()

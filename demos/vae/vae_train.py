#!/usr/bin/env python
"""VAE demo (reference: v1_api_demo/vae/vae_train.py — MLP VAE on MNIST
with reparameterised sampling and an ELBO objective).

Run: python demos/vae/vae_train.py [--batches N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import vae


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=128)
    args = ap.parse_args()

    paddle.init(seed=13)
    trainer = vae.VAETrainer(vae.VAEConfig(), jax.random.PRNGKey(0))
    reader = paddle.batch(paddle.dataset.mnist.train(), args.batch_size)
    key = jax.random.PRNGKey(1)
    i, first = 0, None
    for pass_id in range(100):
        for batch in reader():
            # mnist is [-1, 1]; bernoulli VAE wants [0, 1]
            x = (np.stack([b[0] for b in batch]).astype(np.float32)
                 + 1.0) / 2.0
            key, sub = jax.random.split(key)
            loss = trainer.train_batch(sub, x)
            first = first if first is not None else loss
            if i % 50 == 0:
                print(f"batch {i}: -ELBO {loss:.2f}")
            i += 1
            if i >= args.batches:
                print(f"-ELBO {first:.2f} -> {loss:.2f}")
                return


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""GAN demo (reference: v1_api_demo/gan/gan_trainer.py — alternating
generator/discriminator training on uniform data / MNIST).

Run: python demos/gan/gan_trainer.py [--batches N] [--conv]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import gan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--conv", action="store_true",
                    help="DCGAN-style conv G/D (28x28 images)")
    args = ap.parse_args()

    paddle.init(seed=99)
    cfg = gan.GANConfig(conv=args.conv)
    trainer = gan.GANTrainer(cfg, jax.random.PRNGKey(0))

    reader = paddle.batch(paddle.dataset.mnist.train(), args.batch_size)
    key = jax.random.PRNGKey(1)
    i = 0
    for pass_id in range(100):
        for batch in reader():
            real = np.stack([b[0] for b in batch]).astype(np.float32)
            key, sub = jax.random.split(key)
            d_loss, g_loss = trainer.train_batch(sub, real)
            if i % 50 == 0:
                print(f"batch {i}: d_loss {d_loss:.4f} g_loss {g_loss:.4f}")
            i += 1
            if i >= args.batches:
                samples = trainer.sample(jax.random.PRNGKey(2), 4)
                print("sample stats: mean %.3f std %.3f" %
                      (float(np.mean(samples)), float(np.std(samples))))
                return


if __name__ == "__main__":
    main()

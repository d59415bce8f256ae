#!/usr/bin/env python
"""CTR wide&deep quick-start (reference: v1_api_demo/quick_start/
trainer_config.lr.py — the high-dimensional sparse logistic-regression
showcase that exercised the sparse-remote-update pserver path; here the
embedding shards ride in-graph collectives).

Run: python demos/quick_start/train_ctr.py [--passes N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import paddle_tpu as paddle
from paddle_tpu.models import ctr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--wide-dim", type=int, default=10000)
    ap.add_argument("--vocab", type=int, default=10000)
    args = ap.parse_args()

    paddle.init(seed=7)
    out, cost = ctr.ctr_wide_deep(args.wide_dim, args.vocab)
    params = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Adam(learning_rate=1e-3))

    reader = ctr.synthetic_reader(args.wide_dim, args.vocab, n=2048)
    losses = []
    trainer.train(
        reader=paddle.batch(reader, args.batch_size),
        num_passes=args.passes,
        event_handler=lambda e: losses.append(e.cost)
        if isinstance(e, paddle.event.EndIteration) else None)
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Traffic-speed prediction demo (reference:
v1_api_demo/traffic_prediction/trainer_config.py — 24 past terms of link
speeds -> 24 forecast horizons, one shared-weight classifier head per
horizon over 5 speed classes).

Run: python demos/traffic_prediction/train.py [--passes N]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import layer

TERM_NUM = 24
FORECASTING_NUM = 24
SPEED_CLASSES = 5


def synthetic_traffic(n=2048, seed=0):
    """Sinusoidal daily pattern + noise, discretised into speed classes —
    learnable structure standing in for the sensor CSVs."""
    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(n):
            phase = rng.rand() * 2 * np.pi
            t = np.arange(TERM_NUM + FORECASTING_NUM)
            speed = 2.0 + 2.0 * np.sin(2 * np.pi * t / 24 + phase) \
                + 0.3 * rng.randn(len(t))
            cls = np.clip(np.round(speed), 0, SPEED_CLASSES - 1)
            yield tuple([speed[:TERM_NUM].astype(np.float32)] +
                        [int(c) for c in cls[TERM_NUM:]])
    return reader


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=128)
    args = ap.parse_args()

    paddle.init(seed=23)
    encode = layer.data("link_encode",
                        paddle.data_type.dense_vector(TERM_NUM))
    hidden = layer.fc(encode, 16, act=paddle.activation.Relu(),
                      name="tp_hidden")
    costs = []
    feeding = {"link_encode": 0}
    for i in range(FORECASTING_NUM):
        lbl = layer.data(f"label_{i}",
                         paddle.data_type.integer_value(SPEED_CLASSES))
        feeding[f"label_{i}"] = i + 1
        # shared-weight heads across horizons (the reference's _link_vec.w)
        out = layer.fc(hidden, SPEED_CLASSES,
                       act=paddle.activation.Softmax(),
                       name=f"tp_out_{i}",
                       param_attr=layer.ParamAttr(name="tp_link_vec.w"))
        costs.append(layer.classification_cost(out, lbl,
                                               name=f"tp_cost_{i}"))
    total = layer.addto(costs, name="tp_cost")

    params = paddle.parameters.create(total)
    trainer = paddle.trainer.SGD(
        cost=total, parameters=params,
        update_equation=paddle.optimizer.RMSProp(learning_rate=1e-3))
    seen = []
    trainer.train(reader=paddle.batch(synthetic_traffic(), args.batch_size),
                  num_passes=args.passes, feeding=feeding,
                  event_handler=lambda e: seen.append(e.cost)
                  if isinstance(e, paddle.event.EndIteration) else None)
    print(f"summed 24-horizon cost {seen[0]:.2f} -> {seen[-1]:.2f}")


if __name__ == "__main__":
    main()

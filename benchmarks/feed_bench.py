"""Reader-fed train throughput — does the host feed path throttle?

bench.py measures with device-resident synthetic tensors; the reference
trained from host-side data providers with an async double-buffer
(paddle/gserver/dataproviders/PyDataProvider2.cpp:195). Our equivalents
are the trainer's one-batch-lookahead feed path (trainer.py
_feed_next: batch N+1 converted while step N is on the device) and,
beyond it, the staged async input pipeline
(paddle_tpu/pipeline/): transform workers + staging ring + device
double-buffer, enabled with ``trainer.train(..., prefetch=N)``.

Two workloads:

- ``--workload resnet``   (default) — the original measurement: the
  ResNet-50 config through trainer.SGD with a host numpy reader;
  steady-state img/s against the device-resident number is the feed
  path's cost. ``--prefetch N`` routes it through the pipeline.
- ``--workload synthetic`` — an INPUT-BOUND microbench: a small MLP
  whose device step is cheap next to an artificial per-batch host input
  cost (``--feed-ms``, emulating decode/augment/IO). ``--compare`` runs
  it twice — synchronous feed vs ``--prefetch`` pipeline — and reports
  per-step wall time plus the overlap fraction of the host input cost
  the pipeline hid behind device compute. This is the acceptance
  measurement for the pipeline subsystem: pipelined step time must
  drop below sync.

``--metrics-out=PATH`` leaves a JSONL trail next to the stdout JSON
lines (serving_bench conventions; BENCH_METRICS_OUT env works too).

Run:  python benchmarks/feed_bench.py [--batch 128] [--steps 20]
      python benchmarks/feed_bench.py --workload synthetic --compare \
          [--feed-ms 30] [--prefetch 4] [--metrics-out=feed.jsonl]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_metrics import metrics_write as _metrics_write  # noqa: E402

METRICS_OUT = os.environ.get("BENCH_METRICS_OUT")


def metrics_write(**rec):
    _metrics_write(METRICS_OUT, **rec)


def _step_times(paddle, trainer, reader, prefetch, warmup):
    """Train one pass, returning the steady-state list of per-step wall
    gaps (EndIteration to EndIteration — includes feed wait)."""
    times, t_last = [], [None]

    def handler(ev):
        if isinstance(ev, paddle.event.EndIteration):
            now = time.perf_counter()
            if t_last[0] is not None:
                times.append(now - t_last[0])
            t_last[0] = now

    trainer.train(reader=reader, num_passes=1, event_handler=handler,
                  prefetch=prefetch)
    return times[warmup:]


def run_synthetic(args, prefetch):
    """One synthetic run (sync when prefetch=0); returns the record."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.utils.rng import KeySource

    dim, classes = args.dim, 10
    x = layer.data("x", paddle.data_type.dense_vector(dim))
    y = layer.data("y", paddle.data_type.integer_value(classes))
    h = layer.fc(input=x, size=args.hidden, act=paddle.activation.Relu())
    out = layer.fc(input=h, size=classes, act=paddle.activation.Softmax())
    cost = layer.classification_cost(out, y, name="cost")
    params = paddle.parameters.create(cost, KeySource(0))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.01))
    rng = np.random.RandomState(0)
    n_batches = args.warmup + args.steps
    feed_s = args.feed_ms / 1e3

    def reader():
        # pre-batched column tuples with an artificial host input cost
        # per batch (the decode/augment/IO stand-in): the sync path eats
        # it on the step; the pipeline hides it in the producer thread
        for _ in range(n_batches):
            t0 = time.perf_counter()
            feats = rng.rand(args.batch, dim).astype(np.float32)
            labels = rng.randint(classes, size=args.batch).astype(np.int32)
            rest = feed_s - (time.perf_counter() - t0)
            if rest > 0:
                time.sleep(rest)
            yield (feats, labels)

    steady = _step_times(paddle, trainer, reader, prefetch, args.warmup)
    ms = float(np.median(steady) * 1e3) if steady else 0.0
    return {"metric": "synthetic_feed_step_ms",
            "value": round(ms, 2), "unit": "ms/step",
            "feed": f"pipeline prefetch={prefetch}" if prefetch
                    else "synchronous one-batch lookahead",
            "feed_ms": args.feed_ms, "batch": args.batch,
            "steps_timed": len(steady)}


def run_compare(args):
    """Sync vs pipelined on the input-bound synthetic workload."""
    prefetch = args.prefetch or 4
    rec_sync = run_synthetic(args, prefetch=0)
    rec_pipe = run_synthetic(args, prefetch=prefetch)
    sync_ms, pipe_ms = rec_sync["value"], rec_pipe["value"]
    # how much of the artificial host input cost the pipeline hid
    overlap = ((sync_ms - pipe_ms) / args.feed_ms
               if args.feed_ms > 0 else 0.0)
    rec_speed = {"metric": "pipelined_feed_speedup",
                 "value": round(sync_ms / pipe_ms, 3) if pipe_ms else 0.0,
                 "unit": "x (sync step time / pipelined step time)",
                 "sync_ms": sync_ms, "pipelined_ms": pipe_ms,
                 "overlap_frac_of_feed": round(overlap, 3),
                 "prefetch": prefetch, "feed_ms": args.feed_ms}
    for rec in (rec_sync, rec_pipe, rec_speed):
        print(json.dumps(rec))
        metrics_write(**rec)
    return {"sync": rec_sync, "pipelined": rec_pipe, "speedup": rec_speed}


def run_resnet(args):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.models import resnet
    from paddle_tpu.utils.rng import KeySource

    img = layer.data("image", paddle.data_type.dense_vector(3 * 224 * 224))
    lbl = layer.data("label", paddle.data_type.integer_value(1000))
    out = resnet.resnet_imagenet(img, depth=args.depth, class_num=1000,
                                 stem_space_to_depth=True)
    cost = layer.classification_cost(out, lbl, name="cost")
    params = paddle.parameters.create(cost, KeySource(42))
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(momentum=0.9,
                                                  learning_rate=0.1))

    rng = np.random.RandomState(0)
    n_batches = args.warmup + args.steps

    if args.source == "native":
        import tempfile
        from paddle_tpu.runtime import loader as rl
        dim = 224 * 224 * 3
        tmp = tempfile.NamedTemporaryFile(suffix=".rio", delete=False)
        n = n_batches * args.batch

        def samples():
            for _ in range(n):
                yield (rng.rand(dim).astype(np.float32),
                       int(rng.randint(1000)))

        t_w = time.time()
        try:
            rl.write_dense(tmp.name, samples(), dim,
                           chunk_records=args.batch)
        except BaseException:
            os.unlink(tmp.name)            # don't leak GBs on a failed write
            raise
        print(f"# wrote {n} raw records in {time.time()-t_w:.1f}s",
              flush=True)
        base_reader = rl.dense_batch_reader(tmp.name, dim, args.batch,
                                            num_threads=2, drop_last=True)

        def reader():
            # NHWC view of the natively-assembled batch columns
            for feats, labels in base_reader():
                yield (feats.reshape(-1, 224, 224, 3), labels)
    else:
        def reader():
            # host-side NHWC float batches, generated per item like a real
            # decoded-image pipeline would deliver
            for _ in range(n_batches * args.batch):
                yield (rng.rand(224, 224, 3).astype(np.float32),
                       int(rng.randint(1000)))

    t0 = time.time()
    # the native source yields whole batches already; host yields samples
    train_reader = reader if args.source == "native" \
        else paddle.batch(reader, args.batch)
    try:
        steady = _step_times(paddle, trainer, train_reader,
                             args.prefetch, args.warmup)
    finally:
        if args.source == "native":
            os.unlink(tmp.name)            # ~GBs of synthetic records
    wall = time.time() - t0
    ms = float(np.median(steady) * 1e3) if steady else None
    feed_desc = ("native recordio batch assembly"
                 if args.source == "native" else "host numpy reader")
    feed_desc += (f" + pipeline prefetch={args.prefetch}" if args.prefetch
                  else " + one-batch-lookahead prefetch")
    rec = {"metric": "resnet50_reader_fed_images_per_sec",
           "value": round(args.batch / (ms / 1e3), 1) if steady else 0.0,
           "unit": "images/sec",
           "ms_per_batch": round(ms, 2) if ms is not None else None,
           "batch": args.batch, "steps_timed": len(steady),
           "total_wall_s": round(wall, 1),
           "feed": feed_desc}
    print(json.dumps(rec))
    metrics_write(**rec)
    return rec


def main(argv=None):
    global METRICS_OUT
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--source", choices=["host", "native"], default="host",
                    help="host: python reader, per-sample feeder assembly; "
                    "native: raw recordio + C++ batch assembly "
                    "(runtime/loader.dense_batch_reader)")
    ap.add_argument("--workload", choices=["resnet", "synthetic"],
                    default="resnet")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="feed through the async input pipeline with "
                    "this staging depth (0 = synchronous path)")
    ap.add_argument("--compare", action="store_true",
                    help="synthetic only: run sync AND pipelined, report "
                    "step times + the overlap the pipeline achieved")
    ap.add_argument("--feed-ms", type=float, default=30.0,
                    help="synthetic: artificial host input cost per batch")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)

    if args.metrics_out:
        METRICS_OUT = args.metrics_out

    if args.workload == "synthetic":
        if args.compare:
            return run_compare(args)
        rec = run_synthetic(args, prefetch=args.prefetch)
        print(json.dumps(rec))
        metrics_write(**rec)
        return rec
    return run_resnet(args)


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    main()

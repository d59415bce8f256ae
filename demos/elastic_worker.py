"""A deterministic elastic training worker — the gang member script the
chaos tests (tests/test_elastic_chaos.py), the recovery benchmark
(benchmarks/elastic_bench.py), and docs/howto_elastic.md all run under
``runtime/supervisor.py``.

Each worker is a single-process JAX runtime over
``PADDLE_LOCAL_CPU_DEVICES`` virtual CPU devices that trains the SAME
deterministic stream on a ``data`` mesh of size PADDLE_NUM_PROCESSES —
the CPU simulation of one host in a data-parallel gang (jaxlib cannot
run cross-process CPU collectives: replicated identical compute stands
in for the all-reduce, which keeps every trajectory bit-deterministic
and therefore comparable across kill/restart/shrink scenarios).

The elastic contract is exercised for real: SGD.train heartbeats to
the supervisor, checkpoints through the fenced crash-consistent commit
protocol into a per-rank dir, resumes from the latest INTACT
checkpoint with the input pipeline's stream position (exact next
batch), and reshards the ZeRO layout when PADDLE_NUM_PROCESSES changed
across a restart (meta-driven reshard, io/checkpoint.py).

Env knobs (beyond the supervisor's PADDLE_* contract):
  ELASTIC_OUT        output dir (losses/params per rank+epoch; ckpts)
  ELASTIC_NB         batches per pass              (default 8)
  ELASTIC_BS         batch size                    (default 8)
  ELASTIC_HIDDEN     hidden width (default 16; the observability A/B
                     widens it so step wall is measurable, not noise)
  ELASTIC_ZERO       ZeRO stage for the data mesh  (default 1)
  ELASTIC_STEP_SLEEP extra seconds per step (lets the supervisor catch
                     a gang mid-run instead of racing it to the finish)
  PADDLE_TPU_CHECKPOINT_PERIOD  flag: batches between async saves
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# single-process virtual-device runtime (conftest.py technique); must
# happen before the backend initialises. No distributed.init(): the
# gang members are independent runtimes in the CPU simulation.
_NDEV = int(os.environ.get("PADDLE_LOCAL_CPU_DEVICES", "4"))
os.environ.setdefault("PADDLE_TPU_SEED", "42")
os.environ.setdefault("PADDLE_TPU_COMPUTE_DTYPE", "float32")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", _NDEV)

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import layer, parallel  # noqa: E402
from paddle_tpu.core import place  # noqa: E402
from paddle_tpu.pipeline import Pipeline  # noqa: E402
from paddle_tpu.utils.rng import KeySource  # noqa: E402


def main():
    rank = int(os.environ.get("PADDLE_PROCESS_ID", "0"))
    nprocs = int(os.environ.get("PADDLE_NUM_PROCESSES", "1"))
    epoch = int(os.environ.get("PADDLE_ELASTIC_EPOCH", "0"))
    nb = int(os.environ.get("ELASTIC_NB", "8"))
    bs = int(os.environ.get("ELASTIC_BS", "8"))
    hidden = int(os.environ.get("ELASTIC_HIDDEN", "16"))
    zero = int(os.environ.get("ELASTIC_ZERO", "1"))
    sleep_s = float(os.environ.get("ELASTIC_STEP_SLEEP", "0"))
    out = os.environ.get("ELASTIC_OUT", ".")
    os.makedirs(out, exist_ok=True)
    ckdir = os.path.join(out, f"ckpt_rank{rank}")

    x = layer.data("ew_x", paddle.data_type.dense_vector(8))
    lbl = layer.data("ew_l", paddle.data_type.integer_value(2))
    h = layer.fc(x, hidden, act=paddle.activation.Relu(), name="ew_h")
    o = layer.fc(h, 2, act=paddle.activation.Softmax(), name="ew_o")
    cost = layer.classification_cost(o, lbl, name="ew_cost")
    params = paddle.parameters.create(cost, KeySource(5))
    mesh = place.make_mesh((nprocs,), (place.AXIS_DATA,))
    tr = paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(learning_rate=0.1),
        parallel=parallel.data_parallel(mesh, zero=zero))

    def reader():
        # batch b is a pure function of b: every pass, every rank, and
        # every incarnation sees the identical stream — resume
        # correctness shows up as exact trajectory equality
        for b in range(nb):
            rs = np.random.RandomState(1000 + b)
            for _ in range(bs):
                y = int(rs.randint(2))
                yield ((rs.randn(8) + 2.0 * y).astype(np.float32), y)

    pipe = Pipeline(reader, batch_size=bs, prefetch=2, track_state=True)

    losses = []

    def handler(e):
        if isinstance(e, paddle.event.EndIteration):
            losses.append({"step": tr._step - 1, "loss": float(e.cost),
                           "wall_s": float(e.wall_time_s or 0.0)})
            if sleep_s:
                import time
                time.sleep(sleep_s)

    try:
        tr.train(reader=pipe, num_passes=1, event_handler=handler,
                 checkpoint_dir=ckdir)
    finally:
        pipe.close()

    with open(os.path.join(out, f"losses_rank{rank}_epoch{epoch}.jsonl"),
              "w") as f:
        for rec in losses:
            f.write(json.dumps(rec) + "\n")
    from paddle_tpu.io.checkpoint import _flatten
    np.savez(os.path.join(out, f"final_rank{rank}_epoch{epoch}.npz"),
             **_flatten(tr.parameters.values))
    with open(os.path.join(out, f"done_rank{rank}_epoch{epoch}.json"),
              "w") as f:
        json.dump({"step": tr._step, "nprocs": nprocs}, f)
    print(f"elastic worker rank {rank} epoch {epoch}: done at step "
          f"{tr._step}", flush=True)


if __name__ == "__main__":
    main()

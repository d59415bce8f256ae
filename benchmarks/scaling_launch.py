"""Multi-process DP coordination overhead via runtime/launch.py.

This host has ONE CPU core, so a throughput scaling curve over N local
processes would measure core contention, not parallel efficiency (that
evidence comes from the TPU compiler's schedule — scaling_aot.py). What
a 1-core host CAN measure honestly is the framework's COORDINATION cost:
N processes × 1 virtual device each run the same tiny DP train step via
jax.distributed; with compute serialized, ideal per-step time is
N × t(1), and anything above that is the multi-process machinery —
coordinator RPC, cross-process collectives, launcher overhead. The
reference's analogous in-process-pserver tests measured convergence
equivalence, not speed (paddle/trainer/tests/test_CompareSparse.cpp:65).

Driver:  python benchmarks/scaling_launch.py
Worker:  (spawned via runtime.launch.launch_local)
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def worker():
    import numpy as np
    from paddle_tpu import distributed

    distributed.init()                     # PADDLE_* env contract
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = distributed.process_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    dat = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    D = 64
    rng = np.random.RandomState(0)
    w = jax.device_put(jnp.asarray(rng.randn(D, D).astype(np.float32)), rep)
    per = 8
    x_local = rng.randn(per, D).astype(np.float32)
    gx = jax.make_array_from_process_local_data(dat, x_local,
                                                (per * n, D))

    @jax.jit
    def step(w, x):
        def loss(w):
            h = jnp.tanh(x @ w)
            return jnp.mean(h * h)
        g = jax.grad(loss)(w)              # grads all-reduce over `data`
        return w - 0.01 * g

    w = step(w, gx)                        # compile
    jax.block_until_ready(w)
    iters = 60
    t0 = time.perf_counter()
    for _ in range(iters):
        w = step(w, gx)
    jax.block_until_ready(w)
    dt = (time.perf_counter() - t0) / iters
    if distributed.process_index() == 0:
        out = os.environ["SCALING_OUT"]
        with open(out, "w") as f:
            json.dump({"nprocs": n, "step_ms": dt * 1e3}, f)


def main():
    import tempfile

    from paddle_tpu.runtime import launch

    rows = []
    for n in (1, 2, 4, 8):
        fd, out = tempfile.mkstemp(suffix=f"_scal{n}.json")
        os.close(fd)
        rcs = launch.launch_local(
            n, [os.path.abspath(__file__), "--worker"],
            devices_per_proc=1, env_extra={"SCALING_OUT": out},
            timeout=600)
        assert all(rc == 0 for rc in rcs), rcs
        with open(out) as f:
            rows.append(json.load(f))
        os.unlink(out)
        print(rows[-1], flush=True)

    t1 = rows[0]["step_ms"]
    for r in rows:
        n = r["nprocs"]
        # serialized ideal on one core: n x single-process step time; the
        # delta is dominated by the cross-process all-reduce on the CPU
        # backend's loopback gRPC transport (latency-bound: 16 KB payload)
        r["collective_ms"] = round(max(0.0, r["step_ms"] - n * t1), 3)
    result = {
        "metric": "multiprocess_dp_collective_latency",
        "note": ("1-core host, tiny model: the per-step delta over N x "
                 "t(1) isolates the cross-process collective+coordination "
                 "latency of the gRPC loopback transport — bounded, "
                 "amortized under any real step (ResNet-50: 100 ms). On "
                 "TPU pods collectives are in-graph over ICI instead; "
                 "that path's evidence is scaling_aot.py (real TPU "
                 "compiler schedule)."),
        "per_process_batch": 8, "rows": rows}
    print(json.dumps(result, indent=2))
    path = os.path.join(REPO, "benchmarks", "runs", "scaling_launch.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {path}")


if __name__ == "__main__":
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    if "--worker" in sys.argv:
        worker()
    else:
        main()

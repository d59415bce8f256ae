"""Multi-host runtime initialisation — the cluster-training control plane.

Reference: the trainer/pserver process topology was assembled by gflags
(--trainer_id/--num_gradient_servers/--pservers, utils/Flags.cpp:58-81)
and launcher scripts (paddle/scripts/cluster_train/paddle.py SSH fan-out,
submit_local.sh.in); the Go master + etcd coordinated elasticity.

TPU-native: one JAX process per host joins the cluster through
``jax.distributed.initialize`` (coordinator + process id); after that,
``jax.devices()`` is the *global* device set, meshes span hosts, and every
collective rides ICI within a slice and DCN across slices — there is no
trainer/pserver asymmetry to configure. This module wraps that runtime:

- ``init()``         — join the cluster (env-var or explicit args)
- ``hybrid_mesh()``  — ICI x DCN mesh for multi-slice jobs
- the local N-process simulation used by tests/launcher lives in
  paddle_tpu.runtime.launch

Env contract (set by paddle_tpu.runtime.launch or your scheduler):
  PADDLE_COORDINATOR   host:port of process 0
  PADDLE_NUM_PROCESSES total process count
  PADDLE_PROCESS_ID    this process's rank
  PADDLE_LOCAL_CPU_DEVICES  (simulation) virtual CPU devices per process;
                       the launcher sets JAX_PLATFORMS=cpu beside it
On real TPU pods all three are discovered from the TPU metadata by JAX and
``init()`` degenerates to ``jax.distributed.initialize()``.
"""

import os
import time
from typing import Optional, Sequence

from paddle_tpu.observe import metrics as _metrics
from paddle_tpu.utils.logger import get_logger

log = get_logger("distributed")

_initialized = False

_m_init_s = _metrics.gauge(
    "distributed_init_seconds", "wall time of jax.distributed.initialize")
_m_procs = _metrics.gauge("distributed_process_count",
                          "processes in the cluster")
_m_devices = _metrics.gauge("distributed_global_devices",
                            "global device count")
_m_barriers = _metrics.counter("distributed_barriers_total",
                               "cross-process barriers entered")
_m_barrier_s = _metrics.histogram(
    "distributed_barrier_seconds",
    "barrier wait time — the straggler detector (BarrierStat slot)")


def is_initialized() -> bool:
    return _initialized


def init(coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         local_cpu_devices: Optional[int] = None) -> None:
    """Join (or create) the multi-host JAX cluster.

    With no arguments, reads the PADDLE_* env contract; with nothing set,
    falls back to JAX auto-detection (TPU pod metadata). Safe to call on a
    single host with no env — it then does nothing, keeping single-process
    semantics.
    """
    global _initialized
    if _initialized:
        log.warning("distributed.init() called twice; ignoring")
        return
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "PADDLE_COORDINATOR")
    if num_processes is None and os.environ.get("PADDLE_NUM_PROCESSES"):
        num_processes = int(os.environ["PADDLE_NUM_PROCESSES"])
    if process_id is None and os.environ.get("PADDLE_PROCESS_ID"):
        process_id = int(os.environ["PADDLE_PROCESS_ID"])
    if local_cpu_devices is None and os.environ.get(
            "PADDLE_LOCAL_CPU_DEVICES"):
        local_cpu_devices = int(os.environ["PADDLE_LOCAL_CPU_DEVICES"])

    # simulation mode: k virtual devices per process on the CPU platform
    # (which JAX_PLATFORMS=cpu selects — runtime.launch sets both)
    if local_cpu_devices:
        jax.config.update("jax_num_cpu_devices", local_cpu_devices)

    t0 = time.perf_counter()
    if coordinator_address is None and num_processes is None:
        # single-host (or TPU-pod auto-detect) path
        try:
            jax.distributed.initialize()
            _initialized = True
            _m_init_s.set(time.perf_counter() - t0)
            _m_procs.set(jax.process_count())
            _m_devices.set(len(jax.devices()))
            log.info("distributed: auto-initialized, %d processes, "
                     "%d global devices", jax.process_count(),
                     len(jax.devices()))
        except Exception as e:  # noqa: BLE001 — single-process fallback
            log.info("distributed: single-process mode (%s)", e)
        return

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    _initialized = True
    _m_init_s.set(time.perf_counter() - t0)
    _m_procs.set(jax.process_count())
    _m_devices.set(len(jax.devices()))
    log.info("distributed: joined as process %d/%d, %d global devices "
             "(%d local)", jax.process_index(), jax.process_count(),
             len(jax.devices()), len(jax.local_devices()))


def shutdown():
    global _initialized
    if _initialized:
        import jax
        jax.distributed.shutdown()
        _initialized = False


_barrier_win = None


def barrier_window(create: bool = True):
    """The raw barrier-wait window this process exports to the gang
    supervisor (heartbeat telemetry): the histogram above has already
    binned the per-rank distribution away, and pooled gang quantiles /
    straggler attribution both need raw samples. Lazy — a process that
    never barriers exports nothing. ``create=False`` peeks."""
    global _barrier_win
    if _barrier_win is None and create:
        from paddle_tpu.observe.window import WindowedQuantiles
        _barrier_win = WindowedQuantiles(window_s=120.0,
                                         max_samples=1024)
    return _barrier_win


def barrier(name: str = "barrier") -> float:
    """Block until every process reaches this point; returns (and
    records) this process's wait in seconds. The per-name histogram is
    the straggler detector the reference built BarrierStat for
    (paddle/utils/Stat.h BarrierStat): a process whose wait is
    consistently near-zero while peers wait long IS the straggler.
    Single-process: returns 0.0 immediately (still counted)."""
    import jax

    wall0 = time.time()
    t0 = time.perf_counter()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
    dt = time.perf_counter() - t0
    _m_barriers.inc(name=name)
    _m_barrier_s.observe(dt, name=name)
    barrier_window().observe(dt)
    # barrier waits in the Chrome trace: with pid = process index, the
    # merged multi-host timeline shows exactly which host straggled
    from paddle_tpu.observe import chrome_trace
    chrome_trace.record_span(f"barrier/{name}", wall0, dt)
    # every rank exits a barrier at the same true instant: the first
    # exit per name is this process's clock-alignment mark for the
    # offline gang-trace merge (chrome_trace.merge_traces)
    chrome_trace.note_alignment(f"barrier/{name}", wall0 + dt)
    return dt


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def hybrid_mesh(ici_shape: Sequence[int], axis_names: Sequence[str],
                dcn_axis: str = "dcn",
                num_slices: Optional[int] = None):
    """ICI x DCN mesh for multi-slice / multi-host jobs.

    ici_shape/axis_names lay out the devices *within* a slice; the leading
    ``dcn_axis`` spans slices (usually the pure-DP axis — gradients cross
    DCN once per step, everything else stays on ICI). Single-slice jobs
    (num_slices==1) get a plain mesh without the DCN axis.

    Replaces: the trainer↔pserver split (sync grads crossed the datacenter
    network via ParameterClient2, pserver/ParameterClient2.h:216); here the
    cross-slice all-reduce is one XLA collective on the dcn axis.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    is_cpu_sim = devices[0].platform == "cpu"
    if num_slices is None:
        # slice count from device attributes when present (TPU pods); the
        # CPU backend reports slice_index=0 for every device regardless of
        # process, so in simulation use processes-as-slices instead
        if hasattr(devices[0], "slice_index") and not is_cpu_sim:
            num_slices = len({d.slice_index for d in devices})
        elif jax.process_count() > 1:
            num_slices = jax.process_count()
        else:
            num_slices = 1
    per_slice = int(np.prod(ici_shape))
    if per_slice * num_slices != len(devices):
        raise ValueError(
            f"ici {tuple(ici_shape)} x {num_slices} slices needs "
            f"{per_slice * num_slices} devices, have {len(devices)}")
    if num_slices == 1:
        arr = np.asarray(devices).reshape(tuple(ici_shape))
        return Mesh(arr, tuple(axis_names))
    if hasattr(devices[0], "slice_index") and not is_cpu_sim:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), (num_slices,), devices=devices,
            allow_split_physical_axes=True)
        # create_hybrid_device_mesh puts DCN axes last; move it first
        arr = np.moveaxis(arr, -1, 0)
    else:
        # simulation: group devices by process = slice
        order = sorted(range(len(devices)),
                       key=lambda i: (devices[i].process_index, i))
        arr = np.asarray([devices[i] for i in order]).reshape(
            (num_slices,) + tuple(ici_shape))
    return Mesh(arr, (dcn_axis,) + tuple(axis_names))

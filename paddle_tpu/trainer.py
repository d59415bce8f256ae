"""The training loop.

Reference: python/paddle/v2/trainer.py:124 SGD.train — per-pass/per-batch loop
driving GradientMachine.forwardBackward + ParameterUpdater over SWIG, firing
user events; plus the C++ Trainer/TrainerInternal
(paddle/trainer/TrainerInternal.cpp:66 trainOneBatch).

TPU-native: the whole batch step — forward, backward, optimizer update,
metric accumulables — is ONE jitted function with donated pytrees, so
parameters never leave device and XLA overlaps everything it can. The Python
loop only feeds data and reads back scalars (the reference crossed the SWIG
boundary per layer call; here the boundary is once per step).
"""

import math
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu import event as events
from paddle_tpu import observe
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.evaluator import EvaluatorSet
from paddle_tpu.optimizer import Optimizer
from paddle_tpu.parameters import Parameters
from paddle_tpu.runtime import chaos as _chaos
from paddle_tpu.topology import LayerOutput, Topology, Value
from paddle_tpu.utils import logger
from paddle_tpu.utils.flags import GLOBAL_FLAGS
from paddle_tpu.utils.rng import global_key_source


class _StepMonitor:
    """Per-step observability: wall time, examples/sec, loss, recompile
    tagging, MFU, and memory gauges — fanned out through
    ``observe.report()`` (JSONL sink + handlers), the default metrics
    registry, and the flight recorder's last-K ring. All host work is
    O(1) dict/float ops so instrumentation overhead stays in the noise
    (<5% on the smallnet bench, tested by tests/test_observe.py).

    Recompile accounting is two-sided: the exact jit-cache-miss count
    from the compile tracker (arg-shape signatures; ``compile_count``
    in every record) plus the wall-time outlier heuristic (a step over
    ``outlier_factor`` × the running median of the last ``window``
    steps is tagged ``recompile`` — it also catches slowdowns the
    signature tracker cannot see, e.g. backend-side recompiles)."""

    def __init__(self, window: int = 64, outlier_factor: float = 4.0,
                 opt_state_bytes: int = 0, grad_bytes: int = 0,
                 param_bytes: int = 0):
        self._times = []                     # ring buffer of recent steps
        self._window = window
        self._factor = outlier_factor
        self._idx = 0
        self._opt_bytes = int(opt_state_bytes)
        self._grad_bytes = int(grad_bytes)
        self._param_bytes = int(param_bytes)
        reg = observe.default_registry()
        self.steps = reg.counter(
            "train_steps_total", "optimizer steps taken")
        self.examples = reg.counter(
            "train_examples_total", "training examples consumed")
        self.recompiles = reg.counter(
            "train_recompiles_total",
            "steps tagged as XLA recompiles (step-time outliers)")
        self.step_time = reg.histogram(
            "train_step_seconds", "per-step wall time (dispatch+sync)")
        self.loss_gauge = reg.gauge("train_loss", "last step's mean loss")
        self.mfu_gauge = reg.gauge(
            "train_mfu", "model-FLOPs utilisation of the last step "
            "(lowered-HLO flops / wall / declared peak; 0 until the "
            "step cost is known)")
        self.hbm_gauge = reg.gauge(
            "device_bytes_in_use", "device HBM in use (0 when the backend "
            "hides memory stats, e.g. CPU)")
        self.host_gauge = reg.gauge(
            "host_rss_bytes", "host process resident set size")
        self.opt_bytes_gauge = reg.gauge(
            "opt_state_bytes_per_device",
            "optimizer-state bytes resident on ONE device — under "
            "ZeRO (DistConfig zero_stage>=1) this is ~1/data-axis of "
            "the replicated figure")
        self.grad_bytes_gauge = reg.gauge(
            "grad_bytes_per_device",
            "bytes of the longest-lived gradient object on ONE device "
            "(the accum-scan carry, or the transient grad at the "
            "update point) — ~1/data-axis under ZeRO stage>=2")
        self.param_bytes_gauge = reg.gauge(
            "param_bytes_per_device",
            "parameter bytes resident on ONE device between steps — "
            "~1/data-axis under ZeRO stage 3 (params stored sharded, "
            "all-gathered on use)")
        self.bottleneck_frac = reg.gauge(
            "train_bottleneck_fraction",
            "last step's time split by component (label component = "
            "input|compute|sync; observe/bottleneck.py semantics)")
        self.bottleneck_steps = reg.counter(
            "train_steps_bottleneck_total",
            "steps by bottleneck classification (label bottleneck = "
            "input_bound|compute_bound|sync_bound)")
        # set unconditionally: a stateless-optimizer run must overwrite
        # a previous run's value on the shared registry, not expose it
        self.opt_bytes_gauge.set(self._opt_bytes)
        self.grad_bytes_gauge.set(self._grad_bytes)
        self.param_bytes_gauge.set(self._param_bytes)
        # peak FLOP/s is constant for the process: resolve once, not per
        # step (env read + device lookup + table scan on the hot path)
        self._peak_flops = observe.costs.device_peak_flops()
        # raw step walls for the gang plane: the supervisor pools these
        # ACROSS ranks (never averaging per-rank quantiles), and the
        # straggler detector needs the per-rank distribution, which the
        # histogram above has already binned away
        self.step_window = observe.WindowedQuantiles(window_s=120.0,
                                                     max_samples=512)

    def median(self):
        """Running median step wall over the ring (None before the
        first step) — the goodput accountant's useful-vs-recompile
        split point."""
        if not self._times:
            return None
        return sorted(self._times)[len(self._times) // 2]

    def tag_recompile(self, dt: float) -> bool:
        """Record one step time; True when it is a compile-shaped outlier."""
        times = self._times
        first = not times
        if len(times) < self._window:
            times.append(dt)
        else:
            times[self._idx] = dt
            self._idx = (self._idx + 1) % self._window
        if first:
            return True
        med = sorted(times)[len(times) // 2]
        return dt > self._factor * med and dt > med + 0.01

    def update_memory_gauges(self):
        """Refresh host/device memory gauges (called every log_period —
        device_memory_stats can poke the backend, so not per-step)."""
        from paddle_tpu.utils import memory as mem
        dev = mem.device_memory_stats()
        if dev.get("bytes_in_use"):
            self.hbm_gauge.set(dev["bytes_in_use"])
        host = mem.host_memory_stats()
        if host.get("rss_bytes"):
            self.host_gauge.set(host["rss_bytes"])

    def step(self, *, step, pass_id, batch_id, cost, batch_size, dt,
             flops=None, compile_count=0, feed_s=0.0, dispatch_s=0.0,
             sync_s=0.0):
        """One trained batch: update registry, ring the flight recorder,
        and emit the JSONL record. ``flops`` is the lowered-HLO step
        cost when known (None → MFU reports 0). ``feed_s`` /
        ``dispatch_s`` / ``sync_s`` are the step's span components;
        together with the modeled compute time (flops / peak) they
        classify the step input|compute|sync-bound
        (observe/bottleneck.py)."""
        recompile = self.tag_recompile(dt)
        self.steps.inc()
        self.examples.inc(batch_size)
        self.step_time.observe(dt)
        self.step_window.observe(dt)
        self.loss_gauge.set(cost)
        if recompile:
            self.recompiles.inc()
        eps = batch_size / dt if dt > 0 else 0.0
        mfu = (observe.costs.mfu(flops, dt, self._peak_flops)
               if self._peak_flops else None)
        if mfu is not None:
            self.mfu_gauge.set(mfu)
        est_compute = (flops / self._peak_flops
                       if flops and self._peak_flops else None)
        label, frac = observe.attribute_step(feed_s, dispatch_s, sync_s,
                                             est_compute)
        for comp, f in frac.items():
            self.bottleneck_frac.set(round(f, 6), component=comp)
        if label != "unknown":
            self.bottleneck_steps.inc(bottleneck=label)
        rec = dict(kind="step", step=step, pass_id=pass_id,
                   batch_id=batch_id, loss=round(cost, 6),
                   wall_time_s=round(dt, 6),
                   examples_per_sec=round(eps, 2),
                   mfu=round(mfu, 6) if mfu is not None else 0.0,
                   compile_count=int(compile_count),
                   opt_state_bytes=self._opt_bytes,
                   grad_bytes=self._grad_bytes,
                   param_bytes=self._param_bytes,
                   recompile=recompile,
                   bottleneck=label,
                   frac_input=round(frac["input"], 4),
                   frac_compute=round(frac["compute"], 4),
                   frac_sync=round(frac["sync"], 4))
        # the flight ring ALWAYS sees the step — a post-mortem must not
        # depend on a metrics sink having been configured
        observe.default_flight_recorder().record(rec)
        if observe.has_consumers():
            observe.report(rec)
        return recompile, eps


class SGD:
    """paddle.trainer.SGD (reference: python/paddle/v2/trainer.py:48)."""

    def __init__(self, cost: LayerOutput, parameters: Parameters,
                 update_equation: Optimizer,
                 extra_layers: Optional[List[LayerOutput]] = None,
                 is_local: bool = True, parallel=None,
                 grad_accum_steps: int = 1):
        """parallel: an optional paddle_tpu.parallel.DistConfig — shards
        parameters per its rules and the batch across the data axis; XLA
        inserts the gradient all-reduce (replacing the pserver round-trip,
        reference: trainer/RemoteParameterUpdater.cpp).

        grad_accum_steps: split every batch into this many microbatches
        inside the jitted step (a ``lax.scan``): activations live for one
        microbatch at a time (≈N× less activation memory) while gradients
        accumulate and the optimizer sees the full-batch mean gradient.
        For BN-free, dropout-free models the trajectory matches
        grad_accum_steps=1 up to summation order; batch norm normalizes
        per MICROBATCH (ghost-BN statistics) and dropout draws one mask
        per microbatch, so models using either train on slightly
        different (equally valid) noise. Ragged final batches
        (drop_last=False) fall back to the unaccumulated step."""
        if grad_accum_steps < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, "
                             f"got {grad_accum_steps}")
        self.grad_accum_steps = int(grad_accum_steps)
        self.cost = cost
        self.parameters = parameters
        self.optimizer = update_equation
        self.extra_layers = list(extra_layers or [])
        self.topology = Topology([cost] + self.extra_layers)
        self.optimizer.bind(self.topology.param_specs())
        self._forward = self.topology.compile()
        self._feeder_cache: Dict = {}
        self.opt_state = self.optimizer.init_state(parameters.values)
        self._step = 0
        self.parallel = parallel
        if parallel is not None:
            pv = parameters.values
            parameters.values = parallel.shard_params(pv)
            # zero_stage>=1: state_shardings lays the opt-state leaves of
            # replicated params over the data axis (ZeRO-1) — the same
            # call places them replicated under zero=0
            self.opt_state = jax.device_put(
                self.opt_state, parallel.state_shardings(self.opt_state))
            if parameters.state:
                parameters.state = jax.device_put(
                    parameters.state,
                    jax.tree.map(lambda _: parallel.replicated(),
                                 parameters.state))
            if getattr(parallel, "zero_stage", 0) >= 1:
                rep = parallel.zero_report(parameters.values)
                logger.debug(
                    "zero=%d over %s=%d: %d param states sharded, "
                    "%d replicated (%s)", rep["zero_stage"], rep["axis"],
                    rep["axis_size"], len(rep["sharded"]),
                    len(rep["replicated"]),
                    ", ".join(f"{k}: {v}"
                              for k, v in rep["replicated"].items())
                    or "none")
                # stages 2/3 add grad / stored-param layout decisions —
                # same per-leaf reasons, logged per object class
                for section in ("grads", "params"):
                    view = rep[section]
                    if view["sharded"]:
                        logger.debug(
                            "zero=%d %s: %d sharded, %d replicated (%s)",
                            rep["zero_stage"], section,
                            len(view["sharded"]),
                            len(view["replicated"]),
                            ", ".join(f"{k}: {v}" for k, v in
                                      view["replicated"].items())
                            or "none")
        self._plain_train_step = self._build_train_step()
        self._accum_train_step = (self._build_accum_train_step()
                                  if self.grad_accum_steps > 1 else None)
        self._train_step = self._accum_train_step or self._plain_train_step
        self._eval_step = self._build_eval_step()
        # (fn id, feed signature) -> lowered-HLO flops (or None when the
        # cost model punted); filled lazily, once per signature
        self._step_flops: Dict = {}
        self._last_step_wall = None          # healthz progress probes
        self._last_cost = None
        self.evaluators = EvaluatorSet(self.topology.layers)
        if self.grad_accum_steps > 1 and any(
                getattr(l, "layer_type", "") == "pnpair"
                for l in self.topology.layers):
            logger.warning(
                "grad_accum_steps>1 with a positive_negative_pair "
                "evaluator: pairs spanning microbatch boundaries are not "
                "counted — the metric differs from unaccumulated training")

    # -- compiled steps ----------------------------------------------------
    def _zero_shardings(self):
        """(update, keep, state, compute) sharding dicts for the ZeRO
        constraint points, computed ONCE at step-build time (None under
        zero=0 / local training — the steps then call opt.update
        directly). ``keep`` is the STORED layout updated params return
        to: the serving layout below stage 3, the 1/N shard at stage 3.
        ``compute`` is non-None only at stage 3 — the full/TP layout the
        forward constrains stored shards to (the on-use all-gather)."""
        par = self.parallel
        if par is None or getattr(par, "zero_stage", 0) < 1:
            return None
        values = self.parameters.values
        return (par.zero_update_shardings(values),
                par.store_shardings(values),
                par.state_shardings(self.opt_state),
                par.param_shardings(values) if par.zero_stage >= 3
                else None)

    def _build_train_step(self):
        fwd = self._forward
        opt = self.optimizer
        cost_name = self.cost.name
        par = self.parallel
        zero = self._zero_shardings()

        def train_step(params, opt_state, state, feeds, step, dropout_key):
            def loss_fn(p):
                if zero is not None and zero[3] is not None:
                    # ZeRO-3 gather-on-use: stored 1/N shards constrained
                    # to the compute layout — XLA inserts one all-gather
                    # per leaf at its first use (prefetchable under
                    # earlier layers' compute) and the gather's backward
                    # transpose IS the grad reduce-scatter
                    p = jax.lax.with_sharding_constraint(p, zero[3])
                outs, new_state = fwd(p, state, feeds, is_training=True,
                                      dropout_key=dropout_key)
                per_example = outs[cost_name].array
                return jnp.mean(per_example.astype(jnp.float32)), \
                    (outs, new_state)

            (loss, (outs, new_state)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if zero is not None:
                # ZeRO: grad reduce-scatters, the update runs on 1/N
                # shards against the sharded opt state, updated params
                # return to the stored layout (all-gather below stage 3,
                # still sharded at stage 3 — parallel/spmd.py)
                from paddle_tpu.parallel import spmd
                new_params, new_opt = spmd.zero_constrained_update(
                    par, opt, step, grads, params, opt_state,
                    update_shardings=zero[0], keep_shardings=zero[1],
                    state_shardings=zero[2])
            else:
                new_params, new_opt = opt.update(step, grads, params,
                                                 opt_state)
            return loss, new_params, new_opt, new_state, outs

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _build_accum_train_step(self):
        """Microbatched step: lax.scan over grad_accum_steps slices of the
        batch; gradients sum in the carry, model state (BN running stats)
        threads sequentially, per-microbatch metric accumulables sum (they
        are additive by contract, evaluator.MetricAccumulator — except
        the batch-local pnpair counts, warned about in __init__)."""
        fwd = self._forward
        opt = self.optimizer
        cost_name = self.cost.name
        n = self.grad_accum_steps
        par = self.parallel
        zero = self._zero_shardings()
        metric_names = [l.name for l in self.topology.layers
                        if hasattr(l, "metric_finalize")]

        def train_step(params, opt_state, state, feeds, step, dropout_key):
            def split(a):
                # indivisible batches never reach this step: the train
                # loop routes them to the plain step (_pick_train_step)
                return a.reshape((n, a.shape[0] // n) + a.shape[1:])

            mfeeds = jax.tree_util.tree_map(split, feeds)
            keys = jax.random.split(dropout_key, n)

            def micro(carry, xs):
                st, acc = carry
                fd, mkey = xs

                def loss_fn(p):
                    if zero is not None and zero[3] is not None:
                        # ZeRO-3: gather stored shards on use, per
                        # microbatch (the gather's transpose reduce-
                        # scatters this microbatch's grad into the
                        # sharded accumulator below)
                        p = jax.lax.with_sharding_constraint(p, zero[3])
                    outs, st2 = fwd(p, st, fd, is_training=True,
                                    dropout_key=mkey)
                    per_example = outs[cost_name].array
                    return jnp.mean(per_example.astype(jnp.float32)), \
                        (outs, st2)

                (loss, (outs, st2)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g)
                if zero is not None:
                    # keep the accumulator ZeRO-sharded through the scan:
                    # each microbatch's grad reduce-scatters into the
                    # shard instead of all-reducing a full copy
                    acc = jax.lax.with_sharding_constraint(acc, zero[0])
                mets = {m: outs[m].array.astype(jnp.float32)
                        for m in metric_names if m in outs}
                return (st2, acc), (loss, mets)

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if zero is not None:
                zeros = jax.lax.with_sharding_constraint(zeros, zero[0])
            (new_state, acc), (losses, mets) = jax.lax.scan(
                micro, (state, zeros), (mfeeds, keys))
            grads = jax.tree_util.tree_map(
                lambda a, p: (a / n).astype(p.dtype), acc, params)
            if zero is not None:
                from paddle_tpu.parallel import spmd
                new_params, new_opt = spmd.zero_constrained_update(
                    par, opt, step, grads, params, opt_state,
                    update_shardings=zero[0], keep_shardings=zero[1],
                    state_shardings=zero[2])
            else:
                new_params, new_opt = opt.update(step, grads, params,
                                                 opt_state)
            outs = {m: Value(v.sum(axis=0)) for m, v in mets.items()}
            return (jnp.mean(losses), new_params, new_opt, new_state, outs)

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _build_eval_step(self):
        fwd = self._forward
        cost_name = self.cost.name

        def eval_step(params, state, feeds):
            outs, _ = fwd(params, state, feeds, is_training=False)
            return jnp.mean(outs[cost_name].array.astype(jnp.float32)), outs

        return jax.jit(eval_step)

    def _pick_train_step(self, feeds):
        """Accumulated step when the batch divides by grad_accum_steps;
        otherwise (ragged drop_last=False tail) the plain step — crashing
        at the end of a pass over a remainder batch is not acceptable."""
        if self._accum_train_step is None:
            return self._plain_train_step
        leaves = jax.tree_util.tree_leaves(feeds)
        # every leaf must share the batch dim AND divide evenly; a future
        # non-batched auxiliary input must fall back to the plain step, not
        # die in the accumulated step's reshape with an XLA shape error
        if (leaves
                and all(l.ndim >= 1 and l.shape[0] == leaves[0].shape[0]
                        for l in leaves)
                and leaves[0].shape[0] % self.grad_accum_steps == 0):
            return self._accum_train_step
        return self._plain_train_step

    def _zero_meta(self):
        """The opt-state layout this trainer runs under, for checkpoint
        manifests (None for local / zero=0 training — older checkpoints
        without the key compare equal)."""
        par = self.parallel
        if par is None or getattr(par, "zero_stage", 0) < 1:
            return None
        return {"zero_stage": int(par.zero_stage),
                "axis": par.batch_axis,
                "axis_size": par.zero_axis_size()}

    def _ckpt_meta(self):
        z = self._zero_meta()
        return {"zero": z} if z is not None else None

    @staticmethod
    def _leaf_shard_bytes(leaf, sharding=None, itemsize=None) -> int:
        """Per-device bytes of one leaf: its shard shape under
        ``sharding`` (the leaf's own by default), times itemsize."""
        shape = tuple(jnp.shape(leaf))
        sharding = sharding if sharding is not None else getattr(
            leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(shape)
        if itemsize is None:
            itemsize = getattr(getattr(leaf, "dtype", None),
                               "itemsize", 4)
        n = 1
        for s in shape:
            n *= int(s)
        return n * itemsize

    def opt_state_bytes_per_device(self) -> int:
        """Optimizer-state bytes resident on ONE device: each leaf
        contributes its per-device shard (``sharding.shard_shape``), so
        replicated state counts in full while ZeRO-sharded state counts
        at ~1/axis-size — the number the ``opt_state_bytes_per_device``
        gauge and the zero on/off A/B (benchmarks/zero_bench.py) report."""
        return sum(self._leaf_shard_bytes(leaf) for leaf in
                   jax.tree_util.tree_leaves(self.opt_state))

    def param_bytes_per_device(self) -> int:
        """Parameter bytes resident on ONE device between steps (per-leaf
        ``sharding.shard_shape``): the full replicated figure for pure DP
        / ZeRO<=2, ~1/axis-size under ZeRO-3 where params are stored
        sharded and all-gathered on use — the ``param_bytes_per_device``
        gauge and the per-stage A/B in ``benchmarks/zero_bench.py``."""
        return sum(self._leaf_shard_bytes(leaf) for leaf in
                   jax.tree_util.tree_leaves(self.parameters.values))

    def grad_bytes_per_device(self) -> int:
        """Per-device bytes of the longest-lived gradient object, from
        the sharding plan's LAYOUT COMMITMENT (gradients are
        step-transients in the jitted design — there is no persistent
        grad buffer to measure): under grad accumulation this is the
        fp32 scan-carry accumulator, which rides ZeRO-sharded from
        stage 1 on; without accumulation it is the gradient at the
        update boundary — committed to 1/N by the stage>=2 contract
        (``DistConfig.grad_spec``), the param layout otherwise. XLA may
        transiently materialize a full-shape partial-sum before the
        reduce at any stage; this gauge reports what the plan requires
        to stay live, which is what bounds the accumulator and the
        update's working set."""
        par = self.parallel
        accum = self.grad_accum_steps > 1
        total = 0
        for k, v in self.parameters.values.items():
            sh = None
            if par is not None:
                sh = jax.sharding.NamedSharding(
                    par.mesh,
                    par.grad_spec(k, tuple(jnp.shape(v)), accum=accum))
            total += self._leaf_shard_bytes(
                v, sharding=sh, itemsize=4 if accum else None)
        return total

    def _feeder(self, feeding):
        key = tuple(sorted(feeding.items())) if feeding else None
        if key not in self._feeder_cache:
            dtypes = {l.name: l.data_spec for l in self.topology.data_layers}
            self._feeder_cache[key] = DataFeeder(dtypes, feeding)
        return self._feeder_cache[key]

    def _flops_for(self, step_fn, sig, step_args):
        """Lowered-HLO flops of this step signature (the MFU numerator),
        computed once per signature — one extra trace, no XLA compile —
        and only when an observability consumer exists (metrics sink or
        handler): tracing a big model costs real wall time and nobody
        would read the number."""
        if sig in self._step_flops:
            return self._step_flops[sig]
        if not observe.has_consumers():
            return None
        ca = observe.costs.lowered_cost(step_fn, *step_args)
        flops = ca["flops"] if ca else None
        self._step_flops[sig] = flops
        return flops

    def attach_observability(self, host: str = "127.0.0.1",
                             port: int = 0):
        """Serve ``/metrics`` (default registry, Prometheus text) and
        ``/healthz`` (step progress: step count, last loss, seconds
        since the last finished step, compile count) for this trainer.
        Returns the started ``observe.HealthServer`` — callers own its
        ``close()``. ``port=0`` binds an ephemeral port."""

        def health():
            since = (round(time.perf_counter() - self._last_step_wall, 3)
                     if self._last_step_wall is not None else None)
            return {
                "step": self._step,
                "last_loss": self._last_cost,
                "seconds_since_step": since,
                "compile_count":
                    observe.default_compile_tracker().count("train_step"),
            }

        return observe.HealthServer(health_fn=health, host=host, port=port)

    def _telemetry_doc(self) -> dict:
        """The per-beat gang telemetry payload (supervisor scrape
        transport — ``Heartbeat.set_telemetry``): this rank's registry
        snapshot (counters + gauges; histograms don't aggregate), its
        raw step/barrier windows for the pooled gang quantiles and the
        straggler join, and the goodput accountant's buckets. Runs on
        the beat thread at the heartbeat cadence; all O(registry)
        dict work, no device sync."""
        snap = {name: doc for name, doc in
                observe.default_registry().snapshot().items()
                if doc.get("kind") in ("counter", "gauge")}
        window = {}
        mon = getattr(self, "_monitor", None)
        if mon is not None:
            window["step_time_samples"] = \
                mon.step_window.export_samples()
        from paddle_tpu import distributed as _dist
        bw = _dist.barrier_window(create=False)
        if bw is not None:
            window["barrier_wait_samples"] = bw.export_samples()
        doc = {"snapshot": snap, "window": window}
        acct = getattr(self, "_acct", None)
        if acct is not None:
            gp = acct.snapshot()
            doc["goodput"] = {"buckets": gp["buckets"],
                              "t_start_wall": gp["t_start_wall"]}
        return doc

    # -- public API --------------------------------------------------------
    def train(self, reader, num_passes=1,
              event_handler: Optional[Callable] = None,
              feeding: Optional[Dict[str, int]] = None,
              checkpoint_dir: Optional[str] = None,
              prefetch: int = 0):
        """checkpoint_dir: when set, checkpoints (params + optimizer state +
        model state) are written asynchronously every ``checkpoint_period``
        batches (flag; 0 = once per pass) and training resumes from the
        latest checkpoint found there (reference: ParamUtil per-pass dirs +
        --init_model_path/--start_pass, trainer/ParamUtil.cpp).

        prefetch: >0 feeds through the async input pipeline
        (``paddle_tpu.pipeline``) with a staging ring of that many
        batches — conversion and host→device transfer run on pipeline
        threads so step N+1's feeds are on device while step N executes.
        ``reader`` may also BE a ``pipeline.Pipeline`` (prefetch implied),
        which additionally makes resume exact: the pipeline's stream
        position rides inside every checkpoint and a restore continues
        mid-epoch on the exact next batch. 0 keeps the synchronous
        path, on the trainer's own thread and one batch ahead: batch 0
        is pulled and converted before the loop, batch N+1 after step N
        has been dispatched and before its loss is read back, so the
        host's conversion runs while the device works and costs the
        device nothing until it outlasts the step. One step is in
        flight at a time; a reader or feeder failure on batch N+1 is
        raised after step N has finished (its ``EndIteration`` and its
        checkpoint included).

        Elastic contract: under a supervisor (PADDLE_ELASTIC_DIR set by
        ``runtime/supervisor.py``) this entry is crash-re-enterable —
        it resumes from the latest INTACT checkpoint (torn saves are
        skipped), heartbeats step progress to the supervisor every
        batch, and fences every checkpoint commit on the stamped
        coordination epoch so a zombie from a superseded gang can never
        publish state. The chaos knob (PADDLE_TPU_CHAOS, site ``step``)
        is honored at the top of every batch."""
        event_handler = event_handler or (lambda e: None)
        feeder = self._feeder(feeding)
        from paddle_tpu.pipeline import Pipeline
        pipe, own_pipe = None, False
        if isinstance(reader, Pipeline):
            pipe = reader
        elif prefetch and int(prefetch) > 0:
            # without a checkpoint dir the wrapped pipeline's state can
            # never be consumed — skip the per-batch snapshot entirely
            pipe = Pipeline(reader, prefetch=int(prefetch),
                            track_state=checkpoint_dir is not None)
            own_pipe = True
        if pipe is not None:
            transfer = None
            if self.parallel is not None:
                par = self.parallel

                def transfer(feeds):
                    return jax.device_put(feeds,
                                          par.feed_shardings(feeds))

            pipe.attach(convert=feeder.feed, transfer=transfer)
        ks = global_key_source()
        log_period = GLOBAL_FLAGS.get("log_period", 100)
        # flag-driven JSONL metrics sink (PADDLE_TPU_METRICS_PATH or
        # paddle.init(metrics_path=...)); an explicitly observe.configure()d
        # sink wins, but the flag — which paddle.init may have (re)set to a
        # DIFFERENT path — beats the env-autoconfigured sink and an
        # earlier value of itself
        mpath = GLOBAL_FLAGS.get("metrics_path")
        if mpath and not observe.explicitly_disabled() and (
                observe.sink() is None
                or (observe.sink_source() in ("env", "flag")
                    and observe.sink().path != mpath)):
            observe.configure(mpath, _source="flag")
        self._check_finite = (GLOBAL_FLAGS.get("debug_nans") or
                              GLOBAL_FLAGS.get("debug_infs"))
        # elastic supervision (runtime/supervisor.py env contract):
        # heartbeat step progress + fence checkpoint commits on the
        # stamped coordination epoch; both None outside a supervisor
        hb, fence = None, None
        import os as _os
        if _os.environ.get("PADDLE_ELASTIC_DIR"):
            from paddle_tpu.runtime import supervisor as _sup
            hb = _sup.Heartbeat.from_env()
            fence = _sup.fence_from_env()
        # goodput accounting for this incarnation: the accountant's
        # birth is the "startup ends here" mark the supervisor joins
        # with its launch timestamp, and its buckets ride the heartbeat
        # telemetry into the run-lifetime ledger
        self._acct = observe.StepAccountant()
        if hb is not None and _os.environ.get(
                "PADDLE_GANG_TELEMETRY", "1") != "0":
            hb.set_telemetry(self._telemetry_doc)
        ckpt = None
        if checkpoint_dir is not None:
            from paddle_tpu.io import checkpoint as ckpt_io
            t_restore0 = time.perf_counter()
            latest = ckpt_io.latest_checkpoint(checkpoint_dir)
            if latest:
                (self._step, self.parameters.values, self.opt_state,
                 self.parameters.state) = ckpt_io.load_checkpoint(
                    latest, self.parameters.values, self.opt_state,
                    self.parameters.state)
                if pipe is not None and pipe.track_state:
                    ps = ckpt_io.load_pipeline_state(latest)
                    if ps is not None:
                        # continue the data stream mid-epoch on the
                        # exact next batch (shuffle RNG, shard cursor,
                        # in-flight samples all restored)
                        pipe.load_state_dict(ps)
                if self.parallel is not None:
                    # loaded host arrays must go back to the mesh layout
                    # __init__ applied to the fresh init values; the
                    # checkpoint holds FULL arrays (shards are merged at
                    # load), so this device_put IS the resharding restore
                    # when the mesh or zero layout changed since the save
                    saved = (ckpt_io.checkpoint_meta(latest) or {}
                             ).get("zero")
                    cur = self._zero_meta()
                    if saved != cur:
                        logger.info(
                            "checkpoint opt-state layout %s -> restoring "
                            "into %s (resharding)", saved, cur)
                    self.parameters.values = self.parallel.shard_params(
                        self.parameters.values)
                    self.opt_state = jax.device_put(
                        self.opt_state,
                        self.parallel.state_shardings(self.opt_state))
                    if self.parameters.state:
                        self.parameters.state = jax.device_put(
                            self.parameters.state,
                            jax.tree.map(lambda _: self.parallel.replicated(),
                                         self.parameters.state))
                logger.info("resumed from %s (step %d)", latest, self._step)
                self._acct.add("restore",
                               time.perf_counter() - t_restore0)
            ckpt = ckpt_io.AsyncCheckpointer(checkpoint_dir, fence=fence)

        recorder = observe.default_flight_recorder()
        dumps_before = len(recorder.dumped_paths)
        trained_ok = False
        try:
            self._train_passes(reader, num_passes, event_handler, feeder,
                               ks, log_period, ckpt,
                               GLOBAL_FLAGS.get("checkpoint_period", 0),
                               pipe=pipe, hb=hb)
            trained_ok = True
        except Exception as e:
            # post-mortem for any crash escaping the loop — but only
            # when a flight dir is explicitly configured (a default-on
            # dump would litter artifacts through every failing test and
            # notebook), and not when the NaN tripwire already dumped
            from paddle_tpu.observe import flight as _flight
            if (_flight.configured()
                    and len(recorder.dumped_paths) == dumps_before):
                recorder.dump(reason="exception in training loop", exc=e)
            raise
        finally:
            if hb is not None:
                # only a CLEAN exit is marked done (exempt from the
                # supervisor's staleness judgments); on a crash the
                # beacon just stops, so a process that lingers after a
                # swallowed exception still reads heartbeat_lost
                hb.done() if trained_ok else hb.stop()
            if ckpt is not None:
                ckpt.close()
            if own_pipe:
                pipe.close()   # user-passed pipelines stay open: their
                               # state_dict/resume lifecycle is theirs

    def _feed_next(self, batches, feeder):
        """Pull the next batch off the reader and convert it (under
        ``self.parallel`` also put it to the feed shardings). Returns
        None, and records no ``feed`` span, when the reader is at its
        end. ``feeder.feed`` and the sharded put only DISPATCH the
        host→device copies (``jnp.asarray`` / ``jax.device_put`` are
        asynchronous); the step that consumes the buffers joins them on
        the device."""
        try:
            data_batch = next(batches)
        except StopIteration:
            return None
        with observe.trace_scope("feed"):
            with observe.trace_scope("convert"):
                feeds = feeder.feed(data_batch)
            if self.parallel is not None:
                with observe.trace_scope("transfer"):
                    feeds = jax.device_put(
                        feeds, self.parallel.feed_shardings(feeds))
        return feeds

    def _train_passes(self, reader, num_passes, event_handler, feeder, ks,
                      log_period, ckpt, period, pipe=None, hb=None):
        monitor = _StepMonitor(
            opt_state_bytes=self.opt_state_bytes_per_device(),
            grad_bytes=self.grad_bytes_per_device(),
            param_bytes=self.param_bytes_per_device())
        # published so the heartbeat telemetry thread can export the
        # raw step window (gang pooling + straggler attribution)
        self._monitor = monitor
        acct = getattr(self, "_acct", None)
        if acct is None:
            acct = self._acct = observe.StepAccountant()
        for pass_id in range(num_passes):
            event_handler(events.BeginPass(pass_id))
            self.evaluators.reset()
            pass_t0 = time.perf_counter()
            pass_examples = 0
            # pipelined mode: one iter() == one epoch, resuming mid-epoch
            # after a restore; feeds arrive converted + device-resident.
            # Synchronous mode: batch 0 is fed here, before anything is
            # on the device; batch N+1 is fed under step N, below
            ahead, ahead_exc = None, None
            if pipe is not None:
                feed_iter = iter(pipe)
            else:
                batches = iter(reader())
                feed_t0 = time.perf_counter()
                ahead = self._feed_next(batches, feeder)
                acct.add("input_stall", time.perf_counter() - feed_t0)
            batch_id = -1
            while True:
                if pipe is not None:
                    # the staging-ring get, timed as the pipelined
                    # step's input wait
                    feed_t0 = time.perf_counter()
                    try:
                        feeds = next(feed_iter)
                    except StopIteration:
                        break
                    feed_s = time.perf_counter() - feed_t0
                else:
                    if ahead_exc is not None:
                        raise ahead_exc
                    if ahead is None:
                        break
                    feeds = ahead
                batch_id += 1
                # chaos site 'step': kill/hang/crash BEFORE the step
                # executes, so "kill at step k" means exactly k steps
                # are committed (runtime/chaos.py; no-op without the
                # PADDLE_TPU_CHAOS env knob)
                _chaos.maybe_trigger("step", step=self._step)
                event_handler(events.BeginIteration(pass_id, batch_id))
                step_fn = self._pick_train_step(feeds)
                # feed-shape signature: params/opt/state shapes are fixed
                # per run, so the feeds (plus which step fn) fully key the
                # jit cache entry — an unseen signature IS a compile
                sig = (id(step_fn),) + observe.arg_signature(feeds)
                dropout_key = ks.step("dropout", self._step)
                step_args = (self.parameters.values, self.opt_state,
                             self.parameters.state, feeds,
                             jnp.asarray(self._step, jnp.int32),
                             dropout_key)
                # the one-time cost retrace stays OUTSIDE the timed
                # window: a seconds-long trace of a big model must not
                # masquerade as step wall time in the metrics
                flops = self._flops_for(step_fn, sig, step_args)
                step_t0 = time.perf_counter()
                with observe.step_scope(self._step, "train_step"):
                    with observe.trace_scope("dispatch"):
                        (loss, self.parameters.values, self.opt_state,
                         self.parameters.state, outs) = step_fn(*step_args)
                dispatch_s = time.perf_counter() - step_t0
                self._step += 1
                if pipe is None:
                    # step N is on the device: pull and convert batch
                    # N+1 NOW, before any read of step N's outputs waits
                    # for it (the evaluators' add_batch below is such a
                    # read), so the feed costs the device nothing until
                    # it outlasts the step. feed_s is this stretch,
                    # sync_s below the wait that is left after it
                    feed_t0 = time.perf_counter()
                    try:
                        ahead = self._feed_next(batches, feeder)
                    except Exception as e:
                        # step N is in flight: finish it (sync, monitor,
                        # EndIteration, its checkpoint) and surface batch
                        # N+1's failure at the top of the next iteration,
                        # or the crash would both lose N and point at the
                        # wrong batch index
                        ahead, ahead_exc = None, e
                    feed_s = time.perf_counter() - feed_t0
                self.evaluators.add_batch(outs)
                # float(loss) is the host sync — per-step wall time must
                # include it or async dispatch hides the real step time
                sync_t0 = time.perf_counter()
                with observe.trace_scope("host_sync"):
                    cost = float(loss)
                sync_s = time.perf_counter() - sync_t0
                step_dt = time.perf_counter() - step_t0
                tracker = observe.default_compile_tracker()
                n0 = tracker.count("train_step")
                tracker.record("train_step", sig, step_dt)
                # goodput split: an unseen signature IS a compile — the
                # steady median stays useful, the excess is recompile.
                # The synchronous feed lies INSIDE step_dt: it is booked
                # once, as input, so the buckets still sum to the wall
                acct.step(step_dt - feed_s if pipe is None else step_dt,
                          feed_s=feed_s,
                          compile_miss=tracker.count("train_step") > n0,
                          median_s=monitor.median())
                self._last_step_wall = time.perf_counter()
                self._last_cost = cost
                if hb is not None:
                    # step-progress lease for the elastic supervisor: a
                    # wedged worker keeps the liveness thread beating
                    # but this step counter stalls (wedge_window)
                    hb.beat(self._step)
                bs = int(next(iter(feeds.values())).array.shape[0])
                pass_examples += bs
                _, eps = monitor.step(
                    step=self._step - 1, pass_id=pass_id, batch_id=batch_id,
                    cost=cost, batch_size=bs, dt=step_dt, flops=flops,
                    compile_count=tracker.count("train_step"),
                    feed_s=feed_s, dispatch_s=dispatch_s, sync_s=sync_s)
                if self._check_finite and not math.isfinite(cost):
                    from paddle_tpu.utils import enforce
                    try:
                        enforce.check_numerics(self.parameters.values,
                                               "param")
                        raise enforce.EnforceError(
                            f"non-finite cost {cost} at pass {pass_id} "
                            f"batch {batch_id} (params are finite — check "
                            f"inputs/loss)")
                    except enforce.EnforceError as e:
                        # the NaN tripwire is a flight-recorder trigger:
                        # leave the post-mortem before the raise unwinds
                        observe.default_flight_recorder().dump(
                            reason=f"non-finite cost {cost} (debug_nans "
                                   f"tripwire)", exc=e)
                        raise
                if log_period and batch_id % log_period == 0:
                    monitor.update_memory_gauges()
                    logger.info("pass %d batch %d cost %.5f %s "
                                "(%.1f ex/s)", pass_id, batch_id, cost,
                                self.evaluators.result(), eps)
                event_handler(events.EndIteration(
                    pass_id, batch_id, cost, self.evaluators,
                    wall_time_s=step_dt, examples_per_sec=eps))
                if ckpt is not None and period and self._step % period == 0:
                    # only the synchronous part (device->host snapshot
                    # + enqueue) is checkpoint overhead — the async
                    # write overlaps the next steps
                    save_t0 = time.perf_counter()
                    ckpt.save(self._step, self.parameters.values,
                              self.opt_state, self.parameters.state,
                              pipeline_state=(
                                  pipe.state_dict() if pipe is not None
                                  and pipe.track_state else None),
                              meta=self._ckpt_meta())
                    acct.add("checkpoint_save",
                             time.perf_counter() - save_t0)
            if ckpt is not None and not period:
                save_t0 = time.perf_counter()
                ckpt.save(self._step, self.parameters.values,
                          self.opt_state, self.parameters.state,
                          pipeline_state=(
                              pipe.state_dict() if pipe is not None
                              and pipe.track_state else None),
                          meta=self._ckpt_meta())
                acct.add("checkpoint_save",
                         time.perf_counter() - save_t0)
            monitor.update_memory_gauges()
            pass_dt = time.perf_counter() - pass_t0
            if observe.has_consumers():
                mets = {}
                for k, v in (self.evaluators.result() or {}).items():
                    try:
                        mets[k] = float(v)
                    except (TypeError, ValueError):
                        pass
                observe.report(
                    kind="pass", pass_id=pass_id, step=self._step,
                    wall_time_s=round(pass_dt, 6), examples=pass_examples,
                    examples_per_sec=round(
                        pass_examples / pass_dt if pass_dt > 0 else 0.0, 2),
                    recompiles=int(monitor.recompiles.value()),
                    metrics=mets)
                s = observe.sink()
                if s is not None:
                    s.flush()      # a finished pass must be tail-able
            event_handler(events.EndPass(pass_id, self.evaluators))

    def test(self, reader, feeding: Optional[Dict[str, int]] = None):
        """One evaluation sweep (reference: trainer.py:204 SGD.test)."""
        feeder = self._feeder(feeding)
        self.evaluators.reset()
        total, n = 0.0, 0
        for data_batch in reader():
            feeds = feeder.feed(data_batch)
            if self.parallel is not None:
                feeds = jax.device_put(feeds,
                                       self.parallel.feed_shardings(feeds))
            loss, outs = self._eval_step(self.parameters.values,
                                         self.parameters.state, feeds)
            self.evaluators.add_batch(outs)
            # record count: pre-batched column tuples carry it in the
            # leading axis; sample lists in their length
            if isinstance(data_batch, tuple):
                bs = int(next(iter(feeds.values())).array.shape[0])
            else:
                bs = len(data_batch)
            total += float(loss) * bs
            n += bs
        return events.TestResult(self.evaluators,
                                 cost=total / max(n, 1))

    def save_parameter_to_tar(self, f):
        self.parameters.to_tar(f)

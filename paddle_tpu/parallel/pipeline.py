"""Pipeline parallelism over the ``stage`` mesh axis (GPipe + interleaved).

The modern occupant of the reference's per-layer device placement slot
(SURVEY.md §2.3 — ParallelNeuralNetwork's parallel_nn layer->device
dispatch): the network is cut into S stages with identical signatures;
each device on the ``stage`` axis holds one stage's weights; microbatches
flow through the ring via ``lax.ppermute`` under one ``shard_map``.

Memory layout: microbatches are **sharded across the stage axis** (blocked:
device d owns microbatches [d*K, (d+1)*K), K = M/S) for both inputs and
outputs — per-device activation residency is O(M/S), not O(M). Two
single-microbatch rings move data to where it is consumed:

- input ring: device d injects its slot-q microbatch m = d*K+q at step
  m - d; one down-hop per step lands it on stage 0 exactly at step m.
- output ring: stage S-1 finishes microbatch m at step m + S-1 and pushes
  it down the ring; device m//K captures it (S-1 - m//K) hops later.

Injections never collide with in-flight values: the value from device e
passes device d < e during steps [e*K - d, e*K+K-1 - d], disjoint from
d's injection window [d*K - d, d*K+K-1 - d] for e != d.

Schedule: T = M + S - 1 scanned steps (GPipe fill/drain bubble); step t has
stage s working on microbatch t - s. The scan is reverse-differentiable, so
the same program trains — XLA stitches the backward pipeline automatically
(activations rematerialize per jax.checkpoint policy if requested).

``pipeline_apply_interleaved`` is the 1F1B-family upgrade (the interleaved
virtual-stage schedule): each device holds ``v`` non-adjacent stage chunks
(device d owns virtual stages {c·S + d}), microbatches run in groups of S,
and each scan step does 1/v of a GPipe stage's work — so the fill/drain
bubble shrinks from (S−1) stage-times to (S−1)/v while the ring machinery
is untouched (every activation produced at step t is consumed at t+1 one
hop down the ring; see ``interleaved_schedule`` for the static timetable
and its validity/bubble assertions).
"""

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.core import place


def pipeline_apply(stage_params, x: jax.Array, stage_fn: Callable,
                   mesh: Mesh, num_microbatches: int,
                   stage_axis: str = place.AXIS_STAGE,
                   wire_int8: bool = False) -> jax.Array:
    """Run ``stage_fn`` S times (once per stage) as a pipeline.

    stage_params: pytree whose leaves have a leading stage dim [S, ...];
    x: [B, ...] with B divisible by num_microbatches; stage_fn(params_s, mb)
    must map [mb, ...] -> [mb, ...] (same shape/dtype — residual stages).
    Returns [B, ...] equal to applying the stages sequentially.

    GPipe is exactly the single-chunk case of the interleaved schedule
    (T(m, j) = m + j, makespan M + S − 1), so this delegates to
    ``pipeline_apply_interleaved`` with v=1 — one ring executor to
    maintain. Microbatch counts that don't divide S are padded here
    (padding slots run through the pipe, their outputs are dropped).
    """
    S = mesh.shape[stage_axis]
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
    mb = B // M
    Mp = -(-M // S) * S
    if Mp != M:
        pad = jnp.zeros(((Mp - M) * mb,) + x.shape[1:], x.dtype)
        x = jnp.concatenate([x, pad], 0)
    chunked = jax.tree_util.tree_map(lambda l: l[None], stage_params)
    out = pipeline_apply_interleaved(chunked, x, stage_fn, mesh, Mp,
                                     num_chunks=1, stage_axis=stage_axis,
                                     wire_int8=wire_int8)
    return out[:B]


def interleaved_schedule(num_microbatches: int, num_stages: int,
                         num_chunks: int):
    """Static timetable of the interleaved schedule (pure bookkeeping —
    used by tests and capacity planning, the executor derives the same
    arithmetic inline).

    Returns (table, makespan_steps, bubble_stage_times) where table maps
    ``(step, device) -> (microbatch, virtual_stage)`` for busy slots.
    Virtual stage j runs on device j % S; microbatch m's virtual stage j
    executes at step T(m, j) = (m // S)·S·v + (m % S) + j. One scan step
    performs 1/v of a stage's FLOPs, so the fill/drain bubble in
    stage-time units is (makespan − M·v)/v = (S−1)/v — half of GPipe's
    (S−1) at v=2.
    """
    M, S, v = num_microbatches, num_stages, num_chunks
    if M % S:
        raise ValueError(f"interleaved schedule needs microbatches ({M}) "
                         f"divisible by stages ({S})")
    table = {}
    for m in range(M):
        for j in range(S * v):
            t = (m // S) * S * v + (m % S) + j
            key = (t, j % S)
            if key in table:
                raise AssertionError(f"schedule conflict at {key}")
            table[key] = (m, j)
    makespan = M * v + S - 1
    return table, makespan, (S - 1) / v


def pipeline_apply_interleaved(stage_params, x: jax.Array,
                               stage_fn: Callable, mesh: Mesh,
                               num_microbatches: int, num_chunks: int = 2,
                               stage_axis: str = place.AXIS_STAGE,
                               wire_int8: bool = False) -> jax.Array:
    """Interleaved virtual-stage pipeline (the 1F1B-family schedule).

    stage_params: pytree with leading dim [v, S, ...] — virtual stage
    j = c·S + d lives at ``[c, d]`` (device d holds the v non-adjacent
    chunks {c·S + d}, the Megatron-interleaved placement). stage_fn maps
    (params_leaf [...], mb) -> mb with matching shape/dtype. x: [B, ...]
    with B divisible by num_microbatches and num_microbatches divisible
    by S. Semantics: virtual stages applied in order j = 0 .. S·v−1 —
    equal to ``sequential_apply`` on the [S·v, ...] stacking.

    The backward is autodiff through the scan (reverse pipeline), as in
    ``pipeline_apply``; what the interleaving buys is the halved bubble,
    not memory — pair with jax.checkpoint on stage_fn to trade the rest.

    wire_int8: the inter-stage activation sends (the ``state`` ring)
    travel as int8 + a per-shard scale in both directions (ops/q8
    make_ppermute_q8) — half the ICI bytes per hop, straight-through
    gradients; the input/output rings stay full precision so the
    pipeline's own data is untouched.
    """

    S = mesh.shape[stage_axis]
    v = num_chunks
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, f"batch {B} not divisible by {M} microbatches"
    if M % S:
        raise ValueError(f"microbatches ({M}) must divide by stages ({S}) "
                         f"for the interleaved schedule")
    mb = B // M
    K = M // S                       # input/output slots per device
    for path, leaf in jax.tree_util.tree_leaves_with_path(stage_params):
        if leaf.ndim < 2 or leaf.shape[0] != v or leaf.shape[1] != S:
            # dynamic_index_in_dim would silently CLAMP an out-of-range
            # chunk index, reusing the wrong chunk's weights — reject
            # mislaid params loudly instead
            raise ValueError(
                f"stage_params leaf {jax.tree_util.keystr(path)} must "
                f"have leading dims [num_chunks={v}, stages={S}, ...], "
                f"got {leaf.shape}")
    xs = x.reshape((M, mb) + x.shape[1:])
    Sv = S * v
    # exact makespan incl. the output ring: microbatch m finishes virtual
    # stage Sv-1 at T(m, Sv-1) and its owner (device m // K) captures it
    # S-1-owner down-hops later; the scan runs to the last capture.
    # At v=1 this is exactly the GPipe M + S - 1.
    def _t_last(m):
        return (m // S) * Sv + m % S + Sv - 1
    T_steps = 1 + max(_t_last(p * K + K - 1) + (S - 1 - p)
                      for p in range(S))

    param_specs = jax.tree_util.tree_map(
        lambda leaf: P(None, stage_axis), stage_params)

    def run(params_local, xs_local):
        # params_local leaves: [v, 1, ...] — this device's chunks
        p_here = jax.tree_util.tree_map(lambda l: l[:, 0], params_local)
        idx = jax.lax.axis_index(stage_axis)
        down = [(i, (i - 1) % S) for i in range(S)]
        up = [(i, (i + 1) % S) for i in range(S)]

        def t_inject(m):
            """Arrival step of microbatch m at virtual stage 0 (device 0):
            T(m, 0) = (m // S)·S·v + m % S. Strictly increasing in m, so
            the GPipe input-ring disjointness argument carries over."""
            return (m // S) * Sv + m % S

        def step(carry, t):
            state, g, h, outs_local = carry

            # --- input ring: device d injects slot q (mb m = d·K + q) at
            # t_inject(m) - d so one down-hop/step lands it on device 0
            # exactly at its schedule slot. Injection steps are distinct
            # per m, so windows never collide (see GPipe proof above).
            m_lo = idx * K
            # find the owned m with t_inject(m) - idx == t, i.e. invert
            # w = (m//S)·Sv + m%S at w = t + idx (valid only when the
            # within-group remainder is a real schedule slot, rem < S)
            w_in = t + idx
            g_grp, g_rem = w_in // Sv, w_in % Sv
            m_in = g_grp * S + g_rem
            inject = (g_rem < S) & (m_in >= m_lo) & (m_in < m_lo + K)
            cand = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.clip(m_in - m_lo, 0, K - 1), 0,
                keepdims=False)
            g = jnp.where(inject, cand, g)

            # --- which (m, j) does this device run at step t?
            # j = c·S + idx, T(m, j) = t  =>  u := t - idx,
            # c = (u mod Sv) // S, r = u mod S, group = u // Sv
            u = t - idx
            c = (u % Sv) // S
            grp = u // Sv
            m_here = grp * S + (u % S)
            busy = (u >= 0) & (m_here >= 0) & (m_here < M)
            c = jnp.clip(c, 0, v - 1)

            # virtual stage j = c·S + idx consumes the ring value; j == 0
            # (device 0, chunk 0 slot) consumes the fresh input instead
            is_first = (idx == 0) & ((u % Sv) < S)
            cur = jnp.where(is_first, g, state)
            p_c = jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, c, 0, keepdims=False), p_here)
            out = stage_fn(p_c, cur)

            # --- output ring: virtual stage Sv-1 (device S-1, last chunk)
            # finishes m at T(m, Sv-1); capture on owner p after S-1-p hops
            is_last = (idx == S - 1) & ((u % Sv) >= Sv - S) & busy
            h = jnp.where(is_last, out, h)
            w_out = t + idx - (S - 1) - (Sv - 1)
            og, orr = w_out // Sv, w_out % Sv
            m_out = og * S + orr
            own = ((w_out >= 0) & (orr < S) & (m_out >= m_lo)
                   & (m_out < m_lo + K))
            slot = jnp.clip(m_out - m_lo, 0, K - 1)
            old = jax.lax.dynamic_index_in_dim(outs_local, slot, 0,
                                               keepdims=False)
            outs_local = jax.lax.dynamic_update_index_in_dim(
                outs_local, jnp.where(own, h, old), slot, 0)

            if wire_int8:
                from paddle_tpu.ops import q8 as ops_q8
                state = ops_q8.make_ppermute_q8(stage_axis,
                                                tuple(up))(out)
            else:
                state = jax.lax.ppermute(out, stage_axis, up)
            g = jax.lax.ppermute(g, stage_axis, down)
            h = jax.lax.ppermute(h, stage_axis, down)
            return (state, g, h, outs_local), None

        zero_mb = jnp.zeros_like(xs_local[0])
        carry0 = (zero_mb, zero_mb, zero_mb, jnp.zeros_like(xs_local))
        (_, _, _, outs_local), _ = jax.lax.scan(
            step, carry0, jnp.arange(T_steps))
        return outs_local

    specs_mb = P(stage_axis)
    outs = jax.shard_map(run, mesh=mesh,
                     in_specs=(param_specs, specs_mb),
                     out_specs=specs_mb, check_vma=False)(stage_params, xs)
    return outs.reshape((B,) + x.shape[1:])


def sequential_apply(stage_params, x: jax.Array,
                     stage_fn: Callable) -> jax.Array:
    """Reference semantics: apply the S stages one after another."""
    def body(h, p_s):
        return stage_fn(p_s, h), None

    out, _ = jax.lax.scan(body, x, stage_params)
    return out

"""Ring attention: exact attention over sequences sharded across a mesh axis.

The long-context scaling path (SURVEY.md §5 — the reference's capability slot
was zero-padding LoD sequences; the modern TPU-native equivalent is context
parallelism). Design follows the ring-attention pattern: each device holds a
sequence shard of Q/K/V; K/V blocks rotate around the ring via
``lax.ppermute`` over ICI while an online-softmax accumulator (m, l, o) folds
in one block per step — compute overlaps the neighbor-exchange, memory stays
O(T/P) per chip, and the result is bit-for-bit exact attention (no
approximation).

Used inside ``shard_map`` over the ``seq`` mesh axis; composes with data
(batch) and model (heads) axes.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.core import place

NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One blockwise attention piece → (running max m, scores-exp sum l,
    unnormalized out). q [B,Tq,H,D]; k/v [B,Tk,Hkv,D] where H % Hkv == 0
    — Hkv < H is grouped-query attention (query head h reads kv head
    h // (H//Hkv)); the group broadcast happens HERE, in registers, so
    callers (and ring collectives) carry only Hkv-head K/V."""
    B, Tq, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # [B,Hkv,G,Tq]
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)                                  # [B,Hkv,G,Tq]
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return (m_safe.reshape(B, H, Tq), l.reshape(B, H, Tq),
            o.reshape(B, Tq, H, D))


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   lengths: Optional[jax.Array] = None,
                   scale: Optional[float] = None,
                   wire_int8: bool = False):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    Call inside shard_map. q: local shard [B, T_local, H, D]; k/v
    [B, T_local, Hkv, D] with H % Hkv == 0 (Hkv < H = grouped-query
    attention — the ppermute collectives then move only Hkv-head K/V, the
    group broadcast happens inside the block math); lengths: global
    per-example valid lengths [B] (replicated).
    Returns [B, T_local, H, D].
    """
    B, Tl, H, D = q.shape
    nshards = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = scale or (1.0 / math.sqrt(D))
    q32 = q.astype(jnp.float32)

    q_pos = my * Tl + jnp.arange(Tl)                         # [Tq] global

    def step_mask(src):
        k_pos = src * Tl + jnp.arange(Tl)                    # [Tk] global
        m = jnp.ones((B, Tl, Tl), bool)
        if causal:
            m = m & (q_pos[None, :, None] >= k_pos[None, None, :])
        if lengths is not None:
            m = m & (k_pos[None, None, :] < lengths[:, None, None])
        return m

    perm = [(i, (i + 1) % nshards) for i in range(nshards)]

    def body(step, carry):
        o, mx, l, k_cur, v_cur = carry
        src = (my - step) % nshards
        bm, bl, bo = _block_attn(q32, k_cur, v_cur, step_mask(src), scale)
        new_m = jnp.maximum(mx, bm)
        c_old = jnp.exp(mx - new_m)
        c_new = jnp.exp(bm - new_m)
        l = l * c_old + bl * c_new
        o = (o * c_old[..., None].swapaxes(1, 2) +
             bo * c_new[..., None].swapaxes(1, 2))
        # rotate K/V to the next device; skip the final dead rotation.
        # wire_int8: the rotation carries int8 + a per-shard scale
        # (ops/q8.make_ppermute_q8 — the KV-cache-int8 trick on the
        # wire; halves ICI bytes per hop, straight-through gradients).
        # Each hop re-quantizes, compounding <=0.5 LSB rounding per hop
        # (~sqrt(P) LSB total — bounded by the tolerance test at 8
        # shards); rotating raw int8 in the carry instead would sever
        # the gradient path through the integer loop carry, so the
        # re-quantizing codec is the differentiable design point.
        if wire_int8:
            from paddle_tpu.ops import q8 as ops_q8
            send = ops_q8.make_ppermute_q8(axis_name, tuple(perm))
        else:
            def send(t):
                return jax.lax.ppermute(t, axis_name, perm)
        k_nxt, v_nxt = jax.lax.cond(
            step < nshards - 1,
            lambda kv: (send(kv[0]), send(kv[1])),
            lambda kv: kv, (k_cur, v_cur))
        return o, new_m, l, k_nxt, v_nxt

    o0 = jnp.zeros((B, Tl, H, D), jnp.float32)
    m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    o, mx, l, _, _ = jax.lax.fori_loop(0, nshards, body, (o0, m0, l0, k, v))
    l = jnp.maximum(l, 1e-30)
    out = o / l[..., None].swapaxes(1, 2)
    return out.astype(q.dtype)


def full_attention(q, k, v, *, causal: bool = False,
                   lengths: Optional[jax.Array] = None,
                   scale: Optional[float] = None):
    """Reference single-device attention with the same masking semantics.
    k/v may carry Hkv <= H heads (GQA, H % Hkv == 0) — grouping is done
    in the einsum, no materialized head repetition."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    scale = scale or (1.0 / math.sqrt(D))
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((B, T, T), bool)
    if causal:
        i = jnp.arange(T)
        mask = mask & (i[None, :, None] >= i[None, None, :])
    if lengths is not None:
        mask = mask & (jnp.arange(T)[None, None, :] < lengths[:, None, None])
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None], p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)


def ring_attention_spmd(q, k, v, mesh: Mesh, *, causal: bool = False,
                        lengths: Optional[jax.Array] = None,
                        batch_axis: str = place.AXIS_DATA,
                        seq_axis: str = place.AXIS_SEQ,
                        head_axis: str = place.AXIS_MODEL,
                        scale: Optional[float] = None,
                        use_flash: bool = False,
                        interpret: Optional[bool] = None,
                        wire_int8: bool = False):
    """shard_map wrapper: q/k/v [B, T, H, D] with B over ``batch_axis``,
    T over ``seq_axis``, and heads over ``head_axis`` when the mesh has one
    (tensor parallelism: each model-shard attends its own heads — attention
    is head-separable so no collective is needed on that axis); lengths [B]
    sharded with the batch. k/v may carry Hkv < H heads (GQA) — the ring
    collectives then rotate the Hkv-head tensors; head-axis TP applies
    only when it divides BOTH head counts. ``use_flash`` swaps the
    per-block engine for the Pallas flash kernel (packed equal-length
    sequences only). ``wire_int8`` sends the rotating K/V as int8 + a
    per-shard scale in both engines. Backward precision differs: the
    flash engine's hand-written VJP keeps its dk/dv accumulators fp32
    on the wire; the jnp engine's autodiff backward sends cotangents
    through the same int8 codec per hop (bounded by the grad tolerance
    test — prefer the flash engine for training at scale)."""

    H, Hkv = q.shape[2], k.shape[2]
    tp = (head_axis if head_axis in mesh.axis_names
          and mesh.shape[head_axis] > 1 and H % mesh.shape[head_axis] == 0
          and Hkv % mesh.shape[head_axis] == 0
          else None)
    qkv_spec = P(batch_axis, seq_axis, tp, None)
    len_spec = P(batch_axis)
    if use_flash and lengths is not None:
        raise ValueError(_FLASH_RAGGED_MSG)
    interpret = _default_interpret(interpret)
    if wire_int8 and lengths is not None:
        # the per-shard scale is an absmax over the WHOLE rotating shard;
        # padding K/V beyond lengths would inflate it and collapse the
        # valid rows' precision — reject rather than silently degrade
        raise ValueError("wire_int8 supports packed equal-length "
                         "sequences only (padding would contaminate the "
                         "wire quantization scale); pass lengths=None")
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                           scale=scale, wire_int8=wire_int8)

    if lengths is None:
        if use_flash:
            def wrapped(q_, k_, v_):
                return ring_flash_attention(
                    q_, k_, v_, axis_name=seq_axis, causal=causal,
                    scale=scale, interpret=interpret,
                    wire_int8=wire_int8)
        else:
            def wrapped(q_, k_, v_):
                return fn(q_, k_, v_, lengths=None)
        return jax.shard_map(wrapped, mesh=mesh,
                         in_specs=(qkv_spec,) * 3,
                         out_specs=qkv_spec, check_vma=False)(q, k, v)

    def wrapped(q_, k_, v_, len_):
        return fn(q_, k_, v_, lengths=len_)
    return jax.shard_map(wrapped, mesh=mesh,
                     in_specs=(qkv_spec, qkv_spec, qkv_spec, len_spec),
                     out_specs=qkv_spec, check_vma=False)(q, k, v, lengths)


def _default_interpret(interpret):
    """Off-TPU the Mosaic lowering doesn't exist; interpret mode keeps
    the same kernel code path (tests, CPU dryruns) at reduced speed."""
    if interpret is None:
        return jax.devices()[0].platform != "tpu"
    return interpret


_FLASH_RAGGED_MSG = ("flash attention in context parallelism supports "
                     "packed equal-length sequences only; pass "
                     "lengths=None or use the jnp engine "
                     "(use_flash=False)")


def alltoall_attention_spmd(q, k, v, mesh: Mesh, *, causal: bool = False,
                            lengths: Optional[jax.Array] = None,
                            batch_axis: str = place.AXIS_DATA,
                            seq_axis: str = place.AXIS_SEQ,
                            head_axis: str = place.AXIS_MODEL,
                            scale: Optional[float] = None,
                            use_flash: bool = False,
                            interpret: Optional[bool] = None):
    """All-to-all (Ulysses-style) sequence parallelism — the other
    context-parallel layout: instead of rotating K/V around a ring, one
    all-to-all RESHUFFLES [B, T/P, H, D] (sequence-sharded) into
    [B, T, H/P, D] (head-sharded), attention runs fully local per head
    group, and a second all-to-all restores sequence sharding. Two
    collectives total per attention vs P−1 ring hops — better when
    H ≥ P and the interconnect favors large all-to-alls; ring wins when
    heads are scarce or memory for the full-T K/V slice is tight.
    Autodiff transposes the all-to-alls, so no custom VJP is needed.

    q [B, T, H, D]; k/v may carry Hkv ≤ H heads (GQA) — all three are
    head-scattered, so the seq-axis size (times any head-axis TP shard)
    must divide BOTH H and Hkv. When the mesh carries a >1 ``head_axis``
    that divides the head counts, heads are ALSO tensor-parallel over it
    (as in ring_attention_spmd — each model shard scatters only its own
    heads). ``use_flash`` runs the local attention with the Pallas flash
    kernel (packed equal-length only); ragged ``lengths`` use the jnp
    engine.
    """

    P_ = mesh.shape[seq_axis]
    H, Hkv = q.shape[2], k.shape[2]
    tp_sz = (mesh.shape[head_axis]
             if head_axis in mesh.axis_names else 1)
    tp = (head_axis if tp_sz > 1 and H % (tp_sz * P_) == 0
          and Hkv % (tp_sz * P_) == 0 else None)
    denom = (tp_sz if tp else 1) * P_
    if H % denom or Hkv % denom:
        raise ValueError(
            f"alltoall attention: seq axis size {P_} must divide both "
            f"n_heads={H} and kv_heads={Hkv}; use ring attention for "
            f"head counts that don't split")
    if use_flash and lengths is not None:
        raise ValueError(_FLASH_RAGGED_MSG)
    interpret = _default_interpret(interpret)

    qkv_spec = P(batch_axis, seq_axis, tp, None)
    len_spec = P(batch_axis)

    def local(q_, k_, v_, len_):
        # [B, T/P, H, D] -> all_to_all -> [B, T, H/P, D]: split the head
        # axis across the group, concatenate the sequence shards
        def scatter(t):
            return jax.lax.all_to_all(t, seq_axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        def gather(t):
            return jax.lax.all_to_all(t, seq_axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        qg, kg, vg = scatter(q_), scatter(k_), scatter(v_)
        if use_flash:
            from paddle_tpu.ops.pallas import flash_attention
            out = flash_attention(qg, kg, vg, causal=causal,
                                  sm_scale=scale, interpret=interpret)
        else:
            out = full_attention(qg, kg, vg, causal=causal, lengths=len_,
                                 scale=scale)
        return gather(out)

    if lengths is None:
        return jax.shard_map(
            lambda a, b, c: local(a, b, c, None), mesh=mesh,
            in_specs=(qkv_spec,) * 3, out_specs=qkv_spec,
            check_vma=False)(q, k, v)
    return jax.shard_map(local, mesh=mesh,
                     in_specs=(qkv_spec, qkv_spec, qkv_spec, len_spec),
                     out_specs=qkv_spec, check_vma=False)(q, k, v, lengths)


def ring_flash_attention(q, k, v, *, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: bool = False,
                         wire_int8: bool = False):
    """Ring attention with the Pallas flash kernel as the per-block engine.

    Same exactness and rotation scheme as ``ring_attention``, but each
    ring step runs the streaming-softmax kernel on (q_local, k_block) —
    no [Tq, Tk] score tensor exists even per step, so per-chip memory is
    O(T/P·D) and the kernel's MXU pipeline is reused across the ring.
    Blocks fold by the logsumexp combination rule; the backward re-walks
    the ring calling the flash backward kernel with the GLOBAL logsumexp
    (exact: p = exp(s − lse) under any key partition), with dk/dv
    accumulators riding the rotation so each arrives back at its owner
    after the full cycle.

    Equal-length (packed) sequences only — for ragged ``lengths`` use
    ``ring_attention``. Call inside shard_map; q [B, T_local, H, D],
    k/v [B, T_local, Hkv, D] with H % Hkv == 0 (GQA: the ring rotates
    Hkv-head K/V and dk/dv; the H-head expansion is local per step).
    """
    from paddle_tpu.ops.pallas.attention import (planning_budget,
                                                 select_block_sizes)

    Tl, D = q.shape[1], q.shape[3]
    scale = scale or (1.0 / math.sqrt(D))
    if block_q and block_k:
        bq, bk = min(block_q, Tl), min(block_k, Tl)
    else:
        # block selection keyed on the LOCAL shard length (each ring step
        # runs the kernel on [Tl, D] tiles)
        bq_auto, bk_auto = select_block_sizes(
            Tl, D, q.dtype, planning_budget(bool(interpret)))
        bq = min(block_q, Tl) if block_q else bq_auto
        bk = min(block_k, Tl) if block_k else bk_auto
    return _ring_flash(q, k, v, axis_name, causal, scale, bq, bk,
                       interpret, wire_int8)


def _bhtd(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _btHd(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _expand_groups(kv_r, b, g):
    """[B·Hkv, T, D] -> [B·H, T, D] by repeating each kv head g times —
    the LOCAL GQA broadcast done after the ring rotation, so ppermute
    only ever moves the Hkv-head tensor. Query head h = hkv·g + i maps
    to kv head hkv, matching the models' head grouping convention."""
    if g == 1:
        return kv_r
    bh, t, d = kv_r.shape
    return jnp.repeat(kv_r.reshape(b, bh // b, t, d), g,
                      axis=1).reshape(bh * g, t, d)


def _group_sum(d_r, b, g):
    """[B·H, T, D] -> [B·Hkv, T, D]: fold the q-head-group gradients back
    onto their shared kv head (adjoint of _expand_groups)."""
    if g == 1:
        return d_r
    bh, t, d = d_r.shape
    return d_r.reshape(b, bh // (b * g), g, t, d).sum(axis=2).reshape(
        bh // g, t, d)


def _fold(o, lse, ob, lseb):
    """Combine two normalized partial attentions by logsumexp weights."""
    m = jnp.maximum(lse, lseb)
    w1 = jnp.exp(lse - m)
    w2 = jnp.exp(lseb - m)
    tot = jnp.maximum(w1 + w2, 1e-30)
    o = (o * w1[..., None] + ob * w2[..., None]) / tot[..., None]
    return o, m + jnp.log(tot)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, axis_name, causal, scale, block_q, block_k,
                interpret, wire_int8=False):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q,
                             block_k, interpret, wire_int8)
    return out


def _kv_rot(axis_name, perm, wire_int8):
    """The K/V hop: full precision, or the int8+scale codec
    (ops/q8.ppermute_q8_raw). Gradient ACCUMULATORS never use this —
    re-quantizing a running sum each hop would compound error."""
    if wire_int8:
        from paddle_tpu.ops import q8 as ops_q8

        def rot1(x):
            return ops_q8.ppermute_q8_raw(x, axis_name, perm)
    else:
        def rot1(x):
            return jax.lax.ppermute(x, axis_name, perm)
    return rot1


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                    interpret, wire_int8=False):
    from paddle_tpu.ops.pallas.attention import NEG_INF as FNEG
    from paddle_tpu.ops.pallas.attention import flash_block_fwd

    B, Tl, H, D = q.shape
    G = H // k.shape[2]                 # GQA group size (1 = MHA)
    nshards = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    # rotate k/v in the kernel's [BH, T, D] layout: one transpose per
    # tensor instead of one per ring step (ppermute is layout-agnostic).
    # Under GQA kr/vr stay at Hkv heads — the ring moves the small tensor;
    # the per-step _expand_groups broadcast is local VMEM/HBM traffic the
    # kernel would read anyway.
    qr, kr, vr = _bhtd(q), _bhtd(k), _bhtd(v)

    # step 0: the diagonal block — the only one needing the causal mask
    o, lse = flash_block_fwd(qr, _expand_groups(kr, B, G),
                             _expand_groups(vr, B, G), scale, causal,
                             block_q, block_k, interpret)
    o = o.astype(jnp.float32)

    kv_hop = _kv_rot(axis_name, perm, wire_int8)

    def body(step, carry):
        o, lse, k_cur, v_cur = carry
        # rotate first: at step j the local block is (my - j) mod n
        k_cur = kv_hop(k_cur)
        v_cur = kv_hop(v_cur)
        ob, lseb = flash_block_fwd(qr, _expand_groups(k_cur, B, G),
                                   _expand_groups(v_cur, B, G), scale,
                                   False, block_q, block_k, interpret)
        if causal:
            src = (my - step) % nshards
            lseb = jnp.where(src < my, lseb, FNEG)
        o, lse = _fold(o, lse, ob.astype(jnp.float32), lseb)
        return o, lse, k_cur, v_cur

    o, lse, _, _ = jax.lax.fori_loop(1, nshards, body, (o, lse, kr, vr))
    return _btHd(o, B, H).astype(q.dtype), lse


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, block_q,
                        block_k, interpret, wire_int8=False):
    out, lse = _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q,
                               block_k, interpret, wire_int8)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, block_q, block_k,
                        interpret, wire_int8, res, do):
    from paddle_tpu.ops.pallas.attention import NEG_INF as FNEG
    from paddle_tpu.ops.pallas.attention import flash_block_bwd

    q, k, v, out, lse = res
    B, Tl, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    nshards = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    qr, outr, dor = _bhtd(q), _bhtd(out), _bhtd(do)
    kr, vr = _bhtd(k), _bhtd(v)

    kv_hop = _kv_rot(axis_name, perm, wire_int8)

    def rot_kv(*xs):
        return tuple(kv_hop(x) for x in xs)

    def rot(*xs):
        return tuple(jax.lax.ppermute(x, axis_name, perm) for x in xs)

    # diagonal block first (the causal variant), then rotate the block
    # TOGETHER with its gradient accumulator: at every step the local
    # (k, v, dk, dv) all describe the same block, each device adds its
    # contribution, and after n total rotations the accumulators are home.
    # GQA: the kernel runs in the H-head layout (local expand) but dk/dv
    # are group-summed back to Hkv heads BEFORE rotating, so every
    # ppermute moves only Hkv-head tensors.
    dq0, dk0, dv0 = flash_block_bwd(qr, _expand_groups(kr, B, G),
                                    _expand_groups(vr, B, G), outr, lse,
                                    dor, scale, causal, block_q, block_k,
                                    interpret)
    dq_acc = dq0.astype(jnp.float32)        # [BH, Tl, D], stays local
    k_cur, v_cur = rot_kv(kr, vr)
    dk_acc, dv_acc = rot(
        _group_sum(dk0.astype(jnp.float32), B, G),
        _group_sum(dv0.astype(jnp.float32), B, G))

    def body(step, carry):
        dq_acc, dk_acc, dv_acc, k_cur, v_cur = carry
        lse_b = lse
        if causal:
            # excluded (future) blocks: mask INSIDE the exponent by
            # feeding lse=+big so p = exp(s - lse) is exactly 0 — zeroing
            # the kernel's output after the fact would turn an overflowed
            # p (s far above the global lse, which excludes this block)
            # into 0·inf = NaN
            src = (my - step) % nshards
            lse_b = jnp.where(src < my, lse, -FNEG)
        dqb, dkb, dvb = flash_block_bwd(qr, _expand_groups(k_cur, B, G),
                                        _expand_groups(v_cur, B, G),
                                        outr, lse_b, dor, scale, False,
                                        block_q, block_k, interpret)
        dq_acc = dq_acc + dqb.astype(jnp.float32)
        dk_acc = dk_acc + _group_sum(dkb.astype(jnp.float32), B, G)
        dv_acc = dv_acc + _group_sum(dvb.astype(jnp.float32), B, G)
        # the accumulators need all n rotations to arrive home; the K/V
        # blocks are dead after the last step — skip their final hop
        # (with wire_int8 it would also burn a quantize + extra sends)
        k_cur, v_cur = jax.lax.cond(
            step < nshards - 1, lambda kv: rot_kv(*kv), lambda kv: kv,
            (k_cur, v_cur))
        dk_acc, dv_acc = rot(dk_acc, dv_acc)
        return dq_acc, dk_acc, dv_acc, k_cur, v_cur

    dq_acc, dk_acc, dv_acc, _, _ = jax.lax.fori_loop(
        1, nshards, body, (dq_acc, dk_acc, dv_acc, k_cur, v_cur))
    return (_btHd(dq_acc, B, H).astype(q.dtype),
            _btHd(dk_acc, B, Hkv).astype(k.dtype),
            _btHd(dv_acc, B, Hkv).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)

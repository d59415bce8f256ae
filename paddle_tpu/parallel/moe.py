"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

The modern occupant of the reference's "scale parameters beyond one box"
slot (SURVEY.md §2.3 — sharded sparse embeddings / pserver-sharded weights;
here the GShard/Switch design): tokens are routed by a learned gate, experts
are sharded over the ``expert`` axis, and dispatch/combine are dense one-hot
einsums so XLA lowers them to all-to-alls over ICI instead of host gathers.

Capacity-factor dispatch keeps every shape static (XLA requirement): each
expert processes at most ``capacity`` tokens per batch; overflow tokens are
dropped (standard Switch behavior) and the aux loss keeps the router
balanced so drops stay rare.
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core import place


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    num_experts: int
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    top_k: int = 1                  # 1 = Switch; 2 = GShard-style top-2
    normalize_gates: bool = True    # renormalize the k selected gates to
                                    # sum to 1 (GShard convention; ignored
                                    # at top_k=1 where Switch keeps raw p)


def init_params(key: jax.Array, cfg: MoEConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = 1.0 / math.sqrt(D)
    return {
        "gate": jax.random.normal(k1, (D, E), jnp.float32) * s,
        "w_in": jax.random.normal(k2, (E, D, F), jnp.float32) * s,
        "w_out": jax.random.normal(k3, (E, F, D), jnp.float32) *
        (1.0 / math.sqrt(F)),
    }


def param_shardings(cfg: MoEConfig, mesh: Mesh):
    """Experts sharded over the ``expert`` axis; gate replicated."""
    E = place.AXIS_EXPERT

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    return {"gate": ns(), "w_in": ns(E, None, None),
            "w_out": ns(E, None, None)}


def _route(params, x: jax.Array, cfg: MoEConfig, cap: int):
    """Shared gating + capacity accounting: returns (disp [N, E, cap],
    combine [N, E, cap], frac [E], mean_p [E]).

    One dispatch path serves every k: choice c of every token claims
    capacity AFTER all choices < c (first choices never lose their slot
    to second choices — the GShard priority rule), the [N, E, cap]
    dispatch one-hot sums over choices, and the combine tensor carries
    the per-choice gate weights, so the expert einsums are identical to
    the Switch path."""
    N, _ = x.shape
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32), params["gate"])
    probs = jax.nn.softmax(logits, axis=-1)                 # [N, E]
    gate_k, expert_k = jax.lax.top_k(probs, k)              # [N, k]
    if k > 1 and cfg.normalize_gates:
        gate_k = gate_k / jnp.maximum(
            jnp.sum(gate_k, axis=-1, keepdims=True), 1e-9)

    # capacity accounting over (choice-major, token) order: flatten the
    # [k, N] assignment grid so cumsum gives all first choices priority
    # over any second choice, etc.
    oh_k = jax.nn.one_hot(expert_k.T.reshape(k * N), E,
                          dtype=jnp.int32)                  # [k*N, E]
    pos = jnp.cumsum(oh_k, axis=0) * oh_k - 1               # [k*N, E]
    pos_in_expert = jnp.sum(pos * oh_k, axis=1)             # [k*N]
    keep = pos_in_expert < cap

    # per-choice dispatch one-hots [k*N, E, cap] → summed over choices to
    # the token-level dispatch [N, E, cap] (slots are disjoint, so the
    # sum stays one-hot); combine carries gate weights on the same slots
    slot_oh = jax.nn.one_hot(jnp.clip(pos_in_expert, 0, cap - 1), cap)
    disp_k = oh_k.astype(jnp.float32)[:, :, None] * slot_oh[:, None, :]
    disp_k = jnp.where(keep[:, None, None], disp_k, 0.0)
    disp_k = disp_k.reshape(k, N, E, cap)
    disp = jnp.sum(disp_k, axis=0)                          # [N, E, cap]
    combine = jnp.einsum("knec,nk->nec", disp_k, gate_k)

    # load-balance stats (Switch eq. 4 / GShard l_aux inputs): first
    # choices drive balance
    frac = jnp.mean(jax.nn.one_hot(expert_k[:, 0], E, dtype=jnp.float32),
                    axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return disp, combine, frac, mean_p


def moe_ffn(params, x: jax.Array, cfg: MoEConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jax.Array, jax.Array]:
    """Top-k MoE feed-forward (k=1: Switch; k=2: GShard-style top-2).

    x: [N, D] tokens (flatten batch*seq first) → (out [N, D], aux_loss).
    With a mesh carrying an ``expert`` axis, einsum operands get sharding
    constraints so dispatch/combine become all-to-alls over ICI.
    """
    N, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, num_experts={E}]")
    cap = max(1, int(cfg.capacity_factor * k * N / E))
    disp, combine, frac, mean_p = _route(params, x, cfg, cap)

    # NOTE (round-4 finding): an int8 wire codec at these sharding
    # constraints is a NO-OP — compiled HLO shows the dispatch einsum
    # ("nec,nd->ecd", contracting the token-sharded axis) communicates
    # via fp32 partial all-reduces BEFORE any constraint-point quantize
    # runs. Quantized MoE dispatch lives in the explicit-collective form
    # instead: moe_ffn_a2a(..., wire_int8=True) below (round 5).
    def constrain(v, spec):
        if mesh is None or place.AXIS_EXPERT not in mesh.axis_names:
            return v
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))

    # expert inputs [E, cap, D] — the all-to-all boundary
    xe = jnp.einsum("nec,nd->ecd", disp, x.astype(jnp.float32))
    xe = constrain(xe, P(place.AXIS_EXPERT, None, None))
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_in"])
    h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    ye = constrain(ye, P(place.AXIS_EXPERT, None, None))
    out = jnp.einsum("nec,ecd->nd", combine, ye)            # gate-weighted

    # load-balance aux loss (Switch eq. 4 / GShard l_aux): E * Σ_e
    # frac_first_choice_e * mean_prob_e
    aux = cfg.aux_loss_weight * E * jnp.sum(frac * mean_p)
    return out.astype(x.dtype), aux


def moe_ffn_a2a(params, x: jax.Array, cfg: MoEConfig, mesh: Mesh,
                wire_int8: bool = False) -> Tuple[jax.Array, jax.Array]:
    """MoE feed-forward in the explicit-collective form: shard_map over
    the ``expert`` axis with ``lax.all_to_all`` dispatch/combine.

    Tokens are sharded over the expert axis (x: [N, D] global, N/P per
    shard); capacity is per (expert, source shard) — GShard's layout:
    cap_s = ceil(cf·k·N_s/E) slots per expert from EACH source shard, so
    total expert capacity matches the einsum path but a shard cannot
    borrow another shard's unused slots (documented divergence; drop
    patterns differ only under imbalance).

    ``wire_int8``: the dispatch AND combine all-to-alls carry int8 +
    per-destination-block fp32 scales (ops/q8.make_all_to_all_q8) — half
    the ICI bytes of the bf16 wire, straight-through gradients through
    the codec. This is the form the round-4 HLO inspection demanded: the
    quantize runs BEFORE the collective, inside the shard, so s8 is what
    crosses the wire (asserted in tests/test_moe_pipeline.py).
    """

    ax = place.AXIS_EXPERT
    if ax not in mesh.axis_names:
        raise ValueError(f"mesh must carry an {ax!r} axis")
    pe = mesh.shape[ax]
    N, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    if not 1 <= k <= E:
        raise ValueError(f"top_k={k} must be in [1, num_experts={E}]")
    if E % pe or N % pe:
        raise ValueError(f"num_experts={E} and N={N} must both be "
                         f"divisible by the expert axis size {pe}")
    e_local, n_s = E // pe, N // pe
    cap_s = max(1, int(math.ceil(cfg.capacity_factor * k * n_s / E)))

    if wire_int8:
        from paddle_tpu.ops import q8 as ops_q8
        a2a = ops_q8.make_all_to_all_q8(ax)
    else:
        def a2a(v):
            return jax.lax.all_to_all(v, ax, 0, 0)

    def body(gate, w_in, w_out, xs):
        # xs: [n_s, D] local tokens; w_in/w_out: [e_local, ...] local
        disp, combine, frac, mean_p = _route(
            {"gate": gate}, xs, cfg, cap_s)
        xe = jnp.einsum("nec,nd->ecd", disp, xs.astype(jnp.float32))
        # leading axis = destination shard, then its local expert group
        xe = xe.reshape(pe, e_local, cap_s, D)
        xe = a2a(xe)                      # → leading axis = source shard
        xe = xe.transpose(1, 0, 2, 3).reshape(e_local, pe * cap_s, D)
        h = jax.nn.gelu(jnp.einsum("esd,edf->esf", xe, w_in))
        ye = jnp.einsum("esf,efd->esd", h, w_out)
        ye = ye.reshape(e_local, pe, cap_s, D).transpose(1, 0, 2, 3)
        ye = a2a(ye)                      # back to the source shards
        ye = ye.reshape(E, cap_s, D)
        out = jnp.einsum("nec,ecd->nd", combine, ye)
        # aux loss over GLOBAL balance stats (token means are equal-sized
        # per shard, so pmean == the einsum path's full-batch mean)
        frac_g = jax.lax.pmean(frac, ax)
        mean_p_g = jax.lax.pmean(mean_p, ax)
        aux = cfg.aux_loss_weight * E * jnp.sum(frac_g * mean_p_g)
        return out.astype(xs.dtype), aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(ax, None, None), P(ax, None, None),
                  P(ax, None)),
        out_specs=(P(ax, None), P()),
        check_vma=False)
    return fn(params["gate"], params["w_in"], params["w_out"], x)

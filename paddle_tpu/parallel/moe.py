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


# -- dropless expert layer (no capacity, nothing dropped) ----------------------

_HI = jax.lax.Precision.HIGHEST


def dropless_init_params(key: jax.Array, d_model: int, d_ff: int,
                         num_experts: int, held: int, shared_ff: int,
                         dtype=jnp.float32, route: str = "softmax"):
    """``router`` over ALL experts (float32), SwiGLU weights of the
    ``held`` ones, a shared expert of width ``shared_ff``: under a
    sigmoid gate (``s_gate``) for ``route="softmax"``; ungated, with a
    selection bias per expert (``router_bias``, float32, small and not
    zero so that selection and weighting differ), for
    ``route="sigmoid_bias"``."""
    k = jax.random.split(key, 8)
    D, F, Fs = d_model, d_ff, shared_ff
    s = 1.0 / math.sqrt(D)

    def nrm(kk, shape, scale):
        return (jax.random.normal(kk, shape, jnp.float32)
                * scale).astype(dtype)

    out = {
        "router": jax.random.normal(k[0], (D, num_experts),
                                    jnp.float32) * s,
        "w1": nrm(k[1], (held, D, F), s), "w3": nrm(k[2], (held, D, F), s),
        "w2": nrm(k[3], (held, F, D), 1.0 / math.sqrt(F)),
        "s_w1": nrm(k[5], (D, Fs), s), "s_w3": nrm(k[6], (D, Fs), s),
        "s_w2": nrm(k[7], (Fs, D), 1.0 / math.sqrt(Fs)),
    }
    if route == "sigmoid_bias":
        out["router_bias"] = jax.random.normal(
            jax.random.fold_in(key, 8), (num_experts,), jnp.float32) * 0.1
    else:
        out["s_gate"] = nrm(k[4], (D,), s)
    return out


def route_softmax(params, logits, k: int):
    """softmax over ALL experts -> top-k -> the k weights renormalised
    over the k."""
    gate, expert = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return gate / jnp.sum(gate, axis=-1, keepdims=True), expert


def route_sigmoid_bias(scale: float):
    """The rule of a router trained without an auxiliary loss: scores
    ``s = sigmoid(logits)``; the k experts are chosen by ``s + b``
    (``params["router_bias"]``, which steers the load and nothing
    else); the weights are the UNBIASED scores of the chosen,
    renormalised over the k and multiplied by ``scale``."""
    def route(params, logits, k: int):
        s = jax.nn.sigmoid(logits)
        _, expert = jax.lax.top_k(
            s + params["router_bias"].astype(jnp.float32), k)
        gate = jnp.take_along_axis(s, expert, axis=-1)
        return gate / jnp.sum(gate, axis=-1, keepdims=True) * scale, expert
    return route


def moe_dropless(params, x: jax.Array, *, top_k: int,
                 held: Tuple[int, int], valid: Optional[jax.Array] = None,
                 layer=0, route=route_softmax
                 ) -> Tuple[jax.Array, jax.Array]:
    """Top-k SwiGLU experts without capacity: x [N, D] -> (out [N, D],
    stats int32 [2] = (assignments kept here, distinct held experts hit)).

    The router scores ALL experts in float32 (``params["router"]``
    [D, E]) and ``route(params, logits, k) -> (weights [N, k], experts
    [N, k])`` turns the scores into the k assignments of a token
    (:func:`route_softmax`: the weights renormalised over the k;
    :func:`route_sigmoid_bias`), as
    expert parallelism has every chip do; of the N*k assignments this
    chip keeps those whose expert lies in ``held = (first, count)``,
    the span whose weights it holds (``w1``/``w3`` [count, D, F], ``w2``
    [count, F, D]). Kept rows are sorted by expert and go through three
    grouped matrix products (``jax.lax.ragged_dot``: a group per held
    expert, an expert nobody chose costs nothing); each row returns to
    its token under its weight. An assignment to an absent expert adds
    nothing here: its weight took part in the renormalisation, its
    product is the other chip's. The shared expert (``s_w1``/``s_w3``/
    ``s_w2``; under ``sigmoid(x . s_gate)`` where the tree has an
    ``s_gate``, ungated where it has none) is added once, to every
    token. Rows where ``valid`` [N] is False (a chunk's padding) are
    routed nowhere and counted nowhere.

    The expert weights may be a STACK over layers ([..., count, D, F]:
    every leading axis a layer axis) with ``layer`` (traced) the one to
    use: the groups are then the stack's experts and only this layer's
    have rows, so the grouped product reads the hit experts where they
    lie. Slicing the layer out of the stack first (a scan's ``xs``)
    copies its whole 800 MB for a step that needs a fraction of them.
    """
    N, D = x.shape
    first, count = held
    k = int(top_k)
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=_HI)
    gate, expert = route(params, logits, k)
    local = expert - first
    kept = (local >= 0) & (local < count)
    if valid is not None:
        kept = kept & valid[:, None]
    w1, w3, w2 = (params[n].reshape((-1,) + params[n].shape[-2:])
                  .astype(x.dtype) for n in ("w1", "w3", "w2"))
    groups = w1.shape[0]                    # layers in the stack x count
    # absent and padded assignments sort behind every group
    flat = jnp.where(kept, local + layer * count, groups).reshape(N * k)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((groups + 1,), jnp.int32).at[flat].add(1)[:groups]
    rows = jnp.take(x, order // k, axis=0)                  # [N*k, D]
    up = jax.lax.ragged_dot(rows, w1, sizes,
                            preferred_element_type=jnp.float32)
    lin = jax.lax.ragged_dot(rows, w3, sizes,
                             preferred_element_type=jnp.float32)
    y = jax.lax.ragged_dot((jax.nn.silu(up) * lin).astype(x.dtype), w2,
                           sizes, preferred_element_type=jnp.float32)
    # rows past the last group belong to no expert: whatever the grouped
    # product left there is masked, not multiplied
    wrow = jnp.take(gate.reshape(N * k), order)
    y = jnp.where(jnp.take(kept.reshape(N * k), order)[:, None],
                  y * wrow[:, None], 0.0)
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    out = jnp.sum(jnp.take(y, back, axis=0).reshape(N, k, D), axis=1)
    sw1, sw3, sw2 = (params[n].astype(x.dtype)
                     for n in ("s_w1", "s_w3", "s_w2"))
    sh = (jax.nn.silu(x @ sw1) * (x @ sw3)) @ sw2
    if "s_gate" in params:
        sg = jax.nn.sigmoid(jnp.einsum(
            "nd,d->n", x.astype(jnp.float32),
            params["s_gate"].astype(jnp.float32), precision=_HI))
        out = out + sg[:, None] * sh.astype(jnp.float32)
    else:
        out = out + sh.astype(jnp.float32)
    stats = jnp.stack([jnp.sum(kept.astype(jnp.int32)),
                       jnp.sum((sizes > 0).astype(jnp.int32))])
    return out.astype(x.dtype), stats

"""Operations and bytes a stretch of serving the GLM-4.7-Flash
configuration needs, from its shapes, what the generator saw and what
the engine counted, whatever implements it: 2 FLOP a multiply-add, the
attention in its published (up-projecting) form, each weight once per
program execution at the width the configuration keeps it in, only the
experts that were hit, the live latent rows once, no padding, nothing
recomputed.
"""

from chipbench import reduce


def dims(config: dict) -> dict:
    m = config
    Ld = m["first_k_dense_replace"]
    return {"D": m["hidden_size"], "V": m["vocab_size"],
            "H": m["num_attention_heads"], "rq": m["q_lora_rank"],
            "rkv": m["kv_lora_rank"], "dn": m["qk_nope_head_dim"],
            "dr": m["qk_rope_head_dim"], "dv": m["v_head_dim"],
            "E": m["n_routed_experts"],
            "held": int(m.get("num_experts_held", m["n_routed_experts"])),
            "F": m["moe_intermediate_size"],
            "Fs": m["moe_intermediate_size"] * m["n_shared_experts"],
            "Fd": m["intermediate_size"], "L_dense": Ld,
            "L_sparse": m["num_hidden_layers"] - Ld}


def mixer_params(d: dict) -> int:
    """q_a, q_b, kv_a, kv_b, o: every token multiplies each once."""
    return (d["D"] * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["rkv"] + d["dr"])
            + d["rkv"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * d["D"])


def expert_params(d: dict) -> int:
    return 3 * d["D"] * d["F"]


def dense_params(d: dict) -> int:
    """Weights that every token multiplies, over all layers: mixers,
    the dense layers' SwiGLU, routers and shared experts."""
    layers = d["L_dense"] + d["L_sparse"]
    return (layers * mixer_params(d) + d["L_dense"] * 3 * d["D"] * d["Fd"]
            + d["L_sparse"] * (d["D"] * d["E"] + 3 * d["D"] * d["Fs"]))


def stretch_flops(d: dict, tokens: float, keys: float, assignments: float,
                  sampled: float) -> float:
    """Forward FLOPs of ``tokens`` tokens that attended over ``keys``
    keys in all (summed over tokens, per layer), with ``assignments``
    token-to-expert products computed here and the head applied at
    ``sampled`` positions."""
    layers = d["L_dense"] + d["L_sparse"]
    return (2.0 * dense_params(d) * tokens
            + 2.0 * expert_params(d) * assignments
            + 2.0 * layers * d["H"] * (d["dn"] + d["dr"] + d["dv"]) * keys
            + 2.0 * d["V"] * d["D"] * sampled)


def traced_flops(ctx: dict):
    """The traced stretch's FLOPs: its decoded tokens, the prompts whose
    first token fell in it, the engine's count of expert assignments."""
    a, b = ctx["traced_interval"]
    n_tok, ctx_sum = reduce.decode_tokens_in(ctx["records"], a, b)
    prompts = reduce.prefills_in(ctx["records"], a, b)
    assignments = ctx["traced_counters"].get("engine_moe_assignments_total")
    if assignments is None:
        return None
    keys = ctx_sum + sum(p * (p + 1) / 2.0 for p in prompts)
    return stretch_flops(ctx["dims"], n_tok + float(sum(prompts)), keys,
                         assignments, n_tok + len(prompts))


def step_weight_bytes(d: dict) -> float:
    """What one decode execution reads whatever the tokens: mixers, the
    dense layers' SwiGLU, shared experts and the head at 2 bytes, the
    float32 routers, their biases and the norms at 4."""
    layers = d["L_dense"] + d["L_sparse"]
    two = (layers * mixer_params(d) + d["L_dense"] * 3 * d["D"] * d["Fd"]
           + d["L_sparse"] * 3 * d["D"] * d["Fs"] + d["V"] * d["D"])
    four = (d["L_sparse"] * (d["D"] * d["E"] + d["E"])
            + layers * (2 * d["D"] + d["rq"] + d["rkv"]) + d["D"])
    return 2.0 * two + 4.0 * four


def latent_bytes_per_token(d: dict) -> float:
    """One token's cache rows over all layers, bf16."""
    return 2.0 * (d["L_dense"] + d["L_sparse"]) * (d["rkv"] + d["dr"])


def traced_decode_bytes(ctx: dict, executions: float):
    """Least bytes the traced decode executions move: the step's fixed
    weights once an execution, each distinct expert hit once
    (``engine_moe_decode_experts_hit_total``), the LIVE latent rows
    once a token."""
    hit = ctx["traced_counters"].get("engine_moe_decode_experts_hit_total")
    if hit is None:
        return None
    a, b = ctx["traced_interval"]
    _, ctx_sum = reduce.decode_tokens_in(ctx["records"], a, b)
    d = ctx["dims"]
    return (executions * step_weight_bytes(d)
            + 2.0 * expert_params(d) * hit
            + latent_bytes_per_token(d) * ctx_sum)

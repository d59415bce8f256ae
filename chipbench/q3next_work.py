"""Operations and bytes a stretch of serving the Qwen3-Next
configuration needs, from its shapes, what the generator saw and what
the engine counted, whatever implements it: 2 FLOP a multiply-add, each
weight once per program execution at the width the configuration keeps
it in, only the experts that were hit, live K and V once, the recurrent
rows read and written once a token, no padding, nothing recomputed.
"""

from chipbench import reduce


def dims(config: dict) -> dict:
    m = config
    interval = m["full_attention_interval"]
    periods = m["num_hidden_layers"] // interval
    return {"D": m["hidden_size"], "V": m["vocab_size"],
            "H": m["num_attention_heads"], "Hkv": m["num_key_value_heads"],
            "Dh": m["head_dim"], "Hk": m["linear_num_key_heads"],
            "Hv": m["linear_num_value_heads"],
            "dk": m["linear_key_head_dim"], "dv": m["linear_value_head_dim"],
            "K": m["linear_conv_kernel_dim"], "E": m["num_experts"],
            "held": m["num_experts_held"], "F": m["moe_intermediate_size"],
            "Fs": m["shared_expert_intermediate_size"],
            "L_rec": periods * (interval - 1), "L_full": periods}


def rec_mixer_params(d: dict) -> int:
    """in_proj_qkvz, in_proj_ba, the convolution, out_proj."""
    key, val = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    return (d["D"] * (2 * key + 2 * val) + d["D"] * 2 * d["Hv"]
            + (2 * key + val) * d["K"] + val * d["D"])


def full_mixer_params(d: dict) -> int:
    """q_proj with the gate, k_proj, v_proj, o_proj."""
    return (d["D"] * d["H"] * 2 * d["Dh"] + 2 * d["D"] * d["Hkv"] * d["Dh"]
            + d["H"] * d["Dh"] * d["D"])


def expert_params(d: dict) -> int:
    return 3 * d["D"] * d["F"]


def shared_params(d: dict) -> int:
    return 3 * d["D"] * d["Fs"] + d["D"]


def router_params(d: dict) -> int:
    return d["D"] * d["E"]


def dense_params(d: dict) -> int:
    """Weights that every token multiplies, over all layers: mixers,
    routers and shared experts."""
    layers = d["L_rec"] + d["L_full"]
    return (d["L_rec"] * rec_mixer_params(d)
            + d["L_full"] * full_mixer_params(d)
            + layers * (router_params(d) + shared_params(d)))


def stretch_flops(d: dict, tokens: float, keys: float, assignments: float,
                  sampled: float) -> float:
    """Forward FLOPs of ``tokens`` tokens that attended over ``keys``
    keys in all (summed over tokens, per full-attention layer), with
    ``assignments`` token-to-expert products computed here and the held
    head applied at ``sampled`` positions."""
    return (2.0 * dense_params(d) * tokens
            + 2.0 * expert_params(d) * assignments
            + 4.0 * d["L_full"] * d["H"] * d["Dh"] * keys
            + 6.0 * d["L_rec"] * d["Hv"] * d["dk"] * d["dv"] * tokens
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
    """What one decode execution reads whatever the tokens: mixers,
    shared experts and the held head at 2 bytes, the float32 routers
    and norms at 4."""
    layers = d["L_rec"] + d["L_full"]
    two = (d["L_rec"] * rec_mixer_params(d)
           + d["L_full"] * full_mixer_params(d)
           + layers * shared_params(d) + d["V"] * d["D"])
    four = layers * (router_params(d) + 2 * d["D"]) + d["D"] \
        + d["L_rec"] * (2 * d["Hv"] + d["dv"]) + d["L_full"] * 2 * d["Dh"]
    return 2.0 * two + 4.0 * four


def kv_bytes_per_token(d: dict) -> float:
    return 2.0 * d["L_full"] * d["Hkv"] * d["Dh"] * 2


def state_bytes_per_slot(d: dict) -> float:
    """One slot's recurrent rows: the float32 state matrices and the
    convolution tails, over the DeltaNet layers."""
    ch = 2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]
    return d["L_rec"] * (4.0 * d["Hv"] * d["dk"] * d["dv"]
                         + 2.0 * (d["K"] - 1) * ch)


def traced_decode_bytes(ctx: dict, executions: float):
    """Least bytes the traced decode executions move: the step's fixed
    weights once an execution, each distinct expert hit once
    (``engine_moe_decode_experts_hit_total``), the live K and V once a
    token, the recurrent rows read and written once a token."""
    hit = ctx["traced_counters"].get("engine_moe_decode_experts_hit_total")
    if hit is None:
        return None
    a, b = ctx["traced_interval"]
    n_tok, ctx_sum = reduce.decode_tokens_in(ctx["records"], a, b)
    d = ctx["dims"]
    return (executions * step_weight_bytes(d)
            + 2.0 * expert_params(d) * hit
            + kv_bytes_per_token(d) * ctx_sum
            + 2.0 * state_bytes_per_slot(d) * n_tok)

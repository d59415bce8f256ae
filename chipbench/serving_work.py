"""The work a stretch of serving needed, from what the generator saw
and the engine counted: shared by the per-layer readers that turn device
time into shares of the published peaks.
"""

from chipbench import flops, reduce, trace


def module_time(ctx: dict, which: str):
    """(seconds, executions) of the traced programs of one kind. The
    artifact's programs all carry one name in the trace, so the decode
    program is told by what the engine guarantees: with decoders in
    flight at most one prefill chunk runs per decode step, so the
    program executed most often is the decode step and the others are
    the prefill chunks."""
    mods = {k: v for k, v in ctx["trace"]["modules"].items()
            if trace.module_base(k) == "jit_call_exported"}
    if not mods:
        return None
    decode = max(mods, key=lambda k: mods[k][1])
    if which == "decode":
        return tuple(mods[decode])
    rest = [v for k, v in mods.items() if k != decode]
    if not rest:
        return None
    return (sum(v[0] for v in rest), sum(v[1] for v in rest))


def decode_work(ctx: dict) -> dict:
    a, b = ctx["traced_interval"]
    n_tok, ctx_sum = reduce.decode_tokens_in(ctx["records"], a, b)
    d = ctx["dims"]
    return {"tokens": n_tok, "context_sum": ctx_sum,
            "kv_bytes": ctx_sum * flops.lm_kv_bytes_per_token(d),
            "flops": n_tok * flops.lm_token_flops(d, 0.0)
            + 4.0 * d["n_layers"] * d["d_model"] * ctx_sum}


def prefill_work(ctx: dict) -> dict:
    """Tokens really prefilled in the traced stretch: the prompts whose
    first token fell in it, less what the prefix cache served (counter
    delta); attention scaled by the same share."""
    a, b = ctx["traced_interval"]
    prompts = reduce.prefills_in(ctx["records"], a, b)
    d = ctx["dims"]
    total = float(sum(prompts))
    hit = ctx["traced_counters"].get(
        "engine_prefix_cache_hit_blocks_total", 0.0) * ctx["block_size"]
    new = max(total - hit, 0.0)
    share = new / total if total else 0.0
    keys = sum(p * (p + 1) / 2.0 for p in prompts) * share
    return {"requests": len(prompts), "tokens": new,
            "flops": 2.0 * flops.lm_matmul_params(d) * new
            + 4.0 * d["n_layers"] * d["d_model"] * keys
            + len(prompts) * 2.0 * d["vocab"] * d["d_model"]}

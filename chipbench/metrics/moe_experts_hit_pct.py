"""Distinct held experts hit per expert-layer call of the window's
decode steps, as a share of the experts held (engine counters): what
part of the expert weights a decode step has to read."""


def read(ctx):
    c = ctx["counters"]
    calls = c.get("engine_moe_decode_layer_calls_total", 0)
    if calls <= 0:
        return None
    return 100.0 * c["engine_moe_decode_experts_hit_total"] \
        / (calls * ctx["dims"]["held"])

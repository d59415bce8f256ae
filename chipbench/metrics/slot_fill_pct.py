"""Tokens the decode steps emitted / (decode steps x slots), engine
counter deltas over the window. A prompt's first token comes out of its
last prefill chunk and is not a decode step's."""


def read(ctx):
    c = ctx["counters"]
    steps = c.get("engine_decode_steps_total", 0)
    if steps <= 0:
        return None
    toks = c["engine_tokens_total"] - c.get("engine_prefill_calls_total", 0)
    return 100.0 * toks / (steps * ctx["slots"])

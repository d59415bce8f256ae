"""Decode steps of the window that completed more than a second after
the one before, with decoders in flight throughout
(``engine_slow_steps_total``): 0 in a clean run; a stalled run with 0
here froze outside the engine's loop."""


def read(ctx):
    return ctx["counters"].get("engine_slow_steps_total")

"""Device time per execution of the decode program, from the trace."""
from chipbench import serving_work


def read(ctx):
    t = serving_work.module_time(ctx, "decode")
    return None if t is None else 1000.0 * t[0] / t[1]

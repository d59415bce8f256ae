"""Least time the traced decode steps of the Qwen3-Next cells could take
on the memory system (fixed weights once a step, 6.29 MB per distinct
expert hit, live K/V and the recurrent rows once a token) over the
device time of the decode program. Memory bounds it."""
from chipbench import q3next_work, serving_work


def read(ctx):
    t = serving_work.module_time(ctx, "decode")
    if t is None:
        return None
    byts = q3next_work.traced_decode_bytes(ctx, t[1])
    if byts is None:
        return None
    return 100.0 * byts / ctx["peaks"]["hbm_bytes_per_s"] / t[0]

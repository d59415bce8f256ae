"""Least time the traced decode steps of the GLM-4.7-Flash cells could
take on the memory system (fixed weights once a step, 18.87 MB per
distinct expert hit, the LIVE latent rows once a token: the same work
whatever implements it) over the device time of the decode program.
Memory bounds it."""
from chipbench import glm47f_work, serving_work


def read(ctx):
    t = serving_work.module_time(ctx, "decode")
    if t is None:
        return None
    byts = glm47f_work.traced_decode_bytes(ctx, t[1])
    if byts is None:
        return None
    return 100.0 * byts / ctx["peaks"]["hbm_bytes_per_s"] / t[0]

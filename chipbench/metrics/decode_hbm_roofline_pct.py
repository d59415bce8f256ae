"""Least time the traced decode steps could take on the memory system
(bf16 weights once a step + live K/V once a token) over the device time
of the decode program. Memory bounds it: a step does ~2 FLOP a byte."""
from chipbench import flops, serving_work


def read(ctx):
    t = serving_work.module_time(ctx, "decode")
    if t is None:
        return None
    work = serving_work.decode_work(ctx)
    byts = t[1] * flops.lm_weight_bytes(ctx["dims"]) + work["kv_bytes"]
    return 100.0 * byts / ctx["peaks"]["hbm_bytes_per_s"] / t[0]

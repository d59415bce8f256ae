"""The whole step's share of the chip in the GLM-4.7-Flash cells: model
FLOPs of every token prefilled and decoded in the traced stretch, the
expert products by the engine's count (``chipbench/glm47f_work.py``),
over (its length x peak bf16 FLOP/s)."""
from chipbench import glm47f_work


def read(ctx):
    f = glm47f_work.traced_flops(ctx)
    if not f:
        return None
    return 100.0 * f / (ctx["trace"]["window_s"]
                        * ctx["peaks"]["flops_per_s"]["bf16"])

"""Model FLOPs of every token prefilled and decoded in the traced
stretch over (its length x peak bf16 FLOP/s): the whole step's share of
the chip, beside the kernels' rooflines."""
from chipbench import serving_work


def read(ctx):
    f = serving_work.decode_work(ctx)["flops"] \
        + serving_work.prefill_work(ctx)["flops"]
    if f <= 0:
        return None
    return 100.0 * f / (ctx["trace"]["window_s"]
                        * ctx["peaks"]["flops_per_s"]["bf16"])

"""Forward + backward FLOPs of the layer shapes x steps completed, over
(window x chips x peak bf16 FLOP/s): the whole step's share of the chip
as the user's loop reaches it."""


def read(ctx):
    if ctx["steps"] <= 0:
        return None
    return 100.0 * ctx["step_flops"] * ctx["steps"] / (
        ctx["window_s"] * ctx["chips"]
        * ctx["peaks"]["flops_per_s"]["bf16"])

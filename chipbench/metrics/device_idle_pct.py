"""1 - (union of the device-op intervals / the traced stretch)."""
from chipbench import trace


def read(ctx):
    return trace.idle_pct(ctx["trace"])

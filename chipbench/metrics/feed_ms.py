"""The trainer's ``feed`` scope (DataFeeder convert + transfer of the
next batch), per step over the window (host clock, program's span)."""


def read(ctx):
    total, n = ctx["stats"].get("feed", (0.0, 0))
    return None if n <= 0 else 1000.0 * total / n

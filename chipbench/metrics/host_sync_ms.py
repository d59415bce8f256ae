"""The trainer's ``host_sync`` scope (the read of the step's cost, where
the host waits for the device), per step over the window."""


def read(ctx):
    total, n = ctx["stats"].get("host_sync", (0.0, 0))
    return None if n <= 0 else 1000.0 * total / n

"""Artifact load -> precompile() done, in the worker (host clock)."""


def read(ctx):
    return ctx["spans"].get("replica_ready_s")

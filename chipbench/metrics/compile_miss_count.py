"""Persistent compile-cache misses of this run up to the window's
opening (``utils.compile_cache.stats()``); 0 in a warm run."""


def read(ctx):
    cc = ctx["spans"].get("compile_cache")
    return None if cc is None else cc["misses"]

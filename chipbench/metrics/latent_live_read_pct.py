"""What share of the cache rows a decode step's attention reads is
live: ``engine_decode_live_rows_total`` / ``engine_decode_read_rows_total``
over the window (engine counters). About the pool's fill on the
gathered-view path, which reads every slot's whole span; ~100 where
pages are read in place."""


def read(ctx):
    c = ctx["counters"]
    read_rows = c.get("engine_decode_read_rows_total", 0)
    if read_rows <= 0:
        return None
    return 100.0 * c["engine_decode_live_rows_total"] / read_rows

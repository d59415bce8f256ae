"""The artifact's way into the process, timed from inside
``io.lm_serving``: the tar's members read (``artifact/read``), the
``.npz`` files decoded (``artifact/params``), the exported modules
deserialised (``artifact/programs``). Once a process, so the scopes'
totals in ``utils.stat.global_stats`` are the reading."""

SCOPES = ("artifact/read", "artifact/params", "artifact/programs")


def read(ctx, scopes=SCOPES):
    from paddle_tpu.utils.stat import global_stats
    stats = [global_stats.get(s) for s in scopes]
    if any(s.count <= 0 for s in stats):
        return None
    return sum(s.total_s for s in stats)

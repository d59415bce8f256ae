"""The engine's ``decode_sync`` phase per decode step: the read-back of
the sampled ids, which is where the host waits for the device step
(``engine_decode_sync_seconds``, window delta). Less ``decode_dev_ms``
it is the latency of the read-back itself."""


def read(ctx):
    c = ctx["counters"]
    steps = c.get("engine_decode_steps_total", 0)
    if steps <= 0 or "engine_decode_sync_seconds_sum" not in c:
        return None
    return 1000.0 * c["engine_decode_sync_seconds_sum"] / steps

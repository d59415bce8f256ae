"""The host's own work per decode step: the engine's ``ingest``,
``schedule``, ``decode_stage``, ``decode_dispatch``, ``emit`` and
``reply`` phases (``engine_<phase>_seconds``, window deltas) over the
decode steps of the window. What is left of a step's period beside the
wait for the device (``decode_sync_ms``) and the prefill chunks."""

PHASES = ("ingest", "schedule", "decode_stage", "decode_dispatch", "emit",
          "reply")


def read(ctx):
    c = ctx["counters"]
    steps = c.get("engine_decode_steps_total", 0)
    keys = [f"engine_{p}_seconds_sum" for p in PHASES]
    if steps <= 0 or any(k not in c for k in keys):
        return None
    return 1000.0 * sum(c[k] for k in keys) / steps

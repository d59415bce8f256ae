"""One prefill chunk, dispatch to read-back, on the host's clock: the
engine's ``prefill_chunk`` phase (``engine_prefill_chunk_seconds``) over
``engine_prefill_chunks_total``, window deltas. Each chunk displaces
the decode steps of every slot in flight."""


def read(ctx):
    c = ctx["counters"]
    chunks = c.get("engine_prefill_chunks_total", 0)
    if chunks <= 0 or "engine_prefill_chunk_seconds_sum" not in c:
        return None
    return 1000.0 * c["engine_prefill_chunk_seconds_sum"] / chunks

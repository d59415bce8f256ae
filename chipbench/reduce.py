"""From the generator's request records to the end-to-end numbers.

A record is one request as the generator saw it, times in seconds on the
generator's monotonic clock: ``due`` (when it should have been sent),
``sent``, ``arrive`` (the result line), plus the engine's own
``ttft_ms`` / ``latency_ms`` off that line, ``n_prompt``, ``n_out``.
The wire returns one line per request, so the first-token instant is
the arrival less the engine's (latency - ttft).
"""

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values (q in 0..100)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = max(1, int(math.ceil(q / 100.0 * len(xs))))
    return xs[rank - 1]


def first_token_at(r: dict) -> float:
    return r["arrive"] - (r["latency_ms"] - r["ttft_ms"]) / 1000.0


def answered(r: dict) -> bool:
    return r.get("arrive") is not None and not r.get("error")


def tokens_in_window(records, t_open: float, t_close: float) -> float:
    """Output tokens emitted inside [open, close): token i of a request
    is placed evenly between its first-token instant and its arrival
    (one token per decode step), so a request that straddles an edge
    counts for the part of it that lies inside."""
    total = 0
    for r in records:
        if not answered(r) or r["n_out"] < 1:
            continue
        first, last, n = first_token_at(r), r["arrive"], r["n_out"]
        if n == 1 or last <= first:
            total += 1 if t_open <= first < t_close else 0
            continue
        step = (last - first) / (n - 1)
        lo = max(0, int(math.ceil((t_open - first) / step)))
        hi = min(n - 1, int(math.ceil((t_close - first) / step)) - 1)
        total += max(0, hi - lo + 1)
    return float(total)


def serve_tok_s(records, t_open, t_close) -> float:
    return tokens_in_window(records, t_open, t_close) / (t_close - t_open)


def due_in_window(records, t_open, t_close):
    return [r for r in records
            if r.get("due") is not None and t_open <= r["due"] < t_close]


def ttft_ms_all(records, t_open, t_close):
    """TTFT of every request due in the window, from its due time; one
    that failed or never came counts as the worst: the wait to the close
    and the minute past it."""
    out = []
    for r in due_in_window(records, t_open, t_close):
        if answered(r):
            out.append(1000.0 * (first_token_at(r) - r["due"]))
        else:
            out.append(1000.0 * (t_close - r["due"] + 60.0))
    return out


def tpot_ms_all(records, t_open, t_close):
    out = []
    for r in due_in_window(records, t_open, t_close):
        if answered(r) and r["n_out"] > 1:
            out.append((r["latency_ms"] - r["ttft_ms"]) / (r["n_out"] - 1))
        elif not answered(r):
            out.append(1000.0 * (t_close - r["due"] + 60.0))
    return out


def decode_tokens_in(records, a: float, b: float):
    """(tokens, summed context) of the DECODE steps' tokens emitted in
    [a, b): token i >= 1 of a request attends over n_prompt + i keys
    (token 0 comes out of the prefill's last chunk)."""
    n_tok, ctx_sum = 0, 0.0
    for r in records:
        if not answered(r) or r["n_out"] < 2:
            continue
        first, last, n = first_token_at(r), r["arrive"], r["n_out"]
        if last <= first:
            continue
        step = (last - first) / (n - 1)
        lo = max(1, int(math.ceil((a - first) / step)))
        hi = min(n - 1, int(math.ceil((b - first) / step)) - 1)
        if hi >= lo:
            k = hi - lo + 1
            n_tok += k
            ctx_sum += k * r["n_prompt"] + (lo + hi) * k / 2.0
    return n_tok, ctx_sum


def prefills_in(records, a: float, b: float):
    """Requests whose first token fell in [a, b): their prompt lengths."""
    return [r["n_prompt"] for r in records
            if answered(r) and a <= first_token_at(r) < b]

"""The general request generator: sessions of turns over tenants.

A pure function of (parameters, seed, vocabulary): no clock, no JAX. One
traffic file = one set of parameters; everything the serving cells send
comes out of ``plan``. A mix with one turn, no tenants and a closed
arrival is an offline batch; three turns, four tenants with long system
prompts and Poisson arrivals is a chat front end.

Every seed gets the SAME multiset of lengths, gaps and think times in
another order (stratified quantiles, shuffled inside each stratum), so
two seeds differ in order and token ids and not in the amount of work.

Where a window holds only some tens of requests even the order is work:
which request ends first decides how many prompts are admitted (and
prefilled) before the close, and one admission more or less is a step
in a rate. Such a mix states ``schedule_seed``: lengths, arrivals, think
times and tenants are then drawn from it, the same for every run, and
``--seed`` draws the token ids alone.
"""

import math
import random


def _quantile(dist: dict, u: float) -> float:
    lo, hi = float(dist["lo"]), float(dist["hi"])
    kind = dist["dist"]
    if kind == "uniform":
        return lo + u * (hi - lo)
    if kind == "loguniform":
        return math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    if kind == "exponential":      # lo is unused, hi is the mean
        return -hi * math.log(1.0 - u)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int, rng: random.Random, stratum: int):
    """``n`` draws: consecutive strata of ``stratum`` draws each hold the
    same ``stratum`` quantile points, in an order the seed chooses."""
    out = []
    while len(out) < n:
        block = [_quantile(dist, (i + 0.5) / stratum)
                 for i in range(stratum)]
        rng.shuffle(block)
        out.extend(block)
    return out[:n]


def zipf_counts(n: int, tenants: int):
    """How many of ``n`` sessions each tenant gets under Zipf(1),
    largest remainders first: the same counts for every seed."""
    w = [1.0 / (k + 1) for k in range(tenants)]
    tot = sum(w)
    exact = [n * x / tot for x in w]
    counts = [int(e) for e in exact]
    order = sorted(range(tenants), key=lambda k: exact[k] - counts[k],
                   reverse=True)
    for k in order[:n - sum(counts)]:
        counts[k] += 1
    return counts


def plan(params: dict, seed: int, vocab: int, seconds: float) -> dict:
    ids = random.Random(int(seed))              # token ids
    rng = random.Random(int(params["schedule_seed"])) \
        if "schedule_seed" in params else ids   # sizes, order, arrivals
    stratum = int(params.get("stratum", 16))
    arrival = params["arrival"]
    ramp = float(params["ramp_s"])
    turns = int(params["turns"])
    if arrival["kind"] == "poisson":
        rate = float(arrival["sessions_per_s"])
        n = max(1, int(math.ceil(rate * (ramp + seconds))))
        gaps = stratified({"dist": "exponential", "lo": 0, "hi": 1.0 / rate},
                          n, rng, stratum)
        t, arrive = 0.0, []
        for g in gaps:
            t += g
            arrive.append(t)
    elif arrival["kind"] == "closed":
        n = int(params["sessions"])
        arrive = [None] * n
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")

    def toks(k):
        return [ids.randrange(vocab) for _ in range(k)]

    n_ten = int(params.get("tenants", 0))
    prefixes = [toks(int(params["tenant_prefix_tokens"]))
                for _ in range(n_ten)]
    if n_ten:
        tenant_of = [k for k, c in enumerate(zipf_counts(n, n_ten))
                     for _ in range(c)]
        rng.shuffle(tenant_of)
    else:
        tenant_of = [None] * n
    new_len = stratified(params["new_tokens"], n * turns, rng, stratum)
    out_len = stratified(params["output_tokens"], n * turns, rng, stratum)
    think = stratified({"dist": "exponential", "lo": 0,
                        "hi": float(params.get("think_s_mean", 0.0)) or 1.0},
                       n * turns, rng, stratum)
    samp = params["sampling"]
    cycle = ["greedy"] * int(samp["greedy"]) + \
        ["sampled"] * int(samp["sampled"])
    limit = int(params["max_total_tokens"])
    sessions = []
    for i in range(n):
        ten = tenant_of[i]
        total = len(prefixes[ten]) if ten is not None else 0
        ts = []
        for j in range(turns):
            k = i * turns + j
            new, out = int(round(new_len[k])), int(round(out_len[k]))
            if total + new + out > limit:
                break               # the session stops before the limit
            total += new + out
            mode = cycle[k % len(cycle)]
            ts.append({"new": toks(new), "max_new": out,
                       "temperature": float(samp["temperature"])
                       if mode == "sampled" else 0.0,
                       "top_k": int(samp["top_k"])
                       if mode == "sampled" else 0,
                       "think_s": think[k] if params.get("think_s_mean")
                       else 0.0})
        sessions.append({"arrive_s": arrive[i], "tenant": ten, "turns": ts})
    return {"sessions": sessions, "prefixes": prefixes,
            "arrival": arrival, "ramp_s": ramp}

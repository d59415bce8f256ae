"""Each generator is a pure function of its seed and parameters."""

import json
import os

import numpy as np
import pytest

from chipbench import harness
from chipbench.generators import batches, sessions

TRAFFIC = os.path.join(harness.HERE, "traffic")


# the chat mix ISSUE 23 specifies for the sessions cell (left for a later
# PR, PERF.md Open questions): the generator is tested on it here
SESSIONS = {
    "generator": "sessions",
    "arrival": {"kind": "poisson", "sessions_per_s": 1.0},
    "ramp_s": 10, "turns": 3, "tenants": 4, "tenant_prefix_tokens": 768,
    "new_tokens": {"dist": "loguniform", "lo": 64, "hi": 256},
    "output_tokens": {"dist": "uniform", "lo": 32, "hi": 128},
    "think_s_mean": 2.0, "max_total_tokens": 1920,
    "sampling": {"greedy": 2, "sampled": 1, "temperature": 0.8,
                 "top_k": 40},
    "stratum": 16}


def load(name):
    if name == "sessions":
        return dict(SESSIONS)
    return harness.load_json(os.path.join(TRAFFIC, f"{name}.json"))


@pytest.mark.parametrize("mix", ["batch-gen", "sessions"])
def test_plan_is_a_pure_function_of_the_seed(mix):
    p = load(mix)
    a = sessions.plan(p, 4000000007, 50257, 30.0)
    b = sessions.plan(p, 4000000007, 50257, 30.0)
    c = sessions.plan(p, 4000000008, 50257, 30.0)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)


def test_batch_gen_lengths_within_bounds():
    p = load("batch-gen")
    plan = sessions.plan(p, 7, 50257, 30.0)
    assert len(plan["sessions"]) == p["sessions"]
    for s in plan["sessions"]:
        assert s["tenant"] is None and len(s["turns"]) == 1
        t = s["turns"][0]
        assert 512 <= len(t["new"]) <= 1536
        assert 128 <= t["max_new"] <= 384
        assert len(t["new"]) + t["max_new"] <= 1920
        assert all(0 <= x < 50257 for x in t["new"][:8])
    modes = [s["turns"][0]["temperature"] == 0.0 for s in plan["sessions"]]
    assert abs(sum(modes) / len(modes) - 2 / 3) < 0.01     # 2 : 1 greedy


def test_sessions_share_their_tenants_prefix_and_fit_the_cache():
    p = load("sessions")
    plan = sessions.plan(p, 11, 50257, 30.0)
    assert len(plan["prefixes"]) == 4
    assert all(len(x) == 768 for x in plan["prefixes"])
    per_tenant = [0] * 4
    for s in plan["sessions"]:
        per_tenant[s["tenant"]] += 1
        total = 768
        assert 1 <= len(s["turns"]) <= 3
        for t in s["turns"]:
            assert 64 <= len(t["new"]) <= 256
            assert 32 <= t["max_new"] <= 128
            total += len(t["new"]) + t["max_new"]
        assert total <= 1920
    # Zipf(1): 12 : 6 : 4 : 3 of every 25
    n = len(plan["sessions"])
    assert per_tenant == sessions.zipf_counts(n, 4)
    assert per_tenant[0] > per_tenant[1] > per_tenant[3] > 0


def test_offered_rate_is_as_stated():
    p = load("sessions")
    rate = p["arrival"]["sessions_per_s"]
    horizon = p["ramp_s"] + 30.0
    plan = sessions.plan(p, 5, 50257, 30.0)
    arr = [s["arrive_s"] for s in plan["sessions"]]
    assert arr == sorted(arr)
    inside = sum(1 for a in arr if a <= horizon)
    assert abs(inside / horizon - rate) / rate < 0.08


def test_every_seed_gets_the_same_multiset_of_sizes():
    p = load("batch-gen")

    def sizes(seed):
        plan = sessions.plan(p, seed, 50257, 30.0)
        return (sorted(len(s["turns"][0]["new"]) for s in plan["sessions"]),
                sorted(s["turns"][0]["max_new"] for s in plan["sessions"]))

    assert sizes(1) == sizes(2 ** 31 + 5)


def test_stratified_strata_hold_the_same_quantiles():
    import random
    d = {"dist": "uniform", "lo": 0, "hi": 16}
    xs = sessions.stratified(d, 32, random.Random(3), 16)
    assert sorted(xs[:16]) == sorted(xs[16:])
    assert sorted(xs[:16]) == [i + 0.5 for i in range(16)]


def test_batches_pool_is_pure_and_rows_differ():
    p = {"pool_batches": 3}
    r1, l1 = batches.pool(p, 2 ** 31 + 9, 4, 12, 10)
    r2, l2 = batches.pool(p, 2 ** 31 + 9, 4, 12, 10)
    assert r1.shape == (12, 12) and r1.dtype == np.float32
    assert np.array_equal(r1, r2) and np.array_equal(l1, l2)
    assert len({r.tobytes() for r in r1}) == 12          # all rows differ
    assert 0 <= r1.min() and r1.max() < 1 and l1.max() < 10
    b0 = batches.batch_of(r1, l1, 4, 0)
    b3 = batches.batch_of(r1, l1, 4, 3)                   # cycles the pool
    assert np.array_equal(b0[0], b3[0])
    assert not np.array_equal(b0[0], batches.batch_of(r1, l1, 4, 1)[0])


def test_a_stated_schedule_is_the_same_for_every_seed_and_the_ids_are_not():
    """batch-gen's window holds some twenty requests, so the order of
    lengths is work (how many prompts are admitted before the close):
    its file states ``schedule_seed``; the seed draws the token ids."""
    p = load("batch-gen")
    assert "schedule_seed" in p

    def lengths(plan):
        return [(len(s["turns"][0]["new"]), s["turns"][0]["max_new"],
                 s["turns"][0]["temperature"]) for s in plan["sessions"]]

    a = sessions.plan(p, 3, 50257, 30.0)
    b = sessions.plan(p, 2 ** 31 + 77, 50257, 30.0)
    assert lengths(a) == lengths(b)
    ids = [s["turns"][0]["new"][:16] for s in a["sessions"][:8]]
    assert ids != [s["turns"][0]["new"][:16] for s in b["sessions"][:8]]
    # without the key the order follows the seed, as the chat mix does
    q = {k: v for k, v in p.items() if k != "schedule_seed"}
    assert lengths(sessions.plan(q, 3, 50257, 30.0)) != \
        lengths(sessions.plan(q, 4, 50257, 30.0))
    # and the stated schedule is that of its own seed without the key
    assert lengths(sessions.plan(q, p["schedule_seed"], 50257, 30.0)) == \
        lengths(a)

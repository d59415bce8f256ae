"""Multi-process serving-fleet chaos: SIGKILL a replica mid-trace and
assert ZERO lost requests with outputs equal the single-engine run.

The fleet is real: N ``paddle_tpu serve --port`` subprocesses spawned
from one paged artifact by ``runtime.master.ServingFleet``, fronted by
the prefix-aware ``serving.Router`` over TCP ``SocketReplica`` handles.
The kill lands while the victim has requests in flight (asserted, not
hoped) — the router discovers the death through the dead socket,
re-queues the victim's outstanding work onto survivors, and every
submitted request completes with the exact greedy tokens the reference
single engine produces.

Slow tier: each replica is a full python + jax subprocess (~10-20 s
startup each on this host).
"""

import os
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def fleet_model(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.io import lm_serving
    from paddle_tpu.models import transformer
    cfg = transformer.TransformerConfig(
        vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=32, max_len=96, dtype=jnp.float32, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path_factory.mktemp("fleet") / "lm_v4.tar")
    lm_serving.save_lm_artifact(path, params, cfg, batch=2,
                                prompt_len=6, cache_len=96,
                                engine_buckets=(8, 16),
                                engine_block_size=8)
    return path, params, cfg


def _trace(n=10, vocab=40, shared_len=24, seed=11):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, vocab, shared_len).astype(np.int32)
    prompts = []
    for i in range(n):
        tail = rng.randint(0, vocab, 4 + i % 5).astype(np.int32)
        prompts.append(np.concatenate([shared, tail]) if i % 2
                       else tail)
    return prompts


def _reference(params, cfg, prompts, max_new):
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    return [np.asarray(transformer.generate(
        params, jnp.asarray(p[None]), cfg, max_new=max_new))[0]
        for p in prompts]


def test_kill_replica_mid_trace_zero_lost(fleet_model):
    """The acceptance contract: a 3-replica TCP fleet serves a
    shared-prefix trace; one replica is SIGKILLed WHILE it holds
    in-flight requests; every submitted request still completes, each
    with the single-engine greedy output, and the router reports the
    drain + requeues."""
    from paddle_tpu.runtime.master import ServingFleet
    model, params, cfg = fleet_model
    prompts = _trace()

    fleet = ServingFleet(model, replicas=3,
                         env={"JAX_PLATFORMS": "cpu"})
    try:
        fleet.start()
        router = fleet.router(health_poll_s=0.2, max_in_flight=4)
        # max_new=24: each request decodes for dozens of engine steps,
        # so the victim's in-flight work cannot all complete inside the
        # detect->SIGKILL window — the requeue path MUST fire
        want = _reference(params, cfg, prompts, 24)
        reqs = [router.submit(p, 24) for p in prompts]
        # pump until SOME replica holds in-flight work, then kill it —
        # the chaos must land mid-trace, not on an idle process
        victim = None
        deadline = time.time() + 120
        while victim is None and time.time() < deadline:
            router.step()
            for st in router._all:
                if st.in_flight and any(
                        k == "generate"
                        for _, k in st.outstanding.values()):
                    victim = st
                    break
        assert victim is not None, "no replica ever held work"
        idx = int(victim.name.replace("replica", ""))
        n_at_kill = victim.in_flight
        fleet.kill(idx)
        router.run_until_idle()
        states = router.replica_states()
        assert states[victim.name] == "dead"
        assert sum(1 for s in states.values() if s == "ok") == 2
        # zero lost: every request DONE with the reference output
        for r, w in zip(reqs, want):
            assert r.status == "done", (r.xid, r.status, r.error)
            np.testing.assert_array_equal(r.output, w)
        # the kill landed on live work, and that work was re-queued
        # (>= 1, not == n_at_kill: results DELIVERED before the socket
        # died are salvaged by _collect rather than re-run)
        assert n_at_kill >= 1
        assert router._m_requeued.value() >= 1
        assert router._m_drains.value(reason="dead") == 1
        router.close()
    finally:
        fleet.close()


def test_disaggregated_fleet_over_tcp_bitwise(fleet_model):
    """P/D disaggregation across real processes: prefill replica runs
    the chunked prefill, the KV payload crosses the wire (base64 over
    JSONL), the decode replica adopts it via the prefix-cache publish
    path — generation bitwise the colocated single-engine run, with
    the transfer counters proving the path actually ran."""
    from paddle_tpu.runtime.master import ServingFleet
    model, params, cfg = fleet_model
    prompts = [p for p in _trace() if p.size > 17][:4]  # transferable
    want = _reference(params, cfg, prompts, 6)

    fleet = ServingFleet(model, replicas=2, prefill=1,
                         env={"JAX_PLATFORMS": "cpu"})
    try:
        fleet.start()
        router = fleet.router(health_poll_s=0.2)
        reqs = [router.submit(p, 6) for p in prompts]
        router.run_until_idle()
        for r, w in zip(reqs, want):
            assert r.status == "done", (r.xid, r.status, r.error)
            np.testing.assert_array_equal(r.output, w)
        assert router._m_pd_exports.value() >= 1
        assert router._m_pd_blocks.value() >= 2
        assert all(r.replica == "replica1" for r in reqs)   # decode tier
        router.close()
    finally:
        fleet.close()


def _tier_trace(n=16, vocab=40, prefix_len=32, seed=13):
    """n conversations, each with its OWN prefix — the working set
    that overflows a 24-block replica pool and forces demotions."""
    rng = np.random.RandomState(seed)
    prompts = []
    for i in range(n):
        prefix = rng.randint(0, vocab, prefix_len).astype(np.int32)
        tail = rng.randint(0, vocab, 4 + i % 3).astype(np.int32)
        prompts.append(np.concatenate([prefix, tail]))
    return prompts


def test_kill_replica_mid_demotion_no_torn_spills(fleet_model,
                                                  tmp_path):
    """Tiered-spill chaos: a fleet whose replicas demote to DRAM+disk
    (per-replica --tiers_dir) serves a working set past pool capacity;
    one replica is SIGKILLed while it holds in-flight work with spill
    traffic live. Zero lost requests (all complete bitwise on the
    survivor), the dead replica's directory entries are pruned, and a
    fresh scan of the victim's spill directory adopts NO torn file —
    every surviving entry reads back checksum-clean."""
    from paddle_tpu.runtime.master import ServingFleet
    from paddle_tpu.serving.tiers import TieredStore
    model, params, cfg = fleet_model
    prompts = _tier_trace()
    for i in range(2):
        os.makedirs(tmp_path / f"replica{i}")

    fleet = ServingFleet(
        model, replicas=2,
        args_extra=("--tiers_dram_mb=0.002", "--tiers_disk_mb=8",
                    f"--tiers_dir={tmp_path}" + "/{name}"),
        env={"JAX_PLATFORMS": "cpu"})
    try:
        fleet.start()
        router = fleet.router(health_poll_s=0.2, max_in_flight=2)
        # wave 1: warm every conversation, overflow the pools
        warm = [router.submit(p, 6) for p in prompts]
        router.run_until_idle()
        assert all(r.status == "done" for r in warm)
        tiers_by_rep = {n: rep.get("tiers") or {}
                        for n, rep in router.health()["replicas"].items()}
        assert any((t.get("dram") or 0) + (t.get("disk") or 0) > 0
                   for t in tiers_by_rep.values()), tiers_by_rep
        # wave 2: the same conversations return (promotion traffic +
        # fresh demotions); kill whichever replica holds live work
        want = _reference(params, cfg, prompts, 24)
        reqs = [router.submit(p, 24) for p in prompts]
        victim, deadline = None, time.time() + 120
        while victim is None and time.time() < deadline:
            router.step()
            for st in router._all:
                if st.in_flight and any(
                        k == "generate"
                        for _, k in st.outstanding.values()):
                    victim = st
                    break
        assert victim is not None, "no replica ever held work"
        fleet.kill(int(victim.name.replace("replica", "")))
        router.run_until_idle()
        assert router.replica_states()[victim.name] == "dead"
        for r, w in zip(reqs, want):
            assert r.status == "done", (r.xid, r.status, r.error)
            np.testing.assert_array_equal(r.output, w)
        assert router._m_requeued.value() >= 1
        # directory: the dead replica advertises nothing
        assert not any(v["replica"] == victim.name
                       for v in router.directory().values())
        # torn-spill audit: rescan the victim's directory cold — temps
        # are cleared, and every adopted entry reads back whole
        vdir = tmp_path / victim.name
        store = TieredStore(dram_bytes=0, disk_bytes=8_000_000,
                            disk_dir=str(vdir))
        assert not list(vdir.glob(".tmp-*"))
        for hex_d in store.digests()["disk"]:
            assert store.get(bytes.fromhex(hex_d)) is not None
        assert store.metrics.get(
            "engine_tier_corrupt_total").value() == 0
        router.close()
    finally:
        fleet.close()


def test_kill_source_mid_remote_fetch_falls_back(fleet_model,
                                                 tmp_path):
    """Fleet-directory chaos: a request's prefix is warm ONLY on a
    capped replica, so the router places a remote fetch (warm_only
    export) against it — and the source is SIGKILLed with that export
    outstanding. The request must fall back to a colocated cold
    prefill on the survivor and finish bitwise; the blocker request
    mid-decode on the victim re-queues too — zero lost requests."""
    from paddle_tpu.runtime.master import ServingFleet
    model, params, cfg = fleet_model
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, 40, 24).astype(np.int32)
    tails = [rng.randint(0, 40, 5).astype(np.int32) for _ in range(3)]
    p_warm, p_block, p_fetch = (np.concatenate([prefix, t])
                                for t in tails)
    for i in range(2):
        os.makedirs(tmp_path / f"replica{i}")

    fleet = ServingFleet(
        model, replicas=2,
        args_extra=("--tiers_dram_mb=1", "--tiers_disk_mb=4",
                    f"--tiers_dir={tmp_path}" + "/{name}"),
        env={"JAX_PLATFORMS": "cpu"})
    try:
        fleet.start()
        router = fleet.router(health_poll_s=0.2, max_in_flight=1,
                              fetch_flops_per_byte=0.0)
        r_warm = router.submit(p_warm, 6)
        router.run_until_idle()
        assert r_warm.status == "done"
        src_name = r_warm.replica           # the only warm replica
        # fill the warm replica to its cap with a long decode, then
        # ask for the warm prefix again: the fetch path MUST fire
        # (warm source not placeable, cold survivor is)
        r_block = router.submit(p_block, 32)
        r_fetch = router.submit(p_fetch, 6)
        src = next(st for st in router._all if st.name == src_name)
        deadline = time.time() + 120
        while time.time() < deadline:
            router.step()
            if any(k == "export" for _, k in src.outstanding.values()):
                break
        else:
            raise AssertionError("warm_only export never placed on "
                                 "the warm source")
        assert router._m_kv_fetches.value(tier="hbm") >= 1
        fleet.kill(int(src_name.replace("replica", "")))
        router.run_until_idle()
        want6 = _reference(params, cfg, [p_warm, p_fetch], 6)
        want32 = _reference(params, cfg, [p_block], 32)
        for r, w in ((r_warm, want6[0]), (r_fetch, want6[1]),
                     (r_block, want32[0])):
            assert r.status == "done", (r.xid, r.status, r.error)
            np.testing.assert_array_equal(r.output, w)
        assert router.replica_states()[src_name] == "dead"
        assert router._m_requeued.value() >= 1
        survivor = next(n for n in router.replica_states()
                        if n != src_name)
        assert r_fetch.replica == survivor
        assert not any(v["replica"] == src_name
                       for v in router.directory().values())
        router.close()
    finally:
        fleet.close()


def test_route_sigterm_drains_gracefully(fleet_model):
    """The route CLI's drain contract, end-to-end: SIGTERM mid-request
    finishes the accepted request, emits its result, exits 0 — and the
    in-flight state is asserted via the router /healthz before the
    signal lands (same discipline as the serve drain test)."""
    import json
    import re
    import signal
    import subprocess
    import urllib.request

    model, params, cfg = fleet_model
    want = _reference(params, cfg, [np.asarray([1, 2, 3], np.int32)],
                      24)[0]
    p = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu", "route",
         f"--model={model}", "--replicas=1", "--health_port=0"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=REPO)
    try:
        p.stdin.write(json.dumps({"prompt": [1, 2, 3],
                                  "max_new": 24}) + "\n")
        p.stdin.flush()
        url = None
        while url is None:              # jax logs to stderr first
            line = p.stderr.readline()
            if not line and p.poll() is not None:
                raise AssertionError(
                    f"route process died before announcing its "
                    f"health endpoint (rc={p.poll()})")
            m = re.search(r"(http://[\d.:]+)/metrics", line)
            url = m and m.group(1)
        deadline = time.time() + 120
        doc = {}
        while time.time() < deadline:
            doc = json.loads(urllib.request.urlopen(
                url + "/healthz", timeout=5).read())
            if doc.get("requests", 0) >= 1:
                break
            time.sleep(0.05)
        assert doc.get("requests", 0) >= 1, doc
        p.send_signal(signal.SIGTERM)
        out = json.loads(p.stdout.readline())
        assert p.wait(timeout=120) == 0
        assert out["finish_reason"] == "max_tokens"
        np.testing.assert_array_equal(
            np.concatenate([[1, 2, 3], out["tokens"]]), want)
    finally:
        p.kill()

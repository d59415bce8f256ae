"""Serving fleet: KV transfer wire, P/D disaggregation, replica loop
drain, and the prefix-aware router (fast single-process tier; the
multi-process kill-a-replica chaos run lives in test_fleet_chaos.py).

The bitwise contracts pinned here:

- a serialized block roundtrips BITWISE through the transfer wire for
  fp32, int8 and int4 pools (values + scale tables);
- disaggregated P/D generation — prefill on one engine, KV shipped,
  decode on another — equals the colocated single-engine run exactly;
- a dead replica's in-flight requests are re-queued onto survivors and
  every submitted request completes with the same output.
"""

import json
import time

import numpy as np
import pytest

from paddle_tpu.serving import blocks as blocks_mod
from paddle_tpu.serving import transfer
from paddle_tpu.serving.replica import (EngineLoop, EngineReplica,
                                        ListReply, ReplicaServer,
                                        SocketReplica)
from paddle_tpu.serving.router import Router


# -- tiny shared model ------------------------------------------------------

def _cfg():
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    return transformer.TransformerConfig(
        vocab=40, d_model=16, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=32, max_len=64, dtype=jnp.float32, use_rope=True)


@pytest.fixture(scope="module")
def lm():
    import jax
    from paddle_tpu.models import transformer
    cfg = _cfg()
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


# ONE jitted program pair shared by every engine in this module (jit
# re-specializes per pool pytree structure, so fp32 and quantized pools
# ride the same pair) — fresh pools per engine, compiles amortized
_PROGRAMS = {}


def _mk_engine(lm, *, batch=2, num_blocks=None, kv_dtype=None):
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import PagedDecodeEngine, sampling
    params, cfg = lm
    if not _PROGRAMS:
        pf, df = sampling.paged_step_fns(cfg, 8, pallas="off")
        _PROGRAMS["fns"] = (jax.jit(pf), jax.jit(df))
    jpf, jdf = _PROGRAMS["fns"]
    nb = num_blocks if num_blocks is not None else batch * 8
    pool = transformer.init_block_pool(cfg, nb, 8, kv_dtype=kv_dtype)
    return PagedDecodeEngine(
        jpf, jdf, params, pool, batch=batch, cache_len=64,
        block_size=8, num_blocks=nb, chunk_tokens=16, seed=0,
        decode_flops=None, pallas_mode="off", kv_dtype=kv_dtype)


def _mk_spec_engine(lm):
    """A spec engine on the module's geometry; the target drafts for
    itself (what is under test here is the wire, not acceptance)."""
    from paddle_tpu.serving import SpecDecodeEngine
    params, cfg = lm
    return SpecDecodeEngine.from_params(
        params, cfg, params, cfg, spec_k=2, batch=2, cache_len=64,
        block_size=8, chunk_tokens=16, num_blocks=16, seed=0,
        pallas="off", decode_flops=None)


def _ref_outputs(lm, prompts, max_new):
    """Colocated single-engine reference outputs (greedy)."""
    eng = _mk_engine(lm)
    out = []
    for p in prompts:
        r = eng.submit(p, max_new)
        eng.run_until_idle()
        out.append(r.output)
    return out


def _prompts(seed=3, n=6, shared_len=24, vocab=40):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, vocab, shared_len).astype(np.int32)
    return [np.concatenate([shared,
                            rng.randint(0, vocab, 5 + i).astype(np.int32)])
            for i in range(n)]


# -- KV transfer wire -------------------------------------------------------

class TestKVTransfer:
    @pytest.mark.parametrize("kv_dtype", [None, "int8", "int4"])
    def test_roundtrip_bitwise(self, kv_dtype, rng):
        """Serialized blocks land in a DIFFERENT pool position with
        every byte intact — values and scale tables alike."""
        from paddle_tpu.models import transformer
        import jax.numpy as jnp
        cfg = _cfg()
        pool = transformer.init_block_pool(cfg, 6, 8, kv_dtype=kv_dtype)
        filled = {}
        for k, v in pool.items():
            if v.dtype == jnp.int8:
                a = rng.randint(-127, 128, v.shape).astype(np.int8)
            else:
                a = rng.rand(*v.shape).astype(np.asarray(v).dtype)
            filled[k] = jnp.asarray(a)
        digests = [bytes([i]) * 16 for i in range(3)]
        src_blocks, dst_blocks = [1, 3, 5], [0, 2, 4]
        payload = transfer.serialize_blocks(
            filled, src_blocks, digests, 8, kv_dtype or "none")
        meta, got = transfer.deserialize_blocks(payload)
        assert [d for d, _ in got] == digests
        dest = transformer.init_block_pool(cfg, 6, 8, kv_dtype=kv_dtype)
        transfer.check_pool_match(meta, dest, 8, kv_dtype or "none")
        for (_, arrays), db in zip(got, dst_blocks):
            dest = transfer.write_block(dest, db, arrays, 8)
        for sb, db in zip(src_blocks, dst_blocks):
            for name in filled:
                src = np.asarray(filled[name])
                out = np.asarray(dest[name])
                if src.ndim == 4:
                    s, d = src[:, :, sb * 8:(sb + 1) * 8, :], \
                        out[:, :, db * 8:(db + 1) * 8, :]
                else:
                    s, d = src[:, :, sb * 8:(sb + 1) * 8], \
                        out[:, :, db * 8:(db + 1) * 8]
                assert (s == d).all(), name

    def test_stamp_mismatch_refused(self, lm):
        """A payload from a mismatched pool (kv_dtype, block size) is
        refused loudly — silent adoption would poison the cache."""
        from paddle_tpu.models import transformer
        cfg = _cfg()
        pool8 = transformer.init_block_pool(cfg, 4, 8)
        pool_q = transformer.init_block_pool(cfg, 4, 8, kv_dtype="int8")
        payload = transfer.serialize_blocks(
            pool8, [0], [b"x" * 16], 8, "none")
        meta, _ = transfer.deserialize_blocks(payload)
        with pytest.raises(ValueError, match="kv_dtype"):
            transfer.check_pool_match(meta, pool_q, 8, "int8")
        with pytest.raises(ValueError, match="block_size"):
            transfer.check_pool_match(meta, pool8, 4, "none")
        with pytest.raises(ValueError, match="magic"):
            transfer.deserialize_blocks(b"nope" + payload[4:])
        with pytest.raises(ValueError, match="size mismatch"):
            transfer.deserialize_blocks(payload + b"\0")


# -- engine-level P/D disaggregation ---------------------------------------

class TestPDEngine:
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_pd_bitwise_vs_colocated(self, lm, kv_dtype):
        """Prefill on engine P, ship the KV prefix, decode on engine D:
        generation is bitwise the colocated run, and D admits the
        prompt as a prefix-cache HIT (the adopted blocks serve — only
        the final chunk recomputes)."""
        params, cfg = lm
        prompt = np.random.RandomState(1).randint(
            0, 40, 37).astype(np.int32)
        ref = _mk_engine(lm, kv_dtype=kv_dtype)
        r0 = ref.submit(prompt, 8)
        ref.run_until_idle()

        P = _mk_engine(lm, kv_dtype=kv_dtype)
        D = _mk_engine(lm, kv_dtype=kv_dtype)
        assert P.export_prefix(prompt) is None   # nothing published yet
        P.submit(prompt, 1)
        P.run_until_idle()
        payload = P.export_prefix(prompt)
        assert payload is not None
        n = D.import_prefix(payload)
        assert n == len(P.prefix_digests(prompt)) == 4
        rd = D.submit(prompt, 8)
        D.run_until_idle()
        assert rd.prefix_hit_tokens == n * 8
        np.testing.assert_array_equal(rd.output, r0.output)
        # re-import is a no-op (digests already cached)
        assert D.import_prefix(payload) == 0

    def test_import_stops_at_full_pool(self, lm):
        """A receiver that cannot reserve adopts a PARTIAL chain —
        leading blocks only, still hit-servable — instead of failing."""
        prompt = np.random.RandomState(2).randint(
            0, 40, 37).astype(np.int32)
        P = _mk_engine(lm)
        P.submit(prompt, 1)
        P.run_until_idle()
        payload = P.export_prefix(prompt)
        D = _mk_engine(lm, num_blocks=2)    # room for 2 of the 4
        assert D.import_prefix(payload) == 2

    def test_reimport_full_pool_keeps_cached_head(self, lm):
        """Re-importing a chain whose HEAD is already cached must not
        evict those head blocks to adopt the tail — the full-pool
        guard covers previously-cached chain blocks, not just the ones
        this call adopted (a chain with its head evicted serves zero
        hits)."""
        prompt = np.random.RandomState(5).randint(
            0, 40, 37).astype(np.int32)
        P = _mk_engine(lm)
        P.submit(prompt, 1)
        P.run_until_idle()
        payload = P.export_prefix(prompt)
        D = _mk_engine(lm, num_blocks=2)
        assert D.import_prefix(payload) == 2     # head h0, h1 adopted
        digests = D.prefix_digests(prompt)
        head = D.pool.lookup(digests[0])
        assert head is not None
        assert D.import_prefix(payload) == 0     # full pool: adopting
        #                                          h2 would evict h0
        assert D.pool.lookup(digests[0]) == head
        assert D.pool.lookup(digests[1]) is not None

    def test_spec_engine_refuses_import(self):
        """The spec engine's shared-pool invariant (content hashes
        certify draft rows too) cannot survive target-only imports —
        the guard fires before any state is touched."""
        from paddle_tpu.serving import SpecDecodeEngine
        with pytest.raises(ValueError, match="SpecDecodeEngine"):
            SpecDecodeEngine.import_prefix(None, b"")


# -- replica loop (drain + ops) --------------------------------------------

class TestEngineLoop:
    def test_drain_finishes_in_flight(self, lm):
        """The graceful-drain contract: drain() mid-request stops
        ingestion but every accepted request finishes and emits its
        result, and run() returns 0."""
        eng = _mk_engine(lm)
        loop = EngineLoop(eng)
        sink = ListReply()
        loop.feed({"id": 7, "prompt": [1, 2, 3], "max_new": 6}, sink)
        loop.step_once()                 # accepted, now in flight
        assert not eng.idle
        loop.drain()
        assert loop.run() == 0
        docs = [d for d in sink.docs if "tokens" in d]
        assert len(docs) == 1 and docs[0]["id"] == 7
        assert len(docs[0]["tokens"]) == 6
        assert docs[0]["finish_reason"] == "max_tokens"

    def test_drain_covers_already_queued_lines(self, lm):
        """Lines queued before the drain trigger were accepted — they
        run to completion too (SIGTERM between read and admit must not
        lose the request)."""
        eng = _mk_engine(lm)
        loop = EngineLoop(eng)
        sink = ListReply()
        loop.feed(json.dumps({"prompt": [4, 5], "max_new": 3}), sink)
        loop.drain()                     # before any pump
        assert loop.run() == 0
        assert len([d for d in sink.docs if "tokens" in d]) == 1

    def test_drain_seals_against_streaming_client(self, lm):
        """Drain must CONVERGE under a client that never stops
        sending: the first pump after drain() seals the inbox — lines
        already read finish and emit, later feeds are refused with a
        ``draining`` error doc (id echoed, str and dict lines both)."""
        eng = _mk_engine(lm)
        loop = EngineLoop(eng)
        sink = ListReply()
        loop.feed({"id": 1, "prompt": [1, 2, 3], "max_new": 4}, sink)
        loop.drain()
        assert loop.pump()               # seals; request 1 in flight
        loop.feed({"id": 2, "prompt": [4, 5], "max_new": 4}, sink)
        loop.feed(json.dumps({"id": 3, "prompt": [6], "max_new": 2}),
                  sink)
        refusals = [d for d in sink.docs if "error" in d]
        assert [d.get("id") for d in refusals] == [2, 3]
        assert all(d["error"].startswith("draining")
                   for d in refusals)
        assert loop.run() == 0           # still exits despite the feeds
        done = [d for d in sink.docs if "tokens" in d]
        assert len(done) == 1 and done[0]["id"] == 1
        assert len(done[0]["tokens"]) == 4

    def test_malformed_lines_error_not_crash(self, lm):
        eng = _mk_engine(lm)
        loop = EngineLoop(eng)
        sink = ListReply()
        loop.feed("not json", sink)
        loop.feed(json.dumps({"id": 3, "prompt": [],
                              "max_new": 2}), sink)
        loop.feed(json.dumps({"id": 4, "op": "wat"}), sink)
        loop.feed_eof()
        assert loop.run() == 0
        errs = [d for d in sink.docs if "error" in d]
        assert len(errs) == 3
        assert any("bad json" in e["error"] for e in errs)
        assert {e.get("id") for e in errs} == {None, 3, 4}

    def test_export_import_ops(self, lm):
        """The fleet ops over the loop: a cold export warms through the
        ordinary scheduler and serializes at completion; the import ack
        reports adopted blocks; ordering (import before generate on one
        connection) makes the decode admission a hit."""
        prompt = np.random.RandomState(4).randint(
            0, 40, 37).astype(np.int32)
        want = _ref_outputs(lm, [prompt], 6)[0]
        P, D = EngineLoop(_mk_engine(lm)), EngineLoop(_mk_engine(lm))
        ps, ds = ListReply(), ListReply()
        P.feed({"id": 0, "op": "export_prefix",
                "prompt": prompt.tolist()}, ps)
        P.feed_eof()
        assert P.run() == 0
        (exp,) = ps.docs
        assert exp["op"] == "export_prefix" and exp["blocks"] == 4
        D.feed({"id": 1, "op": "import_prefix",
                "payload": exp["payload"]}, ds)
        D.feed({"id": 2, "prompt": prompt.tolist(), "max_new": 6}, ds)
        D.feed_eof()
        assert D.run() == 0
        by_id = {d["id"]: d for d in ds.docs}
        assert by_id[1]["imported"] == 4
        np.testing.assert_array_equal(
            np.concatenate([prompt, by_id[2]["tokens"]]), want)

    def test_spec_replica_serves_the_export_op(self, lm):
        """The loop asks no engine which one it is: a spec replica
        serves the export op like any other — the wire carries target
        blocks, a target-only replica adopts them and decodes the
        colocated tokens."""
        prompt = np.random.RandomState(4).randint(
            0, 40, 37).astype(np.int32)
        want = _ref_outputs(lm, [prompt], 6)[0]
        S, D = EngineLoop(_mk_spec_engine(lm)), EngineLoop(_mk_engine(lm))
        ss, ds = ListReply(), ListReply()
        S.feed({"id": 0, "op": "export_prefix",
                "prompt": prompt.tolist()}, ss)
        S.feed_eof()
        assert S.run() == 0
        (exp,) = ss.docs
        assert exp["op"] == "export_prefix" and exp["blocks"] == 4
        D.feed({"id": 1, "op": "import_prefix",
                "payload": exp["payload"]}, ds)
        D.feed({"id": 2, "prompt": prompt.tolist(), "max_new": 6}, ds)
        D.feed_eof()
        assert D.run() == 0
        by_id = {d["id"]: d for d in ds.docs}
        assert by_id[1]["imported"] == 4
        np.testing.assert_array_equal(
            np.concatenate([prompt, by_id[2]["tokens"]]), want)

    def test_spec_replica_import_op_is_the_engines_refusal(self, lm):
        """The import op against a spec replica comes back as the
        engine's OWN refusal on an error line (no draft rows travel on
        the wire) — not a check in the loop — and the loop keeps
        serving."""
        prompt = np.random.RandomState(4).randint(
            0, 40, 37).astype(np.int32)
        want = _ref_outputs(lm, [prompt], 6)[0]
        P, ps = EngineLoop(_mk_engine(lm)), ListReply()
        P.feed({"id": 0, "op": "export_prefix",
                "prompt": prompt.tolist()}, ps)
        P.feed_eof()
        assert P.run() == 0
        S, ss = EngineLoop(_mk_spec_engine(lm)), ListReply()
        S.feed({"id": 1, "op": "import_prefix",
                "payload": ps.docs[0]["payload"]}, ss)
        S.feed({"id": 2, "prompt": prompt.tolist(), "max_new": 6}, ss)
        S.feed_eof()
        assert S.run() == 0
        by_id = {d["id"]: d for d in ss.docs}
        assert "SpecDecodeEngine cannot adopt" in by_id[1]["error"]
        np.testing.assert_array_equal(
            np.concatenate([prompt, by_id[2]["tokens"]]), want)

    def test_export_short_prompt_empty(self, lm):
        """A prompt without a transferable prefix (shorter than one
        chunk + 1) answers immediately with an empty payload."""
        loop = EngineLoop(_mk_engine(lm))
        sink = ListReply()
        loop.feed({"id": 0, "op": "export_prefix",
                   "prompt": [1, 2, 3]}, sink)
        loop.feed_eof()
        assert loop.run() == 0
        assert sink.docs == [{"id": 0, "op": "export_prefix",
                              "payload": None, "blocks": 0}]


class TestReplicaServer:
    def test_socket_roundtrip_and_drain(self, lm):
        """The TCP transport: a SocketReplica submits over the wire,
        results come back on the same connection; drain() ends
        serve_forever with rc 0 after in-flight work finishes."""
        import threading
        eng = _mk_engine(lm)
        srv = ReplicaServer(eng, port=0)
        rcbox = []
        t = threading.Thread(target=lambda: rcbox.append(
            srv.serve_forever()), daemon=True)
        t.start()
        h = SocketReplica("r0", ("127.0.0.1", srv.port))
        prompt = np.random.RandomState(6).randint(
            0, 40, 21).astype(np.int32)
        want = _ref_outputs(lm, [prompt], 5)[0]
        h.submit({"id": 11, "prompt": prompt.tolist(), "max_new": 5})
        deadline = time.time() + 60
        docs = []
        while not docs and time.time() < deadline:
            docs = h.poll()
            time.sleep(0.01)
        assert docs and docs[0]["id"] == 11
        np.testing.assert_array_equal(
            np.concatenate([prompt, docs[0]["tokens"]]), want)
        srv.drain()
        t.join(timeout=30)
        assert not t.is_alive() and rcbox == [0]
        h.close()


# -- router over fake replicas (placement / failover / requeue) ------------

class FakeReplica:
    """Scripted replica handle: completes each generate after
    ``delay_steps`` pumps with tokens = f(prompt); health/liveness are
    test-controlled."""

    def __init__(self, name, delay_steps=1):
        self.name = name
        self.delay = delay_steps
        self.work = []                    # [spec, remaining]
        self.out = []
        self.health_doc = {"status": "ok", "queue_depth": 0}
        self._alive = True
        self.seen = []
        self.refuse_generate = None       # error string: refuse admits
        self.export_reply = None          # dict overriding export doc
        self.import_error = None          # error string: refuse imports

    def submit(self, spec):
        self.seen.append(dict(spec))
        if spec.get("op", "generate") == "generate":
            if self.refuse_generate:
                self.out.append({"id": spec["id"],
                                 "error": self.refuse_generate})
                return
            self.work.append([dict(spec), self.delay])
        elif spec.get("op") == "export_prefix":
            self.work.append([dict(spec), self.delay])
        else:                             # import: ack next pump
            self.work.append([dict(spec), 0])

    def pump(self):
        still = []
        for item in self.work:
            item[1] -= 1
            if item[1] >= 0:
                still.append(item)
                continue
            spec = item[0]
            op = spec.get("op", "generate")
            if op == "generate":
                self.out.append({
                    "id": spec["id"],
                    "tokens": [int(t) % 7 for t in spec["prompt"]][
                        :spec["max_new"]],
                    "finish_reason": "max_tokens",
                    "ttft_ms": 1.0, "latency_ms": 2.0})
            elif op == "export_prefix":
                doc = {"id": spec["id"], "op": "export_prefix",
                       "payload": None, "blocks": 0}
                if self.export_reply:
                    doc = {"id": spec["id"], **self.export_reply}
                self.out.append(doc)
            else:
                if self.import_error:
                    self.out.append({"id": spec["id"],
                                     "error": self.import_error})
                else:
                    self.out.append({"id": spec["id"],
                                     "op": "import_prefix",
                                     "imported": 0})
        self.work = still

    def poll(self):
        out, self.out = self.out, []
        return out

    def health(self):
        return self.health_doc

    def alive(self):
        return self._alive

    def kill(self):
        self._alive = False

    def close(self):
        pass


def _fake_router(n=2, caps=4, **kw):
    reps = [FakeReplica(f"r{i}") for i in range(n)]
    kw.setdefault("health_poll_s", 0.0)
    router = Router(reps, block_size=4, chunk_tokens=8,
                    max_in_flight=caps, **kw)
    return reps, router


class TestRouterPlacement:
    def test_shared_prefix_converges(self):
        """Shared-prefix prompts land where their digests went first;
        the hit counter proves the prefix-aware path fired."""
        reps, router = _fake_router(2, caps=16)
        shared = np.arange(16, dtype=np.int32)
        reqs = []
        for i in range(5):
            tail = np.full(3 + i, 30 + i, np.int32)
            reqs.append(router.submit(
                np.concatenate([shared, tail]), 4))
        router.run_until_idle()
        homes = {r.replica for r in reqs}
        assert homes == {reqs[0].replica}
        assert router._m_place_hits.value() == 4       # all but the 1st
        assert router.placement_hit_rate() == pytest.approx(0.8)

    def test_least_loaded_fallback_spreads(self):
        """Distinct prompts (no hot prefix anywhere) spread by load."""
        reps, router = _fake_router(2, caps=16)
        rng = np.random.RandomState(0)
        reqs = [router.submit(rng.randint(0, 99, 12).astype(np.int32),
                              2) for _ in range(6)]
        router._place()
        by = {n: sum(1 for r in reqs if r.replica == n)
              for n in ("r0", "r1")}
        assert by == {"r0": 3, "r1": 3}

    def test_in_flight_cap_queues(self):
        reps, router = _fake_router(1, caps=2)
        reps[0].delay = 3
        rng = np.random.RandomState(1)
        reqs = [router.submit(rng.randint(0, 99, 12).astype(np.int32),
                              2) for _ in range(5)]
        router._place()
        assert sum(1 for r in reqs if r.status == "placed") == 2
        assert router.queue_depth == 3
        router.run_until_idle()            # cap releases as work ends
        assert all(r.status == "done" for r in reqs)

    def test_degraded_deprioritized(self):
        """A degraded replica admits only when no ok replica has room —
        even when its prefix is hot."""
        reps, router = _fake_router(2, caps=16)
        shared = np.arange(16, dtype=np.int32)
        r = router.submit(np.concatenate([shared,
                                          np.full(3, 30, np.int32)]), 2)
        router.run_until_idle()
        home = r.replica
        hot = next(rp for rp in reps if rp.name == home)
        other = next(rp for rp in reps if rp.name != home)
        hot.health_doc = {"status": "degraded"}
        r2 = router.submit(np.concatenate([shared,
                                           np.full(4, 31, np.int32)]),
                           2)
        router.run_until_idle()
        assert r2.replica == other.name    # state dominates the prefix
        # ...until the ok replica is full
        other.delay = 50
        fill = [router.submit(np.random.RandomState(9).randint(
            0, 99, 12).astype(np.int32), 2) for _ in range(16)]
        r3 = router.submit(np.concatenate([shared,
                                           np.full(5, 32, np.int32)]),
                           2)
        router._poll_health(time.perf_counter())
        router._place()
        assert r3.replica == home          # degraded beats unplaceable
        router.run_until_idle()
        assert all(x.status == "done" for x in fill + [r3])

    def test_unhealthy_drains_without_requeue(self):
        """unhealthy = stop admitting; in-flight work FINISHES on the
        replica (nothing re-queued, nothing lost)."""
        reps, router = _fake_router(2, caps=16)
        reps[0].delay = 4
        rng = np.random.RandomState(2)
        reqs = [router.submit(rng.randint(0, 99, 12).astype(np.int32),
                              2) for _ in range(4)]
        router._place()
        placed_on_0 = [r for r in reqs if r.replica == "r0"]
        assert placed_on_0
        reps[0].health_doc = {"status": "unhealthy"}
        more = [router.submit(rng.randint(0, 99, 12).astype(np.int32),
                              2) for _ in range(4)]
        router.run_until_idle()
        assert router._m_requeued.value() == 0
        assert all(r.status == "done" for r in reqs + more)
        assert all(r.replica == "r1" for r in more)
        assert all(r.replica == "r0" for r in placed_on_0)
        assert router.replica_states()["r0"] == "unhealthy"

    def test_dead_replica_requeues_all_in_flight(self):
        """The zero-lost-requests contract at the unit tier: kill a
        replica with work outstanding — everything re-queues onto the
        survivor and completes with the same deterministic output."""
        reps, router = _fake_router(2, caps=16)
        reps[0].delay = 1000               # never completes on r0
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 99, 12).astype(np.int32)
                   for _ in range(6)]
        reqs = [router.submit(p, 4) for p in prompts]
        router._place()
        n_victim = sum(1 for r in reqs if r.replica == "r0")
        assert n_victim == 3
        reps[0].kill()
        done = router.run_until_idle()
        assert len(done) == 6
        assert router._m_requeued.value() == n_victim
        assert router.replica_states() == {"r0": "dead", "r1": "ok"}
        for r, p in zip(reqs, prompts):
            assert r.status == "done" and r.replica == "r1"
            np.testing.assert_array_equal(
                r.tokens, [int(t) % 7 for t in p][:4])
        assert {r.requeues for r in reqs} == {0, 1}

    def test_all_replicas_dead_healthz_503(self):
        reps, router = _fake_router(1)
        r = router.submit(np.arange(12, dtype=np.int32), 2)
        router._place()
        reps[0].kill()
        router._poll_health(time.perf_counter())
        doc = router.health()
        assert doc["healthy"] is False
        assert router.queue_depth == 1     # parked, not lost: a
        #                                    replacement replica would
        #                                    pick it up
        assert r.requeues == 1

    def test_prefill_tier_death_falls_back_colocated(self):
        """P/D mode: the prefill replica dies mid-export — the request
        re-queues and completes colocated on the decode tier
        (disaggregation is never a correctness dependency)."""
        pf, dc = FakeReplica("pf", delay_steps=1000), FakeReplica("dc")
        router = Router([pf, dc], block_size=4, chunk_tokens=8,
                        prefill=["pf"], max_in_flight=8,
                        health_poll_s=0.0)
        prompt = np.arange(16, dtype=np.int32)
        r = router.submit(prompt, 3)
        router.step()
        assert r.status == "prefill" and r.prefill_replica == "pf"
        pf.kill()
        router.run_until_idle()
        assert r.status == "done" and r.replica == "dc"
        assert r.requeues == 1

    def test_replica_error_doc_fails_request(self):
        reps, router = _fake_router(1)

        def bad_pump():
            while reps[0].work:
                spec, _ = reps[0].work.pop()
                reps[0].out.append({"id": spec["id"],
                                    "error": "submit: empty prompt"})
        reps[0].pump = bad_pump
        r = router.submit(np.arange(8, dtype=np.int32), 2)
        router.run_until_idle()
        assert r.status == "failed" and "empty prompt" in r.error
        assert router._m_completed.value(reason="error") == 1

    def test_health_doc_shape(self):
        reps, router = _fake_router(2)
        router.submit(np.arange(12, dtype=np.int32), 2)
        router.run_until_idle()
        doc = router.health()
        assert set(doc["replicas"]) == {"r0", "r1"}
        assert doc["replicas"]["r0"]["role"] == "decode"
        assert doc["completed"] == 1 and doc["requeued"] == 0
        assert "ttft_p99_s" in doc["window"]
        text = router.metrics_text()
        assert "router_placements_total" in text
        assert 'router_replica_state{replica="r0"} 3' in text

    def test_export_refusal_falls_back_colocated(self):
        """P/D mode: the prefill replica REFUSES the export (a budget
        rejection of the warm-up request) — not a request failure; the
        request completes colocated and the refusal is counted."""
        pf, dc = FakeReplica("pf"), FakeReplica("dc")
        pf.export_reply = {"error": "submit: 16 prompt + 1 new tokens "
                                    "exceed tenant 'default's budget "
                                    "of 8"}
        router = Router([pf, dc], block_size=4, chunk_tokens=8,
                        prefill=["pf"], max_in_flight=8,
                        health_poll_s=60.0)
        prompt = np.arange(16, dtype=np.int32)
        r = router.submit(prompt, 3)
        router.run_until_idle()
        assert r.status == "done" and r.replica == "dc"
        assert r.prefill_replica == "pf"   # tried once, not retried
        assert sum(1 for s in pf.seen
                   if s.get("op") == "export_prefix") == 1
        assert router._m_pd_errors.value(op="export") == 1
        assert router._m_pd_exports.value() == 0

    def test_import_refusal_counted_not_fatal(self):
        """A refused adoption (stamp mismatch on a misconfigured
        fleet) degrades to a cold prefill — the request completes,
        zero blocks counted as shipped, the refusal counted."""
        pf, dc = FakeReplica("pf"), FakeReplica("dc")
        pf.export_reply = {"op": "export_prefix", "payload": "QUJD",
                           "blocks": 2}
        dc.import_error = "KV payload kv_dtype mismatch: 'int8' vs " \
                          "'none'"
        router = Router([pf, dc], block_size=4, chunk_tokens=8,
                        prefill=["pf"], max_in_flight=8,
                        health_poll_s=60.0)
        r = router.submit(np.arange(16, dtype=np.int32), 3)
        router.run_until_idle()
        assert r.status == "done" and r.replica == "dc"
        assert router._m_pd_exports.value() == 1
        assert router._m_pd_errors.value(op="import") == 1
        assert router._m_pd_blocks.value() == 0

    def test_draining_refusal_requeues(self):
        """A replica that sealed for graceful drain after placement
        won the race refuses with a ``draining`` error — the router
        treats that as a requeue signal (place on a survivor), never
        a request failure."""
        reps, router = _fake_router(2, caps=16, health_poll_s=60.0)
        shared = np.arange(16, dtype=np.int32)
        r1 = router.submit(
            np.concatenate([shared, np.full(3, 30, np.int32)]), 2)
        router.run_until_idle()
        home = next(rp for rp in reps if rp.name == r1.replica)
        other = next(rp for rp in reps if rp.name != r1.replica)
        home.refuse_generate = "draining: replica not admitting"
        r2 = router.submit(
            np.concatenate([shared, np.full(4, 31, np.int32)]), 2)
        router.run_until_idle()
        assert r2.status == "done" and r2.replica == other.name
        assert r2.requeues == 1
        assert router.replica_states()[home.name] == "unhealthy"
        assert router._m_requeued.value() == 1
        assert router._m_completed.value(reason="error") == 0


# -- router over live engines (in-process fleet) ---------------------------

class TestRouterEngines:
    def test_fleet_outputs_bitwise_and_converge(self, lm):
        """A 2-replica in-process fleet serves a shared-prefix trace
        with outputs bitwise the single-engine run, converging the
        shared prefix onto one warm pool."""
        prompts = _prompts()
        want = _ref_outputs(lm, prompts, 6)
        reps = [EngineReplica(_mk_engine(lm), f"r{i}")
                for i in range(2)]
        router = Router(reps, block_size=8, chunk_tokens=16,
                        health_poll_s=0.0)
        reqs = [router.submit(p, 6) for p in prompts]
        done = router.run_until_idle()
        assert len(done) == len(prompts)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(r.output, w)
        assert len({r.replica for r in reqs}) == 1
        assert router.placement_hit_rate() > 0.5

    def test_disaggregated_pd_bitwise(self, lm):
        """Router-level P/D: prefill tier exports, decode tier adopts,
        generation bitwise the colocated run; the decode engine's
        prefix-hit counter proves adoption (not recompute)."""
        prompts = _prompts(seed=8, n=3)
        want = _ref_outputs(lm, prompts, 6)
        pf = EngineReplica(_mk_engine(lm), "pf")
        dc = EngineReplica(_mk_engine(lm), "dc")
        router = Router([pf, dc], block_size=8, chunk_tokens=16,
                        prefill=["pf"], health_poll_s=0.0)
        reqs = [router.submit(p, 6) for p in prompts]
        router.run_until_idle()
        for r, w in zip(reqs, want):
            assert r.prefill_replica == "pf" or r.prefix_score > 0
            np.testing.assert_array_equal(r.output, w)
        assert router._m_pd_exports.value() >= 1
        assert router._m_pd_blocks.value() >= 2
        hits = dc.eng.metrics.get(
            "engine_prefix_cache_hit_blocks_total").value()
        assert hits >= 2 * len(prompts)
        assert dc.eng.metrics.get(
            "engine_kv_blocks_imported_total").value() >= 2


class TestOneProcessPerChip:
    """A chip belongs to one process: the fleet pins replica k to local
    chip k, keeps its own process off every JAX backend, and lets a
    replica's stderr reach the operator."""

    def test_launch_pins_distinct_chips_and_inherits_stderr(
            self, monkeypatch, tmp_path):
        import subprocess

        from paddle_tpu.runtime.master import ServingFleet
        seen = []

        def fake_popen(argv, **kw):
            seen.append((argv, kw))
            return object()

        monkeypatch.setattr(subprocess, "Popen", fake_popen)
        fleet = ServingFleet(str(tmp_path / "lm.tar"), replicas=4,
                             env={"JAX_PLATFORMS": "cpu"})
        for i in range(4):
            fleet._launch(f"replica{i}")
        pins = [kw["env"]["TPU_VISIBLE_CHIPS"] for _, kw in seen]
        ports = [kw["env"]["TPU_MESH_CONTROLLER_PORT"] for _, kw in seen]
        assert pins == ["0", "1", "2", "3"]
        assert len(set(ports)) == 4
        for _, kw in seen:
            assert kw["env"]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert kw["env"]["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert kw["env"]["JAX_PLATFORMS"] == "cpu"   # env= wins last
            assert kw["stderr"] is None                  # = the fleet's
            assert kw["stdout"] is subprocess.PIPE
        # a healed replica keeps its predecessor's chip; any name that
        # ends in its index names its chip; one that does not is refused
        assert ServingFleet.chip_of("replica2") == 2
        assert ServingFleet.chip_of("r11") == 11
        with pytest.raises(ValueError, match="index"):
            ServingFleet.chip_of("spare")

    def test_ready_line_rides_the_endpoint(self, tmp_path):
        """What a replica says about itself (device, kernel paths,
        time-to-ready) is kept with its endpoint for the launcher to
        show — `route` prints it per replica."""
        import io

        from paddle_tpu.runtime.master import ServingFleet
        fleet = ServingFleet(str(tmp_path / "lm.tar"), replicas=1)
        doc = {"port": 7, "health_port": 8,
               "device": {"platform": "cpu", "kind": "cpu", "id": 0,
                          "count": 1, "visible_chips": "0"},
               "kernel_paths": {"decode": {"attention": "xla"}}}
        proc = type("P", (), {"stdout": io.StringIO(
            json.dumps({"replica_ready": doc}) + "\n"),
            "poll": lambda self: None})()
        ep = fleet._await_ready("replica0", proc, time.time() + 5)
        assert ep["port"] == 7 and ep["health_port"] == 8
        assert ep["ready"]["device"]["visible_chips"] == "0"

    def test_router_side_never_initialises_a_backend(self):
        """Everything the `route` parent runs — the CLI module, the
        router, fleet keying, the compile-cache helper — under a JAX
        platform that does not exist: any backend initialisation would
        raise. (On a TPU host a parent that touched JAX would hold the
        chips its replicas need.)"""
        import os
        import subprocess
        import sys
        code = """
import numpy as np
from paddle_tpu import cli
from paddle_tpu.runtime.master import ServingFleet
from paddle_tpu.serving.router import Router, fleet_keying
from paddle_tpu.utils import compile_cache
compile_cache.configure()

class Fake:
    name = "replica0"
    def __init__(self): self.sent = []
    def health(self): return {"block_size": 128, "chunk_tokens": 128}
    def submit(self, spec): self.sent.append(spec)
    def pump(self): pass
    def poll(self): return []
    def alive(self): return True
    def metrics_snapshot(self): return None
    def close(self): pass

h = Fake()
bs, chunk = fleet_keying([h])
router = Router([h], block_size=bs, chunk_tokens=chunk)
router.submit(np.arange(5, dtype=np.int32), 4)
router.step()
assert h.sent, "router never placed the request"
import jax
try:
    jax.devices()
except RuntimeError as e:
    print("BACKEND_UNTOUCHED", type(e).__name__)
"""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=repo,
            env=dict(os.environ, JAX_PLATFORMS="no_such_platform",
                     PYTHONPATH=repo))
        assert r.returncode == 0, r.stderr[-3000:]
        assert "BACKEND_UNTOUCHED" in r.stdout

"""Request-lifecycle observability (PR 7 tentpole): the sliding-window
quantile estimator, the bounded per-request attribution ring, request
lifecycle tracing joined in the Chrome-trace export, SLO burn-rate
degradation on /healthz, the /requests endpoint, trainer step
bottleneck attribution, and the perf-regression sentinel."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import layer, observe
from paddle_tpu.observe import bottleneck
from paddle_tpu.observe import requests as oreq
from paddle_tpu.observe.window import SloConfig, WindowedQuantiles


@pytest.fixture(autouse=True)
def _isolate_observe():
    observe.reset()
    yield
    observe.reset()


class TestWindowedQuantiles:
    def test_exact_quantiles_on_known_sequence(self):
        w = WindowedQuantiles(window_s=100.0)
        for i, v in enumerate([5.0, 1.0, 9.0, 3.0, 7.0]):
            w.observe(v, t=float(i))
        # nearest-rank over sorted [1,3,5,7,9] (the serving_bench _pct
        # convention): q*(n-1) rounded
        assert w.quantile(0.0, now=4.0) == 1.0
        assert w.quantile(0.5, now=4.0) == 5.0
        assert w.quantile(1.0, now=4.0) == 9.0
        assert w.quantile(0.75, now=4.0) == 7.0
        qs = w.quantiles((0.0, 0.5, 1.0), now=4.0)
        assert (qs[0.0], qs[0.5], qs[1.0]) == (1.0, 5.0, 9.0)

    def test_window_expiry_drops_old_samples(self):
        w = WindowedQuantiles(window_s=10.0)
        w.observe(100.0, t=0.0)
        w.observe(1.0, t=9.0)
        assert w.quantile(1.0, now=9.0) == 100.0     # both live
        # t=0 sample ages out at now > 10
        assert w.quantile(1.0, now=10.5) == 1.0
        assert w.count(now=10.5) == 1
        assert w.quantile(0.5, now=25.0) == 0.0      # empty window
        assert w.count(now=25.0) == 0

    def test_max_samples_bound(self):
        w = WindowedQuantiles(window_s=1e9, max_samples=8)
        for i in range(100):
            w.observe(float(i), t=float(i))
        assert w.count(now=99.0) == 8
        # only the newest 8 (92..99) survive
        assert w.quantile(0.0, now=99.0) == 92.0

    def test_agreement_with_cumulative_histogram_stationary(self):
        """On a stationary stream the windowed estimator and the
        cumulative histogram answer the same question: the windowed
        (exact) quantile must land within the histogram's answer's
        bucket (bucket-upper-bound semantics)."""
        buckets = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)
        h = observe.Histogram("agree_h", buckets=buckets)
        w = WindowedQuantiles(window_s=1e9)
        rng = np.random.RandomState(0)
        vals = rng.gamma(2.0, 0.03, size=2000)       # latency-shaped
        for i, v in enumerate(vals):
            h.observe(float(v))
            w.observe(float(v), t=float(i))
        for q in (0.5, 0.95, 0.99):
            hist_q = h.quantile(q)
            exact_q = w.quantile(q, now=float(len(vals)))
            # the exact answer lies in the bucket whose upper bound the
            # histogram reported
            below = max([b for b in buckets if b < hist_q], default=0.0)
            assert below < exact_q <= hist_q, (
                f"q={q}: exact {exact_q} outside histogram bucket "
                f"({below}, {hist_q}]")

    def test_fraction_over_and_burn_rate(self):
        w = WindowedQuantiles(window_s=1e9)
        for i, v in enumerate([0.1] * 95 + [5.0] * 5):
            w.observe(v, t=float(i))
        assert w.fraction_over(1.0, now=100.0) == pytest.approx(0.05)
        slo = SloConfig(ttft_s=1.0, target=0.99)
        assert slo.budget == pytest.approx(0.01)
        assert slo.burn_rate(0.05) == pytest.approx(5.0)
        assert slo.exceeded(0.05)
        assert not slo.exceeded(0.005)
        assert w.fraction_over(1.0, now=1e9 + 101.0) == 0.0  # empty

    def test_validation(self):
        with pytest.raises(ValueError):
            WindowedQuantiles(window_s=0)
        with pytest.raises(ValueError):
            SloConfig(ttft_s=0.0)
        with pytest.raises(ValueError):
            SloConfig(ttft_s=1.0, target=1.0)


class TestRequestLog:
    def _rec(self, rid, ttft=0.1, stall=0.05):
        return {"rid": rid, "ttft_s": ttft, "latency_s": ttft + 0.2,
                "queue_wait_s": 0.01, "prefill_own_s": 0.02,
                "prefill_stall_s": stall, "decode_s": 0.2,
                "finish_reason": "eos", "tokens": 8}

    def test_ring_bounded_no_unbounded_growth(self):
        log = oreq.RequestLog(capacity=16)
        for i in range(1000):
            log.add(self._rec(i))
        assert len(log) == 16
        assert log.evicted() == 1000 - 16
        assert [r["rid"] for r in log.records()] == list(range(984, 1000))

    def test_slowest_orders_and_attributes(self):
        log = oreq.RequestLog(capacity=64)
        for i, ttft in enumerate([0.1, 0.9, 0.5]):
            log.add(self._rec(i, ttft=ttft))
        slow = log.slowest(2)
        assert [r["rid"] for r in slow] == [1, 2]
        a = slow[0]["attribution"]
        assert a["dominant"] in ("queue_wait", "prefill_own",
                                 "prefill_stall", "decode")
        assert sum(a["fractions"].values()) == pytest.approx(1.0)

    def test_attribute_dominant_and_empty(self):
        a = oreq.attribute({"queue_wait_s": 0.01, "prefill_own_s": 0.0,
                            "prefill_stall_s": 0.5, "decode_s": 0.1})
        assert a["dominant"] == "prefill_stall"
        assert a["ttft_dominant"] == "prefill_stall"
        assert a["fractions"]["prefill_stall_s"] > 0.8
        empty = oreq.attribute({})
        assert empty["dominant"] == "none"
        assert empty["ttft_dominant"] == "none"

    def test_ttft_dominance_ignores_decode(self):
        """A long generation must not mask the scheduling artifact:
        decode dominates the lifetime, prefill_stall dominates TTFT."""
        a = oreq.attribute({"queue_wait_s": 0.02, "prefill_own_s": 0.01,
                            "prefill_stall_s": 0.3, "decode_s": 2.0})
        assert a["dominant"] == "decode"
        assert a["ttft_dominant"] == "prefill_stall"

    def test_summary_counts(self):
        log = oreq.RequestLog(capacity=8)
        log.add(self._rec(0))
        log.add(dict(self._rec(1), finish_reason="max_tokens"))
        s = log.summary()
        assert s["count"] == 2 and s["capacity"] == 8
        assert s["by_reason"] == {"eos": 1, "max_tokens": 1}
        assert s["by_dominant_component"] == {"decode": 2}


class TestBottleneckAttribution:
    def test_input_bound(self):
        label, fr = bottleneck.attribute_step(0.08, 0.001, 0.01)
        assert label == "input_bound"
        assert fr["input"] > 0.8
        assert sum(fr.values()) == pytest.approx(1.0)

    def test_compute_bound_without_cost_model(self):
        """No FLOPs estimate: device wait is indistinguishable from
        compute — sync-dominated steps report compute_bound."""
        label, fr = bottleneck.attribute_step(0.001, 0.002, 0.2)
        assert label == "compute_bound"
        assert fr["sync"] == 0.0

    def test_sync_bound_with_cost_model(self):
        """Sync wait far beyond the modeled compute is attributable:
        stragglers/collectives, not this step's math."""
        label, fr = bottleneck.attribute_step(0.001, 0.002, 0.2,
                                              est_compute_s=0.01)
        assert label == "sync_bound"
        assert fr["sync"] > 0.8

    def test_modeled_compute_caps_at_observed_sync(self):
        # est >= sync: everything observed is explained — compute_bound
        label, fr = bottleneck.attribute_step(0.0, 0.001, 0.05,
                                              est_compute_s=1.0)
        assert label == "compute_bound"
        assert fr["sync"] == 0.0

    def test_zero_step_is_unknown(self):
        label, fr = bottleneck.attribute_step(0.0, 0.0, 0.0)
        assert label == "unknown"
        assert all(v == 0.0 for v in fr.values())

    def test_tie_breaks_toward_earlier_stage(self):
        label, _ = bottleneck.attribute_step(0.1, 0.1, 0.0)
        assert label == "input_bound"


def _smallnet():
    img = layer.data("x", paddle.data_type.dense_vector(8))
    lbl = layer.data("y", paddle.data_type.integer_value(3))
    out = layer.fc(img, 3, act=paddle.activation.Softmax())
    cost = layer.classification_cost(out, lbl, name="cost")
    params = paddle.parameters.create(cost)
    return paddle.trainer.SGD(
        cost=cost, parameters=params,
        update_equation=paddle.optimizer.Momentum(learning_rate=0.1))


class TestTrainerBottleneck:
    def test_steps_carry_classification_and_fractions(self):
        recs = []
        observe.add_report_handler(recs.append)
        tr = _smallnet()
        r = np.random.RandomState(0)
        rows = [(r.rand(8).astype("float32"), int(r.randint(3)))
                for _ in range(32)]
        tr.train(paddle.batch(lambda: iter(rows), 8), num_passes=1)
        steps = [x for x in recs if x.get("kind") == "step"]
        assert steps
        for s in steps:
            assert s["bottleneck"] in ("input_bound", "compute_bound",
                                       "sync_bound", "unknown")
            assert 0.0 <= s["frac_input"] <= 1.0
            total = s["frac_input"] + s["frac_compute"] + s["frac_sync"]
            assert total == pytest.approx(1.0, abs=0.01)
        # flight-recorder post-mortems carry the classification too
        fr = observe.default_flight_recorder().records()
        assert fr and "bottleneck" in fr[-1]
        # counter and fraction gauges are live
        c = observe.default_registry().get("train_steps_bottleneck_total")
        assert sum(cell.value for cell in c.series().values()) == \
            len(steps)
        g = observe.default_registry().get("train_bottleneck_fraction")
        assert g.value(component="input") >= 0.0

    def test_starved_input_classifies_input_bound(self):
        recs = []
        observe.add_report_handler(recs.append)
        tr = _smallnet()
        r = np.random.RandomState(0)
        rows = [(r.rand(8).astype("float32"), int(r.randint(3)))
                for _ in range(24)]

        def slow_reader():
            for row in rows:
                time.sleep(0.004)       # ~30ms/batch vs a sub-ms step
                yield row

        tr.train(paddle.batch(slow_reader, 8), num_passes=1)
        steps = [x for x in recs if x.get("kind") == "step"]
        # the compile step may classify compute_bound; the steady-state
        # majority must see the starved input
        labels = [s["bottleneck"] for s in steps[1:]]
        assert labels.count("input_bound") >= len(labels) / 2, labels


# -- engine-side lifecycle tests (tiny transformer, CPU) -------------------

def _paged_engine(batch=2, cache_len=64, block_size=8, chunk_tokens=8,
                  d_model=16, n_layers=2, **kw):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer
    from paddle_tpu.observe.compile_tracker import CompileTracker
    from paddle_tpu.serving import PagedDecodeEngine
    cfg = transformer.TransformerConfig(
        vocab=40, d_model=d_model, n_heads=2, n_kv_heads=1,
        n_layers=n_layers, d_ff=2 * d_model, max_len=cache_len,
        dtype=jnp.float32, use_rope=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return PagedDecodeEngine.from_params(
        params, cfg, batch=batch, cache_len=cache_len,
        block_size=block_size, chunk_tokens=chunk_tokens, seed=0,
        tracker=CompileTracker(), **kw)


def _lifecycle_events(trace_id):
    evs = [e for e in observe.trace_export()["traceEvents"]
           if e.get("cat") == "request" and e.get("id") == trace_id]
    return evs


class TestEngineLifecycle:
    def test_joined_lifecycle_and_ring_bounds(self, rng):
        eng = _paged_engine()
        eng.request_log = oreq.RequestLog(capacity=4)
        prefix = rng.randint(0, 40, 8).astype(np.int32)
        reqs = []
        for tail in (3, 5, 7, 4, 6, 3, 5, 7, 2, 4):
            reqs.append(eng.submit(
                np.concatenate([prefix,
                                rng.randint(0, 40, tail).astype(
                                    np.int32)]), max_new=3))
        eng.run_until_idle()
        # ring bounds: 10 requests through a capacity-4 ring
        assert len(eng.request_log) == 4
        assert eng.request_log.evicted() == 6
        # every completed request has a fully-joined lifecycle
        for r in reqs:
            assert r.finish_reason is not None
            evs = _lifecycle_events(r.trace_id)
            assert evs, f"{r.trace_id}: no lifecycle events"
            b = sum(1 for e in evs if e["ph"] == "b")
            e_ = sum(1 for e in evs if e["ph"] == "e")
            assert b == e_ >= 1, (r.trace_id, b, e_)
            names = {e["name"] for e in evs}
            assert {"request", "queued", "admitted", "prefill",
                    "first_token", "finished"} <= names
        # prefix-cache hit evidence rides the events: a later request
        # reports hit blocks at admission, the first chunk was cold
        first_evs = _lifecycle_events(reqs[0].trace_id)
        chunk = [e for e in first_evs if e["name"] == "prefill_chunk"]
        assert chunk and chunk[0]["args"]["cold_blocks"] >= 1
        # hits arrive either at admission (cache lookup) or mid-flight
        # (adoption of a concurrent same-prefix request's blocks) —
        # both carry hit-block counts on their events
        hit_evidence = 0
        for r in reqs[1:]:
            if r.prefix_hit_tokens <= 0:
                continue
            evs = _lifecycle_events(r.trace_id)
            hit_evidence += sum(
                e["args"].get("hit_blocks", 0) for e in evs
                if e["name"] in ("admitted", "prefix_adopt"))
        assert hit_evidence >= 1

    def test_victim_ttft_dominated_by_prefill_stall(self, rng):
        """The acceptance scenario, deterministically: with a decoder
        in flight and a long-prompt adversary mid-chunked-prefill, a
        just-submitted short victim's TTFT decomposes into stall behind
        the adversary's chunks (+ interleaved decode steps) — NOT queue
        wait (a slot was free) and NOT decode."""
        eng = _paged_engine(batch=3)
        # a decoding request keeps active.any() true: one chunk/step
        a = eng.submit(rng.randint(0, 40, 4).astype(np.int32),
                       max_new=24)
        for _ in range(3):
            eng.step()
        assert a.status == "running"
        adversary = eng.submit(rng.randint(0, 40, 56).astype(np.int32),
                               max_new=4)                # 7 chunks
        # max_new=1: the victim finishes at its first token, so its
        # lifetime has NO decode component at all — the stall-vs-decode
        # dominance comparison is structural, not a wall-clock race
        # between a ~1 ms stall and one (noise-prone) decode step
        victim = eng.submit(rng.randint(0, 40, 4).astype(np.int32),
                            max_new=1)
        eng.run_until_idle()
        assert adversary.finish_reason and victim.finish_reason
        rec = next(r for r in eng.request_log.records()
                   if r["rid"] == victim.rid)
        attr = oreq.attribute(rec)
        assert attr["ttft_dominant"] == "prefill_stall", (rec, attr)
        assert attr["dominant"] == "prefill_stall", (rec, attr)
        assert rec["prefill_stall_s"] > rec["queue_wait_s"]
        assert rec["prefill_stall_s"] > rec["decode_s"]

    def test_rejection_counted_and_traced(self, rng):
        eng = _paged_engine(batch=2, cache_len=32, block_size=8,
                            chunk_tokens=8)
        with pytest.raises(ValueError):
            eng.submit(rng.randint(0, 40, 40), max_new=8)   # > cache
        assert eng.metrics.get("engine_requests_rejected_total").value(
            reason="exceeds_cache") == 1
        rej = [e for e in observe.trace_export()["traceEvents"]
               if e.get("name") == "request_rejected"]
        assert rej and rej[0]["args"]["reason"] == "exceeds_cache"
        # a rejection leaves a ring record too (the requests.py
        # contract): reason in by_reason, no measured components, and
        # it never surfaces in slowest-by-latency views
        recs = eng.request_log.records()
        assert len(recs) == 1
        assert recs[0]["finish_reason"] == "rejected:exceeds_cache"
        assert oreq.attribute(recs[0])["dominant"] == "none"
        assert eng.request_log.summary()["by_reason"] == {
            "rejected:exceeds_cache": 1}
        assert eng.request_log.slowest(5, by="ttft_s") == []

    def test_degraded_healthz_and_requests_endpoint(self, rng):
        eng = _paged_engine()
        eng.configure_slo(SloConfig(ttft_s=10.0, window_s=300.0))
        eng.submit(rng.randint(0, 40, 6).astype(np.int32), max_new=3)
        eng.run_until_idle()
        assert eng.health().get("status") is None        # within SLO
        assert eng.health()["slo"]["ttft_burn_rate"] == 0.0
        # inject the breach: an SLO no real request can meet
        eng.configure_slo(SloConfig(ttft_s=1e-9, target=0.9,
                                    window_s=300.0))
        eng.submit(rng.randint(0, 40, 6).astype(np.int32), max_new=3)
        eng.run_until_idle()
        http = eng.serve()
        try:
            resp = urllib.request.urlopen(http.url + "/healthz",
                                          timeout=5)
            doc = json.loads(resp.read())
            assert resp.status == 200                    # degraded != 503
            assert doc["status"] == "degraded"
            assert "ttft_slo_burn_rate" in doc["degraded_reason"]
            assert doc["slo"]["ttft_burn_rate"] > 1.0
            rq = json.loads(urllib.request.urlopen(
                http.url + "/requests", timeout=5).read())
            assert rq["count"] == 2
            slow = rq["slowest_by_ttft"]
            assert slow and "attribution" in slow[0]
            assert slow[0]["attribution"]["dominant"] != "none"
        finally:
            http.close()
        # windowed gauges published
        g = eng.metrics.get("engine_ttft_window_seconds")
        assert g.value(q="p99") > 0
        assert eng.metrics.get("engine_slo_burn_rate").value() > 1.0

    def test_window_gauges_refresh_on_read(self, rng):
        """Window samples expire with time; the gauges must not keep
        reporting a breach after the window drains (scrape path goes
        through metrics_text / health, both of which refresh)."""
        eng = _paged_engine()
        eng.configure_slo(SloConfig(ttft_s=1e-9, target=0.9,
                                    window_s=300.0))
        eng.submit(rng.randint(0, 40, 6).astype(np.int32), max_new=3)
        eng.run_until_idle()
        assert eng.metrics.get("engine_slo_burn_rate").value() > 1.0
        # simulate every sample expiring: swap in drained estimators
        # with the same window (the engines' clocks are wall-time, so
        # tests can't wait out a real window)
        eng._win_ttft.clear()
        eng._win_tps.clear()
        eng.metrics_text()
        assert eng.metrics.get("engine_slo_burn_rate").value() == 0.0
        assert eng.metrics.get(
            "engine_ttft_window_seconds").value(q="p99") == 0.0
        assert eng.health().get("status") is None     # breach gone


# -- the host's phases of an engine step (PR 24) ---------------------------

PHASES = ("ingest", "schedule", "prefill_chunk", "decode_stage",
          "decode_dispatch", "decode_sync", "emit", "reply")


def _phase_seconds(eng):
    return {p: eng.metrics.get(f"engine_{p}_seconds").snapshot()
            for p in PHASES}


@pytest.fixture(scope="module")
def phase_run():
    """One paged engine behind its replica loop, 12 requests on 4
    slots: the phases as the registry and the span buffer saw them,
    and the loop's wall time."""
    from paddle_tpu.serving.replica import EngineLoop, ListReply
    # wide enough that a step's programs, not the loop around the
    # phases, take the time
    eng = _paged_engine(batch=4, d_model=256, n_layers=8, chunk_tokens=32)
    eng.precompile()
    observe.reset()
    r = np.random.RandomState(7)
    loop, reply = EngineLoop(eng), ListReply()
    for i in range(12):
        loop.feed({"id": i, "max_new": 6 + i % 5,
                   "prompt": r.randint(0, 40, 5 + i % 13).tolist()}, reply)
    loop.feed_eof()
    t0 = time.perf_counter()
    loop.run()
    wall = time.perf_counter() - t0
    assert len(reply.docs) == 12
    return {"eng": eng, "wall": wall, "seconds": _phase_seconds(eng),
            "spans": [s for s in observe.default_buffer().spans()
                      if s[5] == "X" and s[0].startswith("engine/")]}


class TestEnginePhases:
    @pytest.mark.parametrize("phase", PHASES)
    def test_phase_recorded_in_registry_and_span_buffer(self, phase_run,
                                                        phase):
        cell = phase_run["seconds"][phase]
        spans = [s for s in phase_run["spans"]
                 if s[0] == f"engine/{phase}"]
        assert cell["count"] > 0 and cell["sum"] > 0
        assert len(spans) == cell["count"]
        # the registry's seconds are the span's, plus the scope's own
        # exit: the same phase on two clocks
        inner = sum(s[2] for s in spans)
        assert inner <= cell["sum"] <= inner + 5e-4 * len(spans)
        assert set(spans[0][4]) >= {"step", "active"}

    def test_phases_cover_the_loop_and_none_encloses_another(self,
                                                             phase_run):
        named = sum(c["sum"] for c in phase_run["seconds"].values())
        assert 0.9 * phase_run["wall"] <= named <= phase_run["wall"]
        spans = sorted(phase_run["spans"], key=lambda s: s[1])
        assert {s[0] for s in spans} == {f"engine/{p}" for p in PHASES}
        assert len({s[3] for s in spans}) == 1      # one thread
        for a, b in zip(spans, spans[1:]):          # siblings, in turn
            assert a[1] + a[2] <= b[1] + 1e-4, (a[0], b[0])
        eng = phase_run["eng"]
        steps = eng.metrics.get("engine_decode_steps_total").value()
        chunk = [s for s in spans if s[0] == "engine/prefill_chunk"]
        assert len(chunk) == eng.metrics.get(
            "engine_prefill_chunks_total").value()
        assert chunk[0][4]["tokens"] >= 1 and chunk[0][4]["bucket"] >= 1
        # decode_step_seconds keeps its meaning: stage + dispatch + sync
        step_s = eng.metrics.get("engine_decode_step_seconds").snapshot()
        three = sum(phase_run["seconds"][p]["sum"] for p in
                    ("decode_stage", "decode_dispatch", "decode_sync"))
        assert step_s["count"] == steps
        assert step_s["sum"] == pytest.approx(three, rel=0.1)

    @pytest.mark.parametrize("engine", ["paged", "spec"])
    def test_every_engine_steps_through_the_phases(self, engine, rng):
        eng = _paged_engine() if engine == "paged" else _spec_engine()
        for n in (5, 9):
            eng.submit(rng.randint(0, 40, n).astype(np.int32), max_new=4)
        eng.run_until_idle()
        got = {p for p, c in _phase_seconds(eng).items() if c["count"]}
        assert got == set(PHASES) - {"ingest", "reply"}
        steps = eng.metrics.get("engine_decode_steps_total").value()
        for p in ("decode_stage", "decode_dispatch", "decode_sync",
                  "emit"):
            assert _phase_seconds(eng)[p]["count"] == steps

    @pytest.mark.parametrize("stall_s,slow", [(1.2, 1), (0.0, 0)])
    def test_slow_step_counted_once(self, rng, stall_s, slow):
        """A decode step that completes more than a second after the
        one before counts once; a run without one, and the waits with
        no decoder in flight (before and after), count nothing."""
        eng = _paged_engine()
        decode, calls = eng._decode_fn, []

        def stalling(*a):
            calls.append(1)
            if len(calls) == 3:
                time.sleep(stall_s)
            return decode(*a)

        eng._decode_fn = stalling
        eng.submit(rng.randint(0, 40, 5).astype(np.int32), max_new=6)
        eng.run_until_idle()
        time.sleep(0.01)
        eng.submit(rng.randint(0, 40, 5).astype(np.int32), max_new=3)
        eng.run_until_idle()
        assert len(calls) >= 5
        assert eng.metrics.get("engine_slow_steps_total").value() == slow
        assert "engine_slow_steps_total" in eng.metrics_text()


def _spec_engine():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import transformer
    from paddle_tpu.serving import SpecDecodeEngine
    kw = dict(vocab=40, n_heads=2, n_kv_heads=1, d_ff=32, max_len=64,
              dtype=jnp.float32, use_rope=True)
    cfg = transformer.TransformerConfig(d_model=16, n_layers=2, **kw)
    dcfg = transformer.TransformerConfig(d_model=8, n_layers=1, **kw)
    return SpecDecodeEngine.from_params(
        transformer.init_params(jax.random.PRNGKey(0), cfg), cfg,
        transformer.init_params(jax.random.PRNGKey(1), dcfg), dcfg,
        spec_k=2, batch=2, cache_len=64, block_size=8, chunk_tokens=8,
        seed=0)


class TestHealthStatusMapping:
    def test_degraded_is_200_with_status(self):
        srv = observe.HealthServer(
            registry=observe.Registry(),
            health_fn=lambda: {"status": "degraded",
                               "degraded_reason": "test"})
        try:
            resp = urllib.request.urlopen(srv.url + "/healthz",
                                          timeout=5)
            assert resp.status == 200
            doc = json.loads(resp.read())
            assert doc["status"] == "degraded"
            assert doc["degraded_reason"] == "test"
        finally:
            srv.close()

    def test_status_unhealthy_maps_503(self):
        srv = observe.HealthServer(
            registry=observe.Registry(),
            health_fn=lambda: {"status": "unhealthy"})
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/healthz", timeout=5)
            assert ei.value.code == 503
        finally:
            srv.close()

    def test_requests_route_404_without_fn(self):
        srv = observe.HealthServer(registry=observe.Registry())
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/requests", timeout=5)
            assert ei.value.code == 404
        finally:
            srv.close()


class TestStatsCliRequests:
    def test_renders_default_request_log(self, capsys):
        from paddle_tpu import cli
        observe.default_request_log().add(
            {"rid": 7, "ttft_s": 0.25, "latency_s": 0.5, "tokens": 16,
             "queue_wait_s": 0.01, "prefill_own_s": 0.02,
             "prefill_stall_s": 0.3, "decode_s": 0.15,
             "cache_hit_frac": 0.5, "finish_reason": "eos"})
        assert cli.main(["stats", "--requests", "5"]) == 0
        out = capsys.readouterr().out
        assert "r7" in out and "dominated by prefill_stall" in out
        assert "cache_hit 50%" in out


class TestRegressionSentinel:
    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_regression_under_test",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
                "benchmarks", "check_regression.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _write(self, path, speedup, tps, ttft, mtime):
        doc = {"serving_int8_speedup": speedup,
               "throughput": {"engine_paged": {"tokens_per_sec": tps}},
               "latency": {"engine_paged": {"ttft_p99_s": ttft}}}
        with open(path, "w") as f:
            json.dump(doc, f)
        os.utime(path, (mtime, mtime))

    def test_baseline_then_pass_then_regressed(self, tmp_path, capsys):
        mod = self._load()
        d = str(tmp_path)
        self._write(os.path.join(d, "a_serving_paged.json"),
                    1.4, 250.0, 0.5, 1000)
        assert mod.main(["--dir", d]) == 0
        assert "BASELINE" in capsys.readouterr().out
        # within the noise band: PASS
        self._write(os.path.join(d, "b_serving_paged.json"),
                    1.35, 240.0, 0.55, 2000)
        assert mod.main(["--dir", d]) == 0
        out = capsys.readouterr().out
        assert "SENTINEL: PASS" in out and "REGRESSED" not in out
        # speedup collapses past the 15% band: REGRESSED, exit 1
        self._write(os.path.join(d, "c_serving_paged.json"),
                    0.9, 235.0, 0.56, 3000)
        assert mod.main(["--dir", d]) == 1
        out = capsys.readouterr().out
        assert "serving_int8_speedup: REGRESSED" in out
        assert "SENTINEL: REGRESSED" in out

    def test_missing_figure_skips(self, tmp_path, capsys):
        mod = self._load()
        d = str(tmp_path)
        for i, name in enumerate(("a", "b")):
            with open(os.path.join(d, f"{name}_serving_paged.json"),
                      "w") as f:
                json.dump({"unrelated": 1}, f)
            os.utime(os.path.join(d, f"{name}_serving_paged.json"),
                     (1000 + i, 1000 + i))
        assert mod.main(["--dir", d]) == 0
        assert "SKIP" in capsys.readouterr().out

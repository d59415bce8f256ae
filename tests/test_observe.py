"""The unified observability layer (paddle_tpu/observe/): metric
semantics, label handling, JSONL sink round-trip, Prometheus rendering,
trace-scope nesting on the profiler-free CPU path, and the trainer /
master / distributed instrumentation threaded through it."""

import json
import math

import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe.metrics import (Counter, Gauge, Histogram,
                                        JsonlSink, Registry, read_jsonl)
from paddle_tpu.utils import stat


@pytest.fixture(autouse=True)
def _isolate_observe():
    observe.reset()
    yield
    observe.reset()


class TestMetricTypes:
    def test_counter_semantics(self):
        reg = Registry()
        c = reg.counter("requests_total", "reqs")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError, match="negative"):
            c.inc(-1)

    def test_counter_labels_are_independent_series(self):
        reg = Registry()
        c = reg.counter("rpc_total")
        c.inc(phase="prefill")
        c.inc(phase="decode")
        c.inc(phase="decode")
        assert c.value(phase="prefill") == 1
        assert c.value(phase="decode") == 2
        assert c.value(phase="nothing") == 0     # untouched series reads 0
        # probing must not create a phantom series in the render
        assert 'phase="nothing"' not in reg.render_prometheus()

    def test_gauge_set_inc_dec(self):
        reg = Registry()
        g = reg.gauge("queue_depth")
        g.set(5, queue="todo")
        g.inc(queue="todo")
        g.dec(3, queue="todo")
        assert g.value(queue="todo") == 3

    def test_histogram_buckets_and_snapshot(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["min"] == 0.05 and snap["max"] == 50.0
        assert math.isclose(snap["sum"], 55.55)
        assert math.isclose(snap["avg"], 55.55 / 4)

    def test_histogram_timer_context(self):
        reg = Registry()
        h = reg.histogram("t", buckets=(1.0,))
        with h.time(op="x"):
            pass
        assert h.snapshot(op="x")["count"] == 1

    def test_reregistration_returns_existing_and_kind_conflicts_raise(self):
        reg = Registry()
        a = reg.counter("n")
        assert reg.counter("n") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("n")

    def test_histogram_bucket_conflict_raises(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        assert reg.histogram("lat", buckets=(0.1, 1.0)) is h
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("lat", buckets=(0.5,))


class TestPrometheusRendering:
    def test_counter_gauge_text(self):
        reg = Registry()
        reg.counter("a_total", "help a").inc(3)
        reg.gauge("b").set(1.5, host="h0")
        text = reg.render_prometheus()
        assert "# HELP a_total help a" in text
        assert "# TYPE a_total counter" in text
        assert "a_total 3" in text
        assert 'b{host="h0"} 1.5' in text

    def test_histogram_cumulative_buckets(self):
        reg = Registry()
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        text = reg.render_prometheus()
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text       # cumulative
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text
        assert "lat_seconds_sum 5.55" in text

    def test_labels_sorted_and_histogram_label_order(self):
        reg = Registry()
        h = reg.histogram("x", buckets=(1.0,))
        h.observe(0.5, zone="us", app="demo")
        text = reg.render_prometheus()
        # label keys render sorted; le is appended last
        assert 'x_bucket{app="demo",zone="us",le="1"} 1' in text

    def test_label_values_escaped(self):
        # one raw quote/backslash/newline in a label would invalidate
        # the ENTIRE scrape response — the text format requires escaping
        reg = Registry()
        reg.counter("c").inc(path='dir"x\\y\nz')
        line = [l for l in reg.render_prometheus().splitlines()
                if l.startswith("c{")][0]
        assert line == 'c{path="dir\\"x\\\\y\\nz"} 1'


class TestJsonlSink:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with JsonlSink(path) as sink:
            sink.write(step=0, loss=1.25)
            sink.write({"kind": "pass"}, examples=64)
        recs = read_jsonl(path)
        assert len(recs) == 2
        assert recs[0]["step"] == 0 and recs[0]["loss"] == 1.25
        assert recs[1]["kind"] == "pass" and recs[1]["examples"] == 64
        assert all("ts" in r for r in recs)

    def test_non_finite_floats_stay_valid_json(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with JsonlSink(path) as sink:
            sink.write(loss=float("nan"), grad=float("inf"))
        rec = read_jsonl(path)[0]
        assert rec["loss"] == "nan" and rec["grad"] == "inf"

    def test_nested_non_finite_sanitized(self, tmp_path):
        # a diverged pass record carries metrics={"acc": nan} — every
        # line must stay strict-JSON parseable at any nesting depth
        path = str(tmp_path / "m.jsonl")
        with JsonlSink(path) as sink:
            sink.write(kind="pass",
                       metrics={"acc": float("nan"),
                                "deep": [1.0, float("-inf")]})
        with open(path) as f:
            line = f.read().strip()
        assert "NaN" not in line and "Infinity" not in line
        rec = json.loads(line)
        assert rec["metrics"]["acc"] == "nan"
        assert rec["metrics"]["deep"][1] == "-inf"

    def test_malformed_lines_skipped(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write('{"a": 1}\n{"broken...\n{"b": 2}\n')
        recs = read_jsonl(path)
        assert [sorted(r) for r in recs] == [["a"], ["b"]]

    def test_read_last_n(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with JsonlSink(path) as sink:
            for i in range(5):
                sink.write(i=i)
        assert [r["i"] for r in read_jsonl(path, last=2)] == [3, 4]


class TestTraceScopes:
    def test_nesting_qualifies_names_no_profiler(self):
        s = stat.StatSet("t")
        with observe.trace_scope("step", stats=s, use_profiler=False) as q1:
            assert q1 == "step"
            with observe.trace_scope("fwd", stats=s,
                                     use_profiler=False) as q2:
                assert q2 == "step/fwd"
        assert s.get("step").count == 1
        assert s.get("step/fwd").count == 1
        assert observe.current_scope() == ""          # stack drained

    def test_scope_pops_on_exception(self):
        s = stat.StatSet("t")
        with pytest.raises(RuntimeError):
            with observe.trace_scope("outer", stats=s, use_profiler=False):
                raise RuntimeError("boom")
        assert observe.current_scope() == ""
        assert s.get("outer").count == 1              # time still recorded

    def test_step_scope_accumulates(self):
        s = stat.StatSet("t")
        for i in range(3):
            with observe.step_scope(i, "train_step", stats=s,
                                    use_profiler=False):
                pass
        assert s.get("train_step").count == 3

    def test_trace_scope_inside_step_scope_qualifies(self):
        # the documented train_step/region nesting (GUIDE.md §7)
        s = stat.StatSet("t")
        with observe.step_scope(0, "train_step", stats=s,
                                use_profiler=False):
            with observe.trace_scope("region", stats=s,
                                     use_profiler=False) as q:
                assert q == "train_step/region"
        assert s.get("train_step/region").count == 1

    def test_traced_decorator(self):
        s = stat.StatSet("t")

        @observe.traced("work", stats=s, use_profiler=False)
        def f(x):
            return x + 1

        assert f(1) == 2
        assert s.get("work").count == 1

    def test_profiler_on_does_not_crash_on_cpu(self):
        # TraceAnnotation works without an active trace session on CPU —
        # the scope must run and record regardless
        s = stat.StatSet("t")
        with observe.trace_scope("hot", stats=s, use_profiler=True):
            pass
        assert s.get("hot").count == 1


class _FakeAnnotation:
    """Stands where ``jax.profiler``'s annotation classes stand."""

    opened = []

    def __init__(self, name, **kw):
        self.opened.append((type(self).__name__, name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _FakeStepAnnotation(_FakeAnnotation):
    pass


class TestProfilerClock:
    """Every scope is on the profiler's clock whenever jax is in the
    process: no flag, no argument to switch it on (PR 24)."""

    @pytest.fixture
    def fakes(self, monkeypatch):
        from paddle_tpu.observe import trace
        monkeypatch.setattr(trace, "_annotations",
                            (_FakeAnnotation, _FakeStepAnnotation))
        monkeypatch.setattr(_FakeAnnotation, "opened", [])
        return _FakeAnnotation.opened

    @pytest.mark.parametrize("case", ["top_level_keeps_its_name",
                                      "nested_keeps_its_leaf",
                                      "step_scope", "switched_off",
                                      "profile_flag_is_not_read"])
    def test_annotation_opened(self, fakes, case):
        s = stat.StatSet("t")
        if case == "top_level_keeps_its_name":
            with observe.trace_scope("engine/decode_sync", stats=s,
                                     args={"step": 3}) as q:
                assert q == "engine/decode_sync"
            assert fakes == [("_FakeAnnotation", "engine/decode_sync",
                              {"step": 3})]
            span = observe.default_buffer().spans()[-1]
            assert (span[0], span[4]) == ("engine/decode_sync",
                                          {"step": 3})
        elif case == "nested_keeps_its_leaf":
            with observe.trace_scope("feed", stats=s):
                with observe.trace_scope("convert", stats=s) as q:
                    assert q == "feed/convert"
            assert [f[1] for f in fakes] == ["feed", "convert"]
        elif case == "step_scope":
            with observe.step_scope(7, "train_step", stats=s):
                pass
            assert fakes == [("_FakeStepAnnotation", "train_step",
                              {"step_num": 7})]
        elif case == "switched_off":
            with observe.trace_scope("quiet", stats=s, use_profiler=False):
                pass
            assert fakes == [] and s.get("quiet").count == 1
        else:
            from paddle_tpu.utils.flags import GLOBAL_FLAGS
            assert not GLOBAL_FLAGS.get("profile")
            with observe.trace_scope("hot", stats=s):
                pass
            assert [f[1] for f in fakes] == ["hot"]

    @pytest.mark.parametrize("jax_present", [False, True])
    def test_classes_resolved_once_and_only_with_jax(self, monkeypatch,
                                                     jax_present):
        import sys

        from paddle_tpu.observe import trace
        monkeypatch.setattr(trace, "_annotations", None)
        if not jax_present:
            monkeypatch.delitem(sys.modules, "jax")
        s = stat.StatSet("t")
        with observe.trace_scope("a", stats=s):
            with observe.step_scope(0, "b", stats=s):
                pass
        assert s.get("a").count == 1 and s.get("a/b").count == 1
        if jax_present:
            import jax
            assert trace._annotations == (
                jax.profiler.TraceAnnotation,
                jax.profiler.StepTraceAnnotation)
        else:
            # nothing imported, nothing resolved: asked again next time
            assert trace._annotations is None
            assert "jax" not in sys.modules


class TestFeederScopes:
    @pytest.mark.parametrize("path", ["per_sample", "prebatched"])
    def test_stack_and_put_once_per_dense_slot(self, path):
        from paddle_tpu import data_type as dt
        from paddle_tpu.data_feeder import DataFeeder
        from paddle_tpu.utils.stat import global_stats
        feeder = DataFeeder({"a": dt.dense_vector(4),
                             "b": dt.dense_vector(6),
                             "y": dt.integer_value(3)})
        a = np.arange(20, dtype=np.float32).reshape(5, 4)
        b = np.ones((5, 6), np.float32)
        y = np.array([0, 1, 2, 0, 1], np.int32)
        batch = (a, b, y) if path == "prebatched" else \
            [(a[i], b[i], int(y[i])) for i in range(5)]
        before = {n: global_stats.get(f"feed/convert/{n}").count
                  for n in ("stack", "put")}
        with observe.trace_scope("feed"):
            with observe.trace_scope("convert"):
                feeds = feeder.feed(batch)
        np.testing.assert_array_equal(np.asarray(feeds["a"].array), a)
        np.testing.assert_array_equal(np.asarray(feeds["y"].array), y)
        names = [s[0] for s in observe.default_buffer().spans()]
        for n in ("stack", "put"):          # two dense slots, one index
            assert names.count(f"feed/convert/{n}") == 2
            assert global_stats.get(f"feed/convert/{n}").count \
                == before[n] + 2
        # each put follows its stack, both inside convert
        assert names == ["feed/convert/stack", "feed/convert/put"] * 2 \
            + ["feed/convert", "feed"]


class TestStatFixes:
    def test_min_reported_and_empty_guarded(self):
        s = stat.Stat("op")
        assert "count 0" in str(s) and "inf" not in str(s)
        s.add(0.002)
        s.add(0.004)
        line = str(s)
        assert "min 2.000ms" in line and "max 4.000ms" in line

    def test_reset_zeroes_without_dropping_names(self):
        ss = stat.StatSet("t")
        ss.get("a").add(1.0)
        ss.reset()
        assert ss.get("a").count == 0
        assert ss.get("a").min_s == float("inf")
        ss.reset(clear=True)
        assert "a" not in ss._stats


class TestReportHook:
    def test_report_fans_out_to_sink_and_handlers(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        observe.configure(path)
        got = []
        observe.add_report_handler(got.append)
        assert observe.has_consumers()
        observe.report(kind="step", loss=0.5)
        observe.configure(None)
        assert got == [{"kind": "step", "loss": 0.5}]
        assert read_jsonl(path)[0]["loss"] == 0.5

    def test_broken_handler_never_raises(self):
        observe.add_report_handler(
            lambda rec: (_ for _ in ()).throw(RuntimeError("boom")))
        observe.report(x=1)                           # must not raise

    def test_no_consumers_by_default(self):
        assert not observe.has_consumers()

    def test_flag_path_beats_env_sink(self, tmp_path, monkeypatch):
        """paddle.init(metrics_path=a) with PADDLE_TPU_METRICS_PATH=b in
        the env must write to a — the flag is explicit configuration,
        the env sink is only a default."""
        import paddle_tpu as paddle
        from paddle_tpu.utils.flags import GLOBAL_FLAGS
        env_path = str(tmp_path / "env.jsonl")
        flag_path = str(tmp_path / "flag.jsonl")
        monkeypatch.setenv("PADDLE_TPU_METRICS_PATH", env_path)
        GLOBAL_FLAGS.set("metrics_path", flag_path)
        try:
            assert observe.sink_source() == "env"   # env autoconfigured
            tr = TestTrainerInstrumentation._smallnet(self)
            data = TestTrainerInstrumentation._data(self, 8)
            tr.train(paddle.batch(lambda: iter(data), 8), num_passes=1)
        finally:
            GLOBAL_FLAGS.set("metrics_path", "")
            observe.configure(None)
        assert [r for r in read_jsonl(flag_path)
                if r.get("kind") == "step"]

    def test_changed_flag_path_reconfigures(self, tmp_path):
        """Re-setting metrics_path between runs must move the sink —
        a flag-origin sink is a default, not an explicit configure()."""
        import paddle_tpu as paddle
        from paddle_tpu.utils.flags import GLOBAL_FLAGS
        a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
        tr = TestTrainerInstrumentation._smallnet(self)
        data = TestTrainerInstrumentation._data(self, 8)
        try:
            GLOBAL_FLAGS.set("metrics_path", a)
            tr.train(paddle.batch(lambda: iter(data), 8), num_passes=1)
            GLOBAL_FLAGS.set("metrics_path", b)
            tr.train(paddle.batch(lambda: iter(data), 8), num_passes=1)
        finally:
            GLOBAL_FLAGS.set("metrics_path", "")
            observe.configure(None)
        assert [r for r in read_jsonl(a) if r.get("kind") == "step"]
        assert [r for r in read_jsonl(b) if r.get("kind") == "step"]

    def test_explicit_disable_beats_flag(self, tmp_path):
        """observe.configure(None) is an explicit opt-out: a still-set
        metrics_path flag must not resurrect the sink on train()."""
        import paddle_tpu as paddle
        from paddle_tpu.utils.flags import GLOBAL_FLAGS
        path = str(tmp_path / "off.jsonl")
        tr = TestTrainerInstrumentation._smallnet(self)
        data = TestTrainerInstrumentation._data(self, 8)
        try:
            GLOBAL_FLAGS.set("metrics_path", path)
            observe.configure(None)                 # explicit opt-out
            tr.train(paddle.batch(lambda: iter(data), 8), num_passes=1)
        finally:
            GLOBAL_FLAGS.set("metrics_path", "")
        assert not (tmp_path / "off.jsonl").exists()


class TestTrainerInstrumentation:
    def _smallnet(self):
        import paddle_tpu as paddle
        from paddle_tpu import layer
        img = layer.data("x", paddle.data_type.dense_vector(8))
        lbl = layer.data("y", paddle.data_type.integer_value(3))
        out = layer.fc(img, 3, act=paddle.activation.Softmax())
        cost = layer.classification_cost(out, lbl, name="cost")
        params = paddle.parameters.create(cost)
        return paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(learning_rate=0.1))

    def _data(self, n=24):
        r = np.random.RandomState(0)
        return [(r.rand(8).astype("float32"), int(r.randint(3)))
                for _ in range(n)]

    def test_train_emits_per_step_jsonl(self, tmp_path):
        import paddle_tpu as paddle
        path = str(tmp_path / "train.jsonl")
        observe.configure(path)
        tr = self._smallnet()
        data = self._data()
        tr.train(paddle.batch(lambda: iter(data), 8), num_passes=2)
        observe.configure(None)
        recs = read_jsonl(path)
        steps = [r for r in recs if r.get("kind") == "step"]
        passes = [r for r in recs if r.get("kind") == "pass"]
        assert len(steps) == 6 and len(passes) == 2
        for r in steps:
            assert {"step", "wall_time_s", "examples_per_sec", "loss",
                    "recompile"} <= set(r)
        assert steps[0]["recompile"] is True          # first step compiles
        # registry counters moved too
        reg = observe.default_registry()
        assert reg.get("train_steps_total").value() == 6
        assert reg.get("train_examples_total").value() == 48

    def test_end_iteration_carries_observability_fields(self):
        import paddle_tpu as paddle
        tr = self._smallnet()
        seen = []
        tr.train(paddle.batch(lambda: iter(self._data()), 8), num_passes=1,
                 event_handler=lambda e: seen.append(e)
                 if isinstance(e, paddle.event.EndIteration) else None)
        assert seen and all(e.wall_time_s > 0 for e in seen)
        assert all(e.examples_per_sec > 0 for e in seen)

    def test_stats_cli_renders_jsonl(self, tmp_path, capsys):
        import paddle_tpu as paddle
        from paddle_tpu import cli
        path = str(tmp_path / "train.jsonl")
        observe.configure(path)
        tr = self._smallnet()
        tr.train(paddle.batch(lambda: iter(self._data()), 8), num_passes=1)
        observe.configure(None)
        assert cli.main(["stats", f"--metrics_file={path}"]) == 0
        out = capsys.readouterr().out
        assert "steps" in out and "examples/sec" in out and "loss" in out

    def test_stats_cli_prom_format(self, capsys):
        from paddle_tpu import cli
        observe.default_registry().counter("train_steps_total").inc(3)
        assert cli.main(["stats", "--format=prom"]) == 0
        assert "# TYPE train_steps_total counter" in capsys.readouterr().out


class TestMasterMetrics:
    def test_queue_gauges_and_counters(self, tmp_path):
        from paddle_tpu.runtime import recordio
        from paddle_tpu.runtime.master import MasterService
        rio = str(tmp_path / "d.rio")
        recordio.write_records(rio, list(range(30)), chunk_records=10)
        svc = MasterService(name="m_test")
        svc.set_dataset([rio])
        reg = observe.default_registry()
        depth = reg.get("master_task_queue_depth")
        assert depth.value(service="m_test", queue="todo") == 3
        t = svc.get_task()
        assert depth.value(service="m_test", queue="todo") == 2
        assert depth.value(service="m_test", queue="pending") == 1
        svc.report_done(t.task_id)
        assert reg.get("master_tasks_done_total").value(
            service="m_test") == 1
        t2 = svc.get_task()
        svc.report_failed(t2.task_id)
        assert reg.get("master_tasks_failed_total").value(
            service="m_test") == 1

    def test_metrics_rpc_over_wire(self, tmp_path):
        from paddle_tpu.runtime import recordio
        from paddle_tpu.runtime.master import (MasterClient, MasterServer,
                                               MasterService)
        rio = str(tmp_path / "d.rio")
        recordio.write_records(rio, list(range(10)), chunk_records=10)
        svc = MasterService(name="m_wire")
        svc.set_dataset([rio])
        srv = MasterServer(svc)
        try:
            client = MasterClient(addr=srv.addr)
            text = client.metrics_text()
            assert "# TYPE master_task_queue_depth gauge" in text
            assert 'service="m_wire"' in text
            client.close()
        finally:
            srv.shutdown()
            svc.close()


class TestDistributedMetrics:
    def test_single_process_barrier_records(self):
        from paddle_tpu import distributed
        dt = distributed.barrier("unit")
        assert dt >= 0.0
        reg = observe.default_registry()
        assert reg.get("distributed_barriers_total").value(name="unit") == 1
        assert reg.get("distributed_barrier_seconds").snapshot(
            name="unit")["count"] == 1


class TestBenchMetricsOut:
    def test_bench_driver_metrics_flag_parses_and_writes(self, tmp_path,
                                                         monkeypatch):
        """bench.py --metrics-out leaves a JSONL trail through the
        shared benchmarks/bench_metrics helpers (a full bench run needs
        a TPU), and importing bench.py parses nothing and runs nothing."""
        import os
        import sys
        path = str(tmp_path / "bench.jsonl")
        # resolve_metrics_out exports the flag into the environment:
        # register the variable so teardown restores it
        monkeypatch.setenv("BENCH_METRICS_OUT", "")
        monkeypatch.syspath_prepend(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        monkeypatch.setattr(sys, "argv", ["bench.py", "--bogus-flag"])
        import bench
        out = bench.resolve_metrics_out([f"--metrics-out={path}"])
        assert out == path
        bench.metrics_write(out, kind="bench_batch", images_per_sec=123.4)
        recs = read_jsonl(path)
        assert recs and recs[0]["images_per_sec"] == 123.4

"""The eight readers of the host's half of a step (PR 24): each on a
small recorded context, each with nothing to read, their manifest
entries, and the six serving readers and the two feed readers on what
the program itself records."""

import numpy as np
import pytest

from conftest import tiny_cell

from chipbench import harness

SERVING = "cgpt1.3b.batch-gen-standin"
TRAINING = "resnet50.train-b256"

# name -> (unit, source, layer, moves, cell): the issue's table
ENTRIES = {
    "engine_host_ms": ("ms", "program_span", "engine", "serve_tok_s",
                       SERVING),
    "decode_sync_ms": ("ms", "program_span", "engine", "serve_tok_s",
                       SERVING),
    "prefill_chunk_ms": ("ms", "program_span", "engine", "serve_tok_s",
                         SERVING),
    "slow_steps": ("count", "program_counter", "engine", "serve_tok_s",
                   SERVING),
    "artifact_read_s": ("s", "program_span", "entry points", "setup_s",
                        SERVING),
    "precompile_s": ("s", "program_span", "entry points", "setup_s",
                     SERVING),
    "feed_stack_ms": ("ms", "program_span", "trainer", "train_step_ms",
                      TRAINING),
    "feed_put_ms": ("ms", "program_span", "trainer", "train_step_ms",
                    TRAINING),
}

# a window of 100 decode steps and 8 chunks, as ``flat_counters`` deltas
COUNTERS = {"engine_decode_steps_total": 100.0,
            "engine_prefill_chunks_total": 8.0,
            "engine_slow_steps_total": 1.0,
            "engine_ingest_seconds_sum": 0.010,
            "engine_schedule_seconds_sum": 0.040,
            "engine_decode_stage_seconds_sum": 0.150,
            "engine_decode_dispatch_seconds_sum": 0.200,
            "engine_emit_seconds_sum": 0.080,
            "engine_reply_seconds_sum": 0.020,
            "engine_decode_sync_seconds_sum": 5.500,
            "engine_prefill_chunk_seconds_sum": 1.200}
SCOPES = {"artifact/read": 7.5, "artifact/params": 4.0,
          "artifact/programs": 1.0, "precompile/prefill": 2.0,
          "precompile/decode": 0.25}
EXPECTED = {"engine_host_ms": 5.0, "decode_sync_ms": 55.0,
            "prefill_chunk_ms": 150.0, "slow_steps": 1.0,
            "artifact_read_s": 12.5, "precompile_s": 2.25,
            "feed_stack_ms": 30.0, "feed_put_ms": 60.0}


def reader(name):
    return harness.load_module(harness.reader_path(name), "r").read


@pytest.fixture
def stats(monkeypatch):
    """The process's scope totals, empty for this test."""
    from paddle_tpu.utils import stat
    fresh = stat.StatSet("test")
    monkeypatch.setattr(stat, "global_stats", fresh)
    return fresh


@pytest.fixture
def buffer():
    from paddle_tpu import observe
    observe.default_buffer().clear()
    yield observe.default_buffer()
    observe.default_buffer().clear()


def record_feeds(buf, n, inner=True):
    """``n`` feeds a second apart as the trainer records them (inner
    scopes close first): 10 ms of stack and 20 ms of put in the even
    ones, 50 and 100 in the odd ones."""
    for k in range(n):
        t = 100.0 + k
        a, b = (0.010, 0.020) if k % 2 == 0 else (0.050, 0.100)
        if inner:
            buf.add("feed/convert/stack", t + 0.001, a)
            buf.add("feed/convert/put", t + 0.002 + a, b)
        buf.add("feed/convert", t + 0.0005, a + b + 0.002)
        buf.add("feed", t, a + b + 0.003)
        buf.add("train_step", t + 0.5, 0.1)


def recorded_ctx(stats, buf):
    for name, s in SCOPES.items():
        stats.get(name).add(s)
    # six feeds before the window opens, the window's ten, and a span
    # of the same name outside any feed
    record_feeds(buf, 16)
    buf.add("feed/convert/stack", 99.0, 9.0)
    return {"counters": dict(COUNTERS), "steps": 10}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_on_a_recorded_context(name, stats, buffer):
    ctx = recorded_ctx(stats, buffer)
    assert reader(name)(ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_with_its_series_missing_returns_nothing(name, stats,
                                                        buffer):
    """What the parent's program gives these readers: the engine's old
    counters, the trainer's old spans, no scope totals."""
    record_feeds(buffer, 16, inner=False)
    ctx = {"counters": {"engine_decode_steps_total": 100.0,
                        "engine_prefill_chunks_total": 8.0,
                        "engine_decode_step_seconds_sum": 5.8},
           "steps": 10}
    assert reader(name)(ctx) is None
    assert reader(name)(dict(ctx, counters={}, steps=0)) is None


def test_a_window_longer_than_the_buffer_reads_nothing(stats, buffer):
    record_feeds(buffer, 5)
    for name in ("feed_stack_ms", "feed_put_ms"):
        assert reader(name)({"steps": 10}) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_manifest_entry(name, manifest):
    unit, source, layer, moves, cell = ENTRIES[name]
    tail = manifest["per_layer"][-len(ENTRIES):]
    rows = [m for m in tail if m["name"] == name]
    assert len(rows) == 1, "appended at the end, once"
    assert rows[0] == {"name": name, "unit": unit, "better": "lower",
                       "source": source, "layer": layer, "moves": moves,
                       "workloads": [cell]}
    # the layer is one the accepted benchmark already names
    assert layer in {m["layer"] for m in
                     manifest["per_layer"][:-len(ENTRIES)]}
    assert name in [m["name"] for m in harness.Cell(cell).per_layer]


# -- on what the program itself records -------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The tiny serving cell built and driven once: the readers'
    context as ``systems/lm_serving.run`` makes it."""
    from chipbench.systems import lm_serving
    from paddle_tpu.utils.stat import global_stats
    work = tmp_path_factory.mktemp("chipbench_work")
    cell = tiny_cell(SERVING, "tiny-gpt", "tiny-batch", {})
    names = list(SCOPES)
    before = {n: global_stats.get(n).total_s for n in names}
    srv, eng, spans = lm_serving.build(cell, 2 ** 31 + 17, str(work))
    box = lm_serving.drive(cell, eng, 2 ** 31 + 17, 1.5)
    return {"counters": lm_serving.delta(box["snaps"]["open"],
                                         box["snaps"]["close"]),
            "spans": spans, "slots": eng.batch,
            "scope_s": {n: global_stats.get(n).total_s - before[n]
                        for n in names}}


def test_serving_readers_on_the_engines_own_counters(served):
    c = served["counters"]
    steps = c["engine_decode_steps_total"]
    assert steps > 10
    host = reader("engine_host_ms")(served)
    sync = reader("decode_sync_ms")(served)
    chunk = reader("prefill_chunk_ms")(served)
    assert host > 0 and sync > 0 and chunk > 0
    assert reader("slow_steps")(served) == 0
    # the phases account for the window: nothing large is left unnamed
    named = (host + sync) * steps / 1000.0 \
        + chunk * c["engine_prefill_chunks_total"] / 1000.0
    assert 0.7 * 1.5 <= named <= 1.5 * 1.05


def test_setup_readers_on_the_loaders_own_scopes(served):
    """(Scope totals are the process's: other tests of this worker may
    have loaded artifacts, so the run's own share is taken apart.)"""
    got = served["scope_s"]
    assert all(got[n] > 0 for n in SCOPES), got
    assert reader("artifact_read_s")(served) >= sum(
        got[n] for n in SCOPES if n.startswith("artifact/"))
    assert reader("precompile_s")(served) >= \
        got["precompile/prefill"] + got["precompile/decode"]
    # from inside, the parts of what ``replica_ready_s`` times from
    # outside: together they are most of it and no more
    inside = sum(got.values())
    ready = served["spans"]["replica_ready_s"]
    assert 0.5 * ready <= inside <= ready


def test_feed_readers_on_the_trainers_own_loop(buffer):
    """``SGD.train`` with a host reader, a window of the last steps as
    the training cell opens one: the two halves read from the span
    buffer add up to the ``feed`` scope's own seconds over that window."""
    import paddle_tpu as paddle
    from paddle_tpu import layer
    from paddle_tpu.utils.stat import global_stats
    img = layer.data("x", paddle.data_type.dense_vector(8192))
    lbl = layer.data("y", paddle.data_type.integer_value(3))
    cost = layer.classification_cost(
        layer.fc(img, 3, act=paddle.activation.Softmax()), lbl)
    trainer = paddle.trainer.SGD(
        cost=cost, parameters=paddle.parameters.create(cost),
        update_equation=paddle.optimizer.Momentum(learning_rate=0.1))
    r = np.random.RandomState(0)
    rows = [(r.rand(8192).astype("float32"), int(r.randint(3)))
            for _ in range(32)]
    st = {"n": 0, "stop": False}
    warm, steps = 4, 6

    def reader_():
        while not st["stop"]:
            yield from rows

    def handler(ev):
        if not isinstance(ev, paddle.event.EndIteration):
            return
        st["n"] += 1
        if st["n"] == warm:
            st["open"] = global_stats.get("feed").total_s
        if st["n"] == warm + steps:
            st["close"] = global_stats.get("feed").total_s
            st["stop"] = True

    trainer.train(paddle.batch(reader_, 32), num_passes=1,
                  event_handler=handler)
    ctx = {"steps": steps}
    stack = reader("feed_stack_ms")(ctx)
    put = reader("feed_put_ms")(ctx)
    feed_ms = 1000.0 * (st["close"] - st["open"]) / steps
    assert stack > 0 and put > 0
    assert stack + put <= feed_ms
    assert stack + put >= 0.5 * feed_ms     # the rest: the label's slot
    names = [s[0] for s in buffer.spans()]
    assert names.count("feed") == warm + steps + 1   # one batch ahead

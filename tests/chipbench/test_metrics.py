"""Each per-layer reader on a hand-made context: the number it takes,
and nothing when there is nothing to read."""

import os

import pytest

from chipbench import harness

PEAKS = {"flops_per_s": {"bf16": 197e12}, "hbm_bytes_per_s": 819e9}
DIMS = {"d_model": 4, "n_heads": 2, "n_layers": 3, "d_ff": 16, "vocab": 10,
        "max_len": 8, "eps": 1e-5}


def reader(name):
    return harness.load_module(harness.reader_path(name), "r").read


def rec(first, arrive, n_out, n_prompt):
    return {"due": 0.0, "sent": 0.0, "arrive": arrive, "ttft_ms": 0.0,
            "latency_ms": 1000.0 * (arrive - first), "n_out": n_out,
            "n_prompt": n_prompt, "error": None}


def serving_ctx(**over):
    ctx = {"counters": {"engine_decode_steps_total": 10,
                        "engine_tokens_total": 62,
                        "engine_prefill_calls_total": 2,
                        "engine_prefix_cache_hit_blocks_total": 6,
                        "engine_prefix_cache_miss_blocks_total": 2},
           "traced_counters": {"engine_prefix_cache_hit_blocks_total": 0},
           "slots": 8, "block_size": 128, "dims": DIMS, "peaks": PEAKS,
           "spans": {"replica_ready_s": 12.5,
                     "compile_cache": {"hits": 18, "misses": 0}},
           "trace": {"busy_s": 2.0, "window_s": 2.5,
                     "modules": {"jit_call_exported(1)": [1.8, 30],
                                 "jit_call_exported(2)": [0.15, 3],
                                 "jit_call_exported(3)": [0.05, 1],
                                 "jit_other(9)": [9.0, 99]}},
           "traced_interval": (10.0, 12.5),
           "records": [rec(10.0, 12.0, 21, 100)]}
    ctx.update(over)
    return ctx


def test_counter_readers():
    ctx = serving_ctx()
    assert reader("slot_fill_pct")(ctx) == pytest.approx(75.0)
    assert reader("replica_ready_s")(ctx) == 12.5
    assert reader("compile_miss_count")(ctx) == 0


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = serving_ctx(counters={}, spans={},
                        trace={"busy_s": 0.0, "window_s": 0.0,
                               "modules": {}},
                        records=[])
    for name in ("slot_fill_pct", "replica_ready_s", "compile_miss_count",
                 "decode_dev_ms", "decode_hbm_roofline_pct",
                 "serve_mfu_pct", "device_idle_pct.tok"):
        assert reader(name)(empty) is None, name


def test_a_quantity_split_by_what_it_moves_shares_one_reader():
    """``device_idle_pct.tok`` and ``.train`` are one quantity under the
    two end-to-end metrics it moves: one reader, found by the part of
    the name before the first dot; a reader under the full name wins."""
    shared = os.path.join(harness.HERE, "metrics", "device_idle_pct.py")
    assert harness.reader_path("device_idle_pct.tok") == shared
    assert harness.reader_path("device_idle_pct.train") == shared
    assert harness.reader_path("feed_ms") == os.path.join(
        harness.HERE, "metrics", "feed_ms.py")
    every = {m["name"] for m in harness.manifest()["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(harness.HERE, "metrics"))
             if f.endswith(".py")}
    assert files == {n if n in files else n.split(".", 1)[0] for n in every}


def test_the_decode_program_is_the_one_executed_most_often():
    ctx = serving_ctx()
    assert reader("decode_dev_ms")(ctx) == pytest.approx(60.0)
    for suffix in ("tok", "train"):
        assert reader(f"device_idle_pct.{suffix}")(ctx) == \
            pytest.approx(20.0)


def test_roofline_and_mfu_by_hand():
    from chipbench import flops
    ctx = serving_ctx()
    # 20 decode tokens (1..20) of a 100-token prompt inside the stretch
    ctx_sum = sum(100 + i for i in range(1, 21))
    byts = 30 * flops.lm_weight_bytes(DIMS) \
        + ctx_sum * flops.lm_kv_bytes_per_token(DIMS)
    assert reader("decode_hbm_roofline_pct")(ctx) == pytest.approx(
        100 * byts / 819e9 / 1.8)
    dec = 20 * flops.lm_token_flops(DIMS, 0.0) + 4.0 * 3 * 4 * ctx_sum
    pre = 2.0 * flops.lm_matmul_params(DIMS) * 100 \
        + 4.0 * 3 * 4 * (100 * 101 / 2) + 2.0 * 10 * 4
    assert reader("serve_mfu_pct")(ctx) == pytest.approx(
        100 * (dec + pre) / (2.5 * 197e12))


def test_trainer_readers():
    ctx = {"stats": {"feed": (1.2, 10), "host_sync": (0.5, 10)},
           "steps": 10, "window_s": 2.0, "chips": 1, "peaks": PEAKS,
           "step_flops": 197e12 * 0.05,
           "trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert reader("feed_ms")(ctx) == pytest.approx(120.0)
    assert reader("host_sync_ms")(ctx) == pytest.approx(50.0)
    assert reader("train_mfu_pct")(ctx) == pytest.approx(25.0)
    assert reader("device_idle_pct.train")(ctx) == pytest.approx(50.0)
    none = dict(ctx, stats={}, steps=0)
    assert reader("feed_ms")(none) is None
    assert reader("train_mfu_pct")(none) is None

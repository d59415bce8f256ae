"""The Qwen3-Next cell without a chip: its files against the catalog's
published keys, the work arithmetic by hand, the three readers on
hand-made ``ctx``, the system module's refusal on a program that lacks
the skeleton, and a tiny cell through the module end to end with the
control and a planted fault."""

import json
import time

import pytest

from chipbench import harness, q3next_work
from conftest import tiny_cell

CELL = "q3next.long-gen-standin"
# The tiny cell computes in float32: at 64 wide, 16 experts top-4, bf16
# rounding alone puts a fifth of the served tokens off the reference's
# best (widest gap 1.0 - 2.4 over ~650 tokens, the fp8 control's 4.0:
# too close to tell; tests/test_gated_hybrid.py holds the bf16 path to
# the reference by logits). Tiny-size readings (CPU, seeds 11, 13; ~550
# served tokens each), widest gap / mean gap: the program 0.0 - 0.01 /
# under 2e-5; the fp8 control 2.7 - 4.1 / 0.53 - 0.65; int8 1.35 - 1.53
# / 0.155 - 0.165; a chunk's recurrent rows lost 5.6 / 2.07; half of
# one layer's held experts dropped 1.43 / 0.081
LIMITS = {"gap_max": {"limit": 0.5}, "gap_mean": {"limit": 0.05},
          "unanswered": {"limit": 0}}
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def config():
    return harness.load_json(
        harness.HERE + "/configs/qwen3-next-80b-a3b-standin.json")


def test_the_file_holds_the_published_keys_and_states_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == changed | {"num_experts_held"}
    assert (config["num_hidden_layers"], config["num_experts_held"],
            config["vocab_size"]) == (4, 256, 75968)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts_held": 512,
                                   "vocab_size": 151936}
    assert "2 chips share each layer" in config["deployment"]
    assert config["name"].endswith("-standin") and config["stands_in_for"]
    assert config["serving"] == {
        "slots": 32, "cache_len": 8192, "block_size": 128,
        "chunk_buckets": [256, 1024], "num_blocks": 2048,
        "kernel_paths": "xla", "pallas": "off"}
    # the floors: a whole period, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0 and config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_traffic_is_the_issues(manifest):
    cell = harness.Cell(CELL, manifest)
    t = cell.traffic
    assert t["generator"] == "sessions" and cell.chips == 1
    assert t["arrival"] == {"kind": "closed", "outstanding": 40}
    assert t["new_tokens"] == {"dist": "loguniform", "lo": 1024, "hi": 6144}
    assert t["output_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    assert (t["max_total_tokens"], t["sessions"], t["ramp_s"], t["turns"],
            t["tenants"], t["stratum"], t["check_sample"], t["trace_s"]) \
        == (7680, 400, 5, 1, 0, 4, 5, 3)
    assert t["sampling"] == {"greedy": 2, "sampled": 1, "temperature": 0.8,
                             "top_k": 40} and "schedule_seed" in t
    from chipbench.generators import sessions
    plan = sessions.plan(t, 7, cell.config["vocab_size"], 30.0)
    turns = [s["turns"] for s in plan["sessions"]]
    assert all(len(ts) == 1 for ts in turns)        # nothing cut short
    total = [len(ts[0]["new"]) + ts[0]["max_new"] for ts in turns]
    assert max(total) <= 7680 <= cell.config["serving"]["cache_len"]
    assert max(max(ts[0]["new"]) for ts in turns) < 75968


def test_work_by_hand(config):
    d = q3next_work.dims(config)
    assert (d["L_rec"], d["L_full"], d["held"]) == (3, 1, 256)
    assert q3next_work.rec_mixer_params(d) == 2048 * 12288 + 2048 * 64 \
        + 8192 * 4 + 4096 * 2048 == 33718272
    assert q3next_work.full_mixer_params(d) == 2048 * 8192 \
        + 2 * 2048 * 512 + 4096 * 2048 == 27262976
    assert q3next_work.expert_params(d) == 3145728
    assert q3next_work.router_params(d) == 1048576
    dense = 3 * 33718272 + 27262976 + 4 * (1048576 + 3145728 + 2048)
    assert q3next_work.dense_params(d) == dense
    # one decoded token at context 1000 that kept 5 of its 40 assignments
    assert q3next_work.stretch_flops(d, 1, 1000, 5, 1) == pytest.approx(
        2 * dense + 2 * 3145728 * 5 + 4 * 16 * 256 * 1000
        + 6 * 3 * 32 * 128 * 128 + 2 * 75968 * 2048)
    assert q3next_work.kv_bytes_per_token(d) == 2 * 2 * 256 * 2
    assert q3next_work.state_bytes_per_slot(d) == 3 * (
        4 * 32 * 128 * 128 + 2 * 3 * 8192)
    two = 3 * 33718272 + 27262976 + 4 * (3145728 + 2048) + 75968 * 2048
    four = 4 * (1048576 + 2 * 2048) + 2048 + 3 * (64 + 128) + 2 * 256
    assert q3next_work.step_weight_bytes(d) == 2 * two + 4 * four


def hand_ctx(config, **counters):
    recs = [{"arrive": 10.0, "latency_ms": 10000.0, "ttft_ms": 0.0,
             "n_prompt": 1000, "n_out": 1001}]      # a token every 10 ms
    return {"dims": q3next_work.dims(config), "records": recs,
            "traced_interval": (2.0, 3.0),
            "traced_counters": counters, "counters": counters,
            "peaks": {"flops_per_s": {"bf16": 197e12},
                      "hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 1.0, "modules": {
                "jit_call_exported(1)": [0.8, 100],
                "jit_call_exported(2)": [0.1, 3]}}}


def read(name, ctx):
    return harness.load_module(harness.reader_path(name), "m").read(ctx)


def test_readers_on_a_hand_made_stretch(config):
    ctx = hand_ctx(config, engine_moe_assignments_total=500.0,
                   engine_moe_decode_experts_hit_total=400 * 119.0,
                   engine_moe_decode_layer_calls_total=400.0)
    d = ctx["dims"]
    # 100 tokens decoded in [2, 3): tokens 200..299, context 1200..1299
    keys = sum(1000 + i for i in range(200, 300))
    f = q3next_work.stretch_flops(d, 100, keys, 500, 100)
    assert read("serve_mfu_pct.q3next", ctx) == pytest.approx(
        100 * f / 197e12)
    byts = 100 * q3next_work.step_weight_bytes(d) \
        + 2 * 3145728 * 400 * 119 + 2048 * keys \
        + 2 * q3next_work.state_bytes_per_slot(d) * 100
    assert read("decode_hbm_roofline_pct.q3next", ctx) == pytest.approx(
        100 * byts / 819e9 / 0.8)
    assert read("moe_experts_hit_pct", ctx) == pytest.approx(
        100 * 119 / 256)


def test_readers_find_nothing_on_a_program_without_the_counters(config):
    ctx = hand_ctx(config, engine_decode_steps_total=100.0)
    for name in ("serve_mfu_pct.q3next", "decode_hbm_roofline_pct.q3next",
                 "moe_experts_hit_pct"):
        assert read(name, ctx) is None


def test_a_program_without_the_skeleton_is_refused_before_any_export(
        monkeypatch, tmp_path):
    from chipbench.systems import q3next_serving as system
    from paddle_tpu.models import transformer

    def old_config(**kw):
        raise TypeError("TransformerConfig.__init__() got an unexpected "
                        "keyword argument 'skeleton'")

    monkeypatch.setattr(transformer, "TransformerConfig", old_config)
    cell = tiny_cell(CELL, "tiny-q3next", "tiny-long-gen", LIMITS)
    t = time.time()
    with pytest.raises(SystemExit, match="cannot state"):
        system.build(cell, 1, str(tmp_path))
    assert time.time() - t < 5 and not list(tmp_path.iterdir())


# -- the tiny cell end to end -------------------------------------------------

@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_q3next_work")


def run_cell(cell, capsys, work, seed, **kw):
    from chipbench.systems import q3next_serving as system
    rc = system.run(cell, seed=seed, seconds=2.0, trace=False,
                    device=harness.device_info(), t_start=time.time(),
                    work=str(work), **kw)
    cap = capsys.readouterr()
    assert rc == 0
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err


def test_tiny_cell_runs_and_is_correct(capsys, work):
    cell = tiny_cell(CELL, "tiny-q3next", "tiny-long-gen", LIMITS)
    doc, err = run_cell(cell, capsys, work, 2 ** 31 + 12)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"serve_tok_s", "setup_s"}
    assert doc["metrics"]["serve_tok_s"]["value"] > 0
    assert doc["notes"]["tokens_compared"] >= 40
    assert doc["notes"]["moe_assignments_in_window"] > 0
    assert doc["notes"]["state"]["recurrent_state_bytes"] \
        == 6 * 4 * (4 * 4 * 16 * 16 + 4 * 3 * 128)
    assert "compared gap_max" in err


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, work):
    cell = tiny_cell(CELL, "tiny-q3next", "tiny-long-gen", LIMITS)

    def break_engine(eng):
        inner = eng._decode_fn

        def altered(*a):
            out, cache = inner(*a)
            return out.at[:eng.batch].set((out[:eng.batch] + 1) % 256), cache

        eng._decode_fn = altered

    doc, err = run_cell(cell, capsys, work, 13, break_engine=break_engine)
    assert doc["correct"] is False
    assert doc["compared"]["gap_max"]["ok"] is False and "NOT OK" in err


def tool_readings(work, **kw):
    """``tools/limits_q3next.py``'s readings of the tiny cell."""
    import argparse
    from chipbench.systems import q3next_serving as system
    tool = harness.load_module(
        harness.HERE + "/tools/limits_q3next.py", "limits_q3next")
    limits = harness.load_module(harness.HERE + "/tools/limits.py", "limits")
    cell = tiny_cell(CELL, "tiny-q3next", "tiny-long-gen", LIMITS)
    args = argparse.Namespace(workload=CELL, seconds=3.0, budget_s=600.0,
                              **kw)
    out = {"rows": [], "faults": [], "verdicts": []}
    tool.save = lambda *a: None             # nothing under chiprun_out/
    tool.collect(system, cell, args, out, [11, 13], str(work),
                 limits.judged)
    return out


def test_the_tool_reads_program_controls_and_faults_each_to_its_verdict(
        work):
    out = tool_readings(work, controls=1, faults=2)
    a, b = out["rows"]
    for row in (a, b):
        assert row["tokens_compared"] >= 100 == 100 * row["judged"]["ok"]
        assert len(row["gaps"]) == row["tokens_compared"]
        assert row["gap_mean"] <= row["gap_max"] <= LIMITS["gap_max"]["limit"]
    for c in ("fp8", "int8"):               # on the first seed only
        assert f"control_{c}" not in b
        assert a[f"control_{c}"]["gap_mean"] > LIMITS["gap_mean"]["limit"]
        assert a[f"control_{c}_judged"]["ok"] is False
    assert a["control_fp8"]["gap_max"] > LIMITS["gap_max"]["limit"]
    assert [f["fault"] for f in out["faults"]] == ["state_zeroed",
                                                   "experts_dropped"]
    for f in out["faults"]:                 # planted, read, and undone
        assert f["gap_mean"] > LIMITS["gap_mean"]["limit"], f["fault"]
        assert f["judged"] == {"ok": False,
                               "failed": ["gap_max", "gap_mean"]}
    assert all(ok == sound for _, sound, ok in out["verdicts"])
    assert len(out["verdicts"]) == 2 + 2 + 2

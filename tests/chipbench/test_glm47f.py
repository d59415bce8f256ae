"""The GLM-4.7-Flash cell without a chip: its files against the
catalog's published keys, the work arithmetic by hand, the three new
readers on hand-made ``ctx``, the system module's refusal on a program
that lacks the skeleton, and a tiny cell through the module end to end
with the controls and the reference's planted faults."""

import json
import time

import pytest

from chipbench import glm47f_work, harness
from conftest import tiny_cell

CELL = "glm47f.long-ctx-gen-standin"
# The tiny cell computes in float32 (tests/test_latent_moe.py holds the
# bf16 path to the reference by logits: at 64 wide bf16 rounding alone
# puts served tokens off the reference's best by more than the int8
# control does). Tiny-size readings (CPU, seeds 11, 13; ~500 served
# tokens each), widest gap / mean gap: the program 0.0 - 0.001 / under
# 1e-5; the int8 control 3.3 / 0.083; fp8 3.0 / 0.39; the planted faults
# rope_key_unrotated 5.4 / 1.66, latent_norm_skipped 4.2 / 0.92,
# scale_dropped 2.1 / 0.23, bias_in_weights 1.2 / 0.0065
LIMITS = {"gap_max": {"limit": 0.3}, "gap_mean": {"limit": 0.01},
          "unanswered": {"limit": 0}}
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}


@pytest.fixture(scope="module")
def config():
    return harness.load_json(
        harness.HERE + "/configs/glm-4.7-flash-standin.json")


def test_the_file_holds_the_published_keys_and_states_its_cut(config):
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 5
    assert config["published"] == {"num_hidden_layers": 47}
    assert "num_experts_held" not in config     # every expert is held
    assert "no chip shares a layer" in config["deployment"]
    assert config["name"].endswith("-standin") and config["stands_in_for"]
    assert config["source"] == ("https://huggingface.co/zai-org/"
                                "GLM-4.7-Flash/blob/main/config.json")
    assert any("multi-token-prediction" in d and "NOT run" in d
               for d in config["departures"])
    assert {"rotary pairing", "weights", "e_score_correction_bias"} \
        <= set(config["assumed"])
    assert config["serving"] == {
        "slots": 48, "cache_len": 14336, "block_size": 128,
        "chunk_buckets": [256, 1024], "num_blocks": 5376,
        "kernel_paths": "xla", "pallas": "off"}
    # the floors: the leading dense layer and four of those that follow
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4


def test_the_traffic_is_the_issues(manifest):
    cell = harness.Cell(CELL, manifest)
    t = cell.traffic
    assert t["generator"] == "sessions" and cell.chips == 1
    assert t["arrival"] == {"kind": "closed", "outstanding": 52}
    assert t["new_tokens"] == {"dist": "loguniform", "lo": 4096,
                               "hi": 12288}
    assert t["output_tokens"] == {"dist": "uniform", "lo": 256, "hi": 1024}
    assert (t["max_total_tokens"], t["turns"], t["tenants"], t["stratum"],
            t["check_sample"], t["trace_s"]) == (13312, 1, 0, 4, 5, 3)
    assert 15 <= t["ramp_s"] <= 30
    assert t["sampling"] == {"greedy": 2, "sampled": 1, "temperature": 0.8,
                             "top_k": 40} and "schedule_seed" in t
    from chipbench.generators import sessions
    plan = sessions.plan(t, 7, cell.config["vocab_size"], 30.0)
    turns = [s["turns"] for s in plan["sessions"]]
    assert all(len(ts) == 1 for ts in turns)        # nothing cut short
    total = [len(ts[0]["new"]) + ts[0]["max_new"] for ts in turns]
    assert max(total) <= 13312 <= cell.config["serving"]["cache_len"]
    # the first wave: 48 prompts of about 355k tokens in all
    first = sum(len(ts[0]["new"]) for ts in turns[:48])
    assert 340_000 < first < 370_000
    # what every slot may come to hold fits the pool
    sv = cell.config["serving"]
    assert sv["slots"] * max(total) <= sv["num_blocks"] * sv["block_size"]
    # the same lengths in the same order for another seed
    other = sessions.plan(t, 8, cell.config["vocab_size"], 30.0)
    assert [len(s["turns"][0]["new"]) for s in other["sessions"]] \
        == [len(ts[0]["new"]) for ts in turns]


def test_work_by_hand(config):
    d = glm47f_work.dims(config)
    assert (d["L_dense"], d["L_sparse"], d["held"], d["Fs"]) \
        == (1, 4, 64, 1536)
    mixer = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 \
        + 20 * 256 * 2048
    assert glm47f_work.mixer_params(d) == mixer == 21757952
    assert glm47f_work.expert_params(d) == 3 * 2048 * 1536 == 9437184
    dense = 5 * mixer + 3 * 2048 * 10240 \
        + 4 * (2048 * 64 + 3 * 2048 * 1536)
    assert glm47f_work.dense_params(d) == dense
    # one decoded token at context 8000 with its 16 assignments
    assert glm47f_work.stretch_flops(d, 1, 8000, 16, 1) == pytest.approx(
        2 * dense + 2 * 9437184 * 16 + 2 * 5 * 20 * 512 * 8000
        + 2 * 154880 * 2048)
    assert glm47f_work.latent_bytes_per_token(d) == 5 * 576 * 2 == 5760
    two = 5 * mixer + 3 * 2048 * 10240 + 4 * 9437184 + 154880 * 2048
    four = 4 * (2048 * 64 + 64) + 5 * (2 * 2048 + 768 + 512) + 2048
    assert glm47f_work.step_weight_bytes(d) == 2 * two + 4 * four
    # the configuration's weights: the issue's 6.52 GB
    total = 2 * two + 2 * 154880 * 2048 + 2 * 4 * 64 * 9437184 + 4 * four
    assert 6.51e9 < total < 6.53e9


def hand_ctx(config, **counters):
    recs = [{"arrive": 10.0, "latency_ms": 10000.0, "ttft_ms": 0.0,
             "n_prompt": 8000, "n_out": 1001}]      # a token every 10 ms
    return {"dims": glm47f_work.dims(config), "records": recs,
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
    ctx = hand_ctx(config, engine_moe_assignments_total=1600.0,
                   engine_moe_decode_experts_hit_total=400 * 61.0,
                   engine_moe_decode_layer_calls_total=400.0,
                   engine_decode_live_rows_total=100 * 48 * 8000.0,
                   engine_decode_read_rows_total=100 * 48 * 14336.0)
    d = ctx["dims"]
    # 100 tokens decoded in [2, 3): tokens 200..299, context 8200..8299
    keys = sum(8000 + i for i in range(200, 300))
    f = glm47f_work.stretch_flops(d, 100, keys, 1600, 100)
    assert read("serve_mfu_pct.glm47f", ctx) == pytest.approx(
        100 * f / 197e12)
    byts = 100 * glm47f_work.step_weight_bytes(d) \
        + 2 * 9437184 * 400 * 61 + 5760 * keys
    share = read("decode_hbm_roofline_pct.glm47f", ctx)
    assert share == pytest.approx(100 * byts / 819e9 / 0.8) and share < 100
    assert read("latent_live_read_pct", ctx) == pytest.approx(
        100 * 8000 / 14336)
    assert read("moe_experts_hit_pct", ctx) == pytest.approx(100 * 61 / 64)


def test_readers_find_nothing_on_a_program_without_the_counters(config):
    ctx = hand_ctx(config, engine_decode_steps_total=100.0)
    for name in ("serve_mfu_pct.glm47f", "decode_hbm_roofline_pct.glm47f",
                 "latent_live_read_pct", "moe_experts_hit_pct"):
        assert read(name, ctx) is None
    ctx["trace"]["modules"] = {}
    assert read("decode_hbm_roofline_pct.glm47f", ctx) is None


def test_a_program_without_the_skeleton_is_refused_before_any_export(
        monkeypatch, tmp_path):
    from chipbench.systems import glm47f_serving as system
    from paddle_tpu.models import transformer

    def old_config(**kw):
        raise TypeError("TransformerConfig.__init__() got an unexpected "
                        "keyword argument 'q_lora_rank'")

    monkeypatch.setattr(transformer, "TransformerConfig", old_config)
    cell = tiny_cell(CELL, "tiny-glm47f", "tiny-long-ctx-gen", LIMITS)
    t = time.time()
    with pytest.raises(SystemExit, match="cannot state"):
        system.build(cell, 1, str(tmp_path))
    assert time.time() - t < 5 and not list(tmp_path.iterdir())


def test_the_weights_follow_the_programs_tree(config):
    """``weight_specs`` against ``models/latent_moe.init_params``: the
    same leaves, shapes and dtypes (no prediction module)."""
    import jax
    from chipbench.systems import glm47f_serving as system
    from paddle_tpu.models import latent_moe
    tiny = harness.load_json(
        harness.ROOT + "/tests/chipbench/configs/tiny-glm47f.json")
    for cfg in (tiny, config):
        pcfg = system.program_config(cfg)
        assert pcfg.mtp_layers == 0 and pcfg.moe_route_scale == 1.8
        want = jax.eval_shape(lambda: latent_moe.init_params(
            jax.random.PRNGKey(0), pcfg, dtype=pcfg.dtype))
        specs = system.weight_specs(cfg)
        got = jax.tree_util.tree_map(
            lambda t: (tuple(t[0]), t[1]), specs,
            is_leaf=lambda x: isinstance(x, tuple))
        assert got == jax.tree_util.tree_map(
            lambda a: (a.shape, a.dtype.name), want)


# -- the tiny cell end to end -------------------------------------------------

@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_glm47f_work")


def run_cell(cell, capsys, work, seed, **kw):
    from chipbench.systems import glm47f_serving as system
    rc = system.run(cell, seed=seed, seconds=2.0, trace=False,
                    device=harness.device_info(), t_start=time.time(),
                    work=str(work), **kw)
    cap = capsys.readouterr()
    assert rc == 0
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err


def test_tiny_cell_runs_and_is_correct(capsys, work):
    cell = tiny_cell(CELL, "tiny-glm47f", "tiny-long-ctx-gen", LIMITS)
    doc, err = run_cell(cell, capsys, work, 2 ** 31 + 12)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"serve_tok_s", "setup_s"}
    assert doc["metrics"]["serve_tok_s"]["value"] > 0
    assert doc["notes"]["tokens_compared"] >= 40
    assert doc["notes"]["moe_assignments_in_window"] > 0
    # one latent row a token and layer, in whole lanes, float32 here
    assert doc["notes"]["state"]["kv_bytes_per_token"] == 3 * 128 * 4
    assert doc["notes"]["state"]["kv_pool_bytes"] == 3 * 128 * 4 * 64 * 8
    assert doc["notes"]["last_answer_after_close_s"] < 60
    assert "compared gap_max" in err


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, work):
    cell = tiny_cell(CELL, "tiny-glm47f", "tiny-long-ctx-gen", LIMITS)

    def break_engine(eng):
        inner = eng._decode_fn

        def altered(*a):
            out, cache = inner(*a)
            return out.at[:eng.batch].set((out[:eng.batch] + 1) % 256), cache

        eng._decode_fn = altered

    doc, err = run_cell(cell, capsys, work, 13, break_engine=break_engine)
    assert doc["correct"] is False
    assert doc["compared"]["gap_max"]["ok"] is False and "NOT OK" in err


def test_the_tool_reads_program_controls_and_faults_each_to_its_verdict(
        work):
    """``tools/limits_glm47f.py``'s readings of the tiny cell."""
    import argparse
    from chipbench.references import glm4_moe_lite as ref
    from chipbench.systems import glm47f_serving as system
    mine = harness.load_module(
        harness.HERE + "/tools/limits_glm47f.py", "limits_glm47f")
    tool = harness.load_module(
        harness.HERE + "/tools/limits_q3next.py", "limits_q3next")
    limits = harness.load_module(harness.HERE + "/tools/limits.py", "limits")
    cell = tiny_cell(CELL, "tiny-glm47f", "tiny-long-ctx-gen", LIMITS)
    args = argparse.Namespace(workload=CELL, seconds=3.0, budget_s=600.0,
                              controls=1, faults=0)
    out = {"rows": [], "faults": [], "verdicts": []}
    tool.save = lambda *a: None             # nothing under chiprun_out/
    tool.collect(mine.WithFaults(system), cell, args, out, [11, 13],
                 str(work), limits.judged)
    a, b = out["rows"]
    for row in (a, b):
        assert row["tokens_compared"] >= 100 == 100 * row["judged"]["ok"]
        assert row["gap_mean"] <= row["gap_max"] <= LIMITS["gap_max"]["limit"]
    for c in ("fp8", "int8") + ref.FAULTS:  # on the first seed only
        assert f"control_{c}" not in b
        # a bias of 0.02 moves an expert's weight by a few per cent:
        # taking the weights from s + b shows in the widest gap alone
        which = "gap_max" if c == "bias_in_weights" else "gap_mean"
        assert a[f"control_{c}"][which] > LIMITS[which]["limit"], c
        assert a[f"control_{c}_judged"]["ok"] is False, c
    assert all(ok == sound for _, sound, ok in out["verdicts"])
    assert len(out["verdicts"]) == 2 + 2 + len(ref.FAULTS)
    assert out["faults"] == []

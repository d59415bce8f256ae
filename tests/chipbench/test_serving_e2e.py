"""The serving cells end to end at a tiny size on the CPU: the run past
the harness's look for a chip, its result line, the control, and the
planted fault that has to come out as not correct."""

import json
import time

import pytest

from conftest import tiny_cell

# tiny-size readings (CPU, seeds 11, 12, 13; XLA path, bf16; ~300 served
# tokens each): the program's widest gap 0.024 - 0.046, the fp8
# control's 0.88 - 1.84
LIMITS = {"gap_max": {"limit": 0.2}, "unanswered": {"limit": 0}}


def run_cell(cell, capsys, work, seed, **kw):
    from chipbench import harness
    from chipbench.systems import lm_serving
    rc = lm_serving.run(cell, seed=seed, seconds=1.5, trace=False,
                        device=harness.device_info(), t_start=time.time(),
                        work=str(work), **kw)
    cap = capsys.readouterr()
    assert rc == 0
    return json.loads(cap.out.strip().splitlines()[-1]), cap.err


@pytest.fixture(scope="module")
def work(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_work")


def test_batch_cell_runs_and_is_correct(capsys, work):
    cell = tiny_cell("cgpt1.3b.batch-gen-standin", "tiny-gpt", "tiny-batch", LIMITS)
    doc, err = run_cell(cell, capsys, work, 2 ** 31 + 11)
    assert doc["correct"] is True and doc["failed"] == 0
    assert set(doc["metrics"]) == {"serve_tok_s", "setup_s"}
    assert doc["metrics"]["serve_tok_s"]["value"] > 0
    assert doc["metrics"]["serve_tok_s"]["unit"] == "tokens/s"
    assert list(doc)[-1] == "compared"
    assert set(doc["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert doc["compared"]["gap_max"]["limit"] == 0.2
    assert doc["notes"]["tokens_compared"] >= 20
    last = [l for l in err.strip().splitlines()][-2:]
    assert all(l.startswith("compared ") and " limit " in l for l in last)
    # the benchmark's token count agrees with the engine's own counter
    theirs = doc["notes"]["engine_tokens_in_window"] / 1.5
    assert abs(doc["metrics"]["serve_tok_s"]["value"] - theirs) \
        < 0.1 * theirs


def test_sessions_cell_reports_tails_and_is_correct(capsys, work):
    cell = tiny_cell("cgpt1.3b.sessions", "tiny-gpt", "tiny-sessions",
                     LIMITS, end_to_end=("ttft_p95_ms", "tpot_p95_ms"))
    doc, _ = run_cell(cell, capsys, work, 12)
    assert doc["correct"] is True
    assert set(doc["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert doc["metrics"]["ttft_p95_ms"]["value"] > 0


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, work):
    cell = tiny_cell("cgpt1.3b.batch-gen-standin", "tiny-gpt", "tiny-batch", LIMITS)

    def break_engine(eng):
        inner = eng._decode_fn

        def altered(*a):
            nxt, cache = inner(*a)
            return (nxt + 1) % 128, cache

        eng._decode_fn = altered

    doc, err = run_cell(cell, capsys, work, 13, break_engine=break_engine)
    assert doc["correct"] is False
    assert doc["compared"]["gap_max"]["ok"] is False
    assert doc["compared"]["gap_max"]["value"] > 0.5
    assert "NOT OK" in err


def test_the_control_fails_the_limit_the_program_passes(work):
    """The reference in fp8, put in the program's place, on three
    seeds: each reads over the limit and three times the program's."""
    from chipbench.systems import lm_serving
    cell = tiny_cell("cgpt1.3b.batch-gen-standin", "tiny-gpt", "tiny-batch", LIMITS)
    srv, eng, _ = lm_serving.build(cell, 11, str(work))
    from chipbench import flops, weights
    for seed in (11, 12, 13):
        eng.params = weights.lm_weights(seed, flops.lm_dims(cell.config))
        box = lm_serving.drive(cell, eng, seed, 4.0)    # long enough for
        got = lm_serving.check(cell, seed, box["final"], control="fp8")
        assert got["tokens_compared"] >= 100     # a loaded test machine
        assert got["gap_max"] <= LIMITS["gap_max"]["limit"]
        assert got["control_gap_max"] > LIMITS["gap_max"]["limit"]
        assert got["control_gap_max"] >= 3 * got["gap_max"]

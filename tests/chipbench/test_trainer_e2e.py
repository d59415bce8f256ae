"""The training cell end to end on the CPU (ResNet-50's own graph, batch
2, float32), built by the function the chip cell is built by: a sound
run is correct; the control and each fault the cell can have come out
as not correct, on the numbers the committed cell compares."""

import contextlib
import io
import json
import time

import pytest

from conftest import tiny_cell

from chipbench import harness

# ResNet-50's own graph compiles once in a run of the cell and once in
# the reference (three quarters of a minute together on eight idle
# cores). One run stays in tier-1: the cell driven end to end with the
# timed path broken underneath. The others carry the slow marker: five
# such compiles under tier-1's six workers starved the 8-device CPU
# collectives of tests/test_moe_pipeline.py into aborting their worker.
#   python -m pytest tests/chipbench/test_trainer_e2e.py -m slow
slow = pytest.mark.slow

# The numbers compared are the committed cell's own
# (chipbench/limits/resnet50.train-b256.json), at limits read at this
# size (CPU, float32, batch 2, lr 5e-4, seed 2**31+21):
#               grad_diff_rel_fc  grad/change median   grad/change worst
#   program     2.1e-6            3.4e-5 / 5.3e-5      0.0007 / 0.0009
#   fp8 control 0.065             0.011  / 0.0097      0.096  / 0.141
#   int8        0.022             0.0084 / 0.0089      0.77   / 0.80
#   half batch  0.995             0.080  / 0.072       0.40   / 0.64
#   state unchanged: 1 by the worst leaf and as a vector
LIMITS = {"grad_diff_rel_fc": {"limit": 0.005},
          "grad_norm_gap_median": {"limit": 0.003},
          "change_norm_gap_median": {"limit": 0.003},
          "grad_norm_gap": {"limit": 0.02},
          "change_norm_gap": {"limit": 0.02}}
SEED = 2 ** 31 + 21


def cell():
    return tiny_cell("resnet50.train-b256", "small-resnet", "small-train",
                     LIMITS)


def run(**kw):
    from chipbench.systems import trainer as tsys
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = tsys.run(cell(), seed=SEED, seconds=1.0, trace=False,
                      device=harness.device_info(), t_start=time.time(),
                      **kw)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_numbers_compared_are_the_committed_cells():
    assert set(LIMITS) == set(harness.Cell("resnet50.train-b256").limits)


@slow
def test_a_sound_run_is_correct():
    doc = run()
    assert doc["correct"] is True, doc["compared"]
    assert set(doc["metrics"]) == {"train_step_ms", "setup_s"}
    assert doc["metrics"]["train_step_ms"]["value"] > 0
    assert list(doc)[-1] == "compared"
    assert set(doc["compared"]) == set(LIMITS)
    assert len(doc["notes"]["losses"]) == 3


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    import jax.numpy as jnp
    from chipbench import weights
    from chipbench.references import resnet50 as ref

    def break_step(trainer, n):
        w0 = weights.resnet_weights(SEED, ref.leaf_shapes(10))
        trainer.parameters.values = dict(w0)
        trainer.opt_state = {k: jnp.zeros_like(v) for k, v in w0.items()}

    doc = run(break_step=break_step)
    assert doc["correct"] is False
    assert set(doc["compared"]) == set(LIMITS)
    assert not any(c["ok"] for c in doc["compared"].values())
    for name in ("grad_norm_gap", "change_norm_gap", "grad_diff_rel_fc"):
        assert doc["compared"][name]["value"] == pytest.approx(1.0), name


@slow
def test_half_of_the_batch_left_out_is_not_correct():
    doc = run(break_feed="half")
    assert doc["correct"] is False
    bad = [k for k, c in doc["compared"].items() if not c["ok"]]
    assert bad, doc["compared"]


@slow
def test_the_control_and_the_planted_fault_fail_a_number():
    """The reference in fp8, and the reference with half the batch left
    out, put in the program's place against the reference proper."""
    from chipbench import compare
    from chipbench.generators import batches
    from chipbench.references import resnet50 as ref
    from chipbench.systems import trainer as tsys
    c = cell()
    shapes = ref.leaf_shapes(10)
    rows, labels = batches.pool(c.traffic, SEED, 2, 3 * 224 * 224, 10)
    fb = [batches.batch_of(rows, labels, 2, k) for k in range(3)]
    sound = tsys.reference_readings(c, SEED, shapes, fb)
    for kw in ({"fmt": "fp8"}, {"drop_half": True}):
        bad = tsys.reference_readings(c, SEED, shapes, fb, **kw)
        judged = compare.judge(tsys.readings_gap(bad, sound), LIMITS)
        assert not judged["grad_diff_rel_fc"]["ok"], (kw, judged)
        assert not judged["grad_norm_gap"]["ok"], (kw, judged)

"""What the system modules decide before anything runs: which exported
artifact a serving run may reuse, and which graph a training
configuration builds."""

import os

import pytest

from chipbench import flops, harness


def test_an_edited_program_source_never_reuses_the_exported_artifact(
        tmp_path):
    from chipbench.systems import lm_serving
    pkg = tmp_path / "paddle_tpu"
    (pkg / "models").mkdir(parents=True)
    (pkg / "models" / "transformer.py").write_text("A = 1\n")
    (pkg / "sampling.py").write_text("B = 2\n")
    (pkg / "notes.txt").write_text("not source\n")
    h0 = lm_serving.program_sources_hash(str(pkg))
    assert h0 == lm_serving.program_sources_hash(str(pkg))
    (pkg / "notes.txt").write_text("still not source\n")
    assert lm_serving.program_sources_hash(str(pkg)) == h0
    (pkg / "models" / "transformer.py").write_text("A = 2\n")
    h1 = lm_serving.program_sources_hash(str(pkg))
    assert h1 != h0
    (pkg / "ops.py").write_text("")
    assert lm_serving.program_sources_hash(str(pkg)) not in (h0, h1)


def test_the_artifact_key_follows_the_programs_source(monkeypatch):
    from chipbench.systems import lm_serving
    cell = harness.Cell("cgpt1.3b.batch-gen-standin")
    a = lm_serving.artifact_path(cell, "/w")
    assert a == lm_serving.artifact_path(cell, "/w")
    assert os.path.dirname(a) == "/w/cerebras-gpt-1.3b-standin"
    monkeypatch.setattr(lm_serving, "program_sources_hash",
                        lambda root=None: "an edited tree")
    assert lm_serving.artifact_path(cell, "/w") != a


def resnet_config(**over):
    cfg = harness.load_json(harness.HERE + "/configs/resnet-50.json")
    cfg.update(over)
    return cfg


def test_the_training_graph_is_built_from_the_configurations_own_keys():
    """One builder for every trainer configuration: depth, classes and
    image side are read, so the tests' small cell and the chip's cell
    run the same function."""
    import paddle_tpu as paddle
    from chipbench.references import resnet50 as ref
    from chipbench.systems import trainer as tsys
    for classes in (1000, 10):
        cfg = resnet_config(classes=classes)
        params = paddle.parameters.create(tsys.cost_of(cfg))
        have = {k: tuple(v.shape) for k, v in params.values.items()}
        assert have == ref.leaf_shapes(classes, ref.stages_of(cfg))
        assert have["res_fc.w"] == (2048, classes)


def test_a_depth_that_its_stage_blocks_do_not_state_is_refused():
    """A 224/1000 configuration of another depth is not silently run as
    ResNet-50: the program's leaves (from ``depth``) have to equal the
    reference's (from ``stage_blocks``)."""
    from chipbench.systems import trainer as tsys
    cell = harness.Cell("resnet50.train-b256")
    cell.config = resnet_config(depth=101, compute_dtype="float32")
    with pytest.raises(SystemExit) as e:
        tsys.build(cell, 5, False)
    assert "depth 101" in str(e.value) and "stage_blocks" in str(e.value)
    cell.config = resnet_config(bottleneck_expansion=1,
                                compute_dtype="float32")
    with pytest.raises(SystemExit):
        tsys.build(cell, 5, False)


def test_the_flop_count_follows_the_configurations_stages():
    r50 = flops.resnet_train_flops(2)
    assert r50 == flops.resnet_train_flops(2, 224, 1000, [3, 4, 6, 3],
                                           [64, 128, 256, 512])
    r101 = flops.resnet_train_flops(2, 224, 1000, [3, 4, 23, 3],
                                    [64, 128, 256, 512])
    assert 1.8 < r101 / r50 < 2.0       # 7.6e9 against 3.8e9 multiply-adds


def test_the_step_note_tells_a_loop_of_period_two_from_a_steady_one():
    import numpy as np
    from chipbench.systems import trainer
    ends = np.cumsum([0.0] + [0.140, 0.263] * 10)
    scopes = np.cumsum([[0.0] * 5] + [[0.029, 0.029, 0, 0.002, 0.103],
                                      [0.152, 0.152, 0, 0.002, 0.103]] * 10,
                       axis=0)
    note = trainer.step_spread(ends, scopes)
    assert note["step"]["even_mean"] == pytest.approx(140.0)
    assert note["step"]["odd_mean"] == pytest.approx(263.0)
    assert note["feed"]["odd_mean"] == pytest.approx(152.0)
    assert note["host_sync"]["p10"] == pytest.approx(note["host_sync"]["p90"])
    assert "feed/transfer" not in note      # a scope that never ran is left out

"""The cells' programs at their real sizes, compiled for a TPU v5e by
the real compiler with no chip attached: each fits one chip, and the
bytes are on record for the memory reckoning in ``PERF.md``.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and every xdist worker imports this
file.

All of it carries the slow marker: the four 1.3B programs and the
ResNet-50 step are minutes of compiling on every core, and under
tier-1's six workers that load made load-sensitive tests of the seed
fail (PR 23's first whole run: 16 failures against the seed's 9).
    python -m pytest tests/chipbench/test_aot_sizes.py -m slow
"""

import json

import pytest

pytestmark = pytest.mark.slow

HBM = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def device():
    from paddle_tpu.ops.pallas import aot
    try:
        return aot.topology_device()
    except Exception as e:  # noqa: BLE001 — no topology = cannot test
        pytest.skip(f"libtpu cannot describe a {aot.TOPOLOGY} topology "
                    f"on this host: {type(e).__name__}: {e}")


def footprint(compiled) -> dict:
    m = compiled.memory_analysis()
    doc = {"arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "aliased": m.alias_size_in_bytes}
    doc["total"] = (doc["arguments"] + doc["outputs"] + doc["temporaries"]
                    - doc["aliased"])
    return doc


@pytest.fixture(scope="module")
def serving_programs():
    from chipbench import harness
    from chipbench.systems import lm_serving
    from paddle_tpu.ops.pallas import aot
    cfg = harness.load_json(harness.HERE + "/configs/cerebras-gpt-1.3b-standin.json")
    sv = cfg["serving"]
    assert sv["num_blocks"] == sv["slots"] * sv["cache_len"] \
        // sv["block_size"]          # what aot.engine_programs assumes
    programs, _ = aot.engine_programs(
        lm_serving.transformer_config(cfg), batch=sv["slots"],
        cache_len=sv["cache_len"], block_size=sv["block_size"],
        chunk_tokens=max(sv["chunk_buckets"]))
    return programs


@pytest.mark.parametrize("which", [0, 1, 2, 3])
def test_serving_program_fits_one_chip(device, serving_programs, which,
                                       record_property):
    from paddle_tpu.ops.pallas import aot
    name = sorted(serving_programs)[which]
    fn, args = serving_programs[name]
    doc = footprint(aot.compile_for(device, fn, *args))
    record_property(name, json.dumps(doc))
    print(name, doc)
    # fp32 weights 5.26 GB + the bf16 pool 3.22 GB are arguments
    assert 8.3e9 < doc["arguments"] < 8.8e9
    assert doc["total"] < HBM


def test_resnet_step_fits_one_chip(device, record_property):
    """The trainer's own jitted step at batch 256, bf16 compute, on the
    graph the cell's own builder makes from the committed configuration."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from chipbench import harness
    from chipbench.systems import trainer as tsys
    from paddle_tpu.ops.pallas import aot
    from paddle_tpu.utils.flags import GLOBAL_FLAGS
    from paddle_tpu.utils.rng import global_key_source
    before = GLOBAL_FLAGS.get("compute_dtype")
    GLOBAL_FLAGS.set("compute_dtype", "bfloat16")
    try:
        cfg = harness.load_json(harness.HERE + "/configs/resnet-50.json")
        cost = tsys.cost_of(cfg)
        params = paddle.parameters.create(cost)
        opt = cfg["optimizer"]
        trainer = paddle.trainer.SGD(
            cost=cost, parameters=params,
            update_equation=paddle.optimizer.Momentum(
                momentum=opt["momentum"],
                learning_rate=opt["learning_rate"]))
        row = np.zeros(3 * cfg["image_size"] ** 2, np.float32)
        feeds = trainer._feeder(None).feed([(row, 0)] * cfg["batch_size"])
        args = (trainer.parameters.values, trainer.opt_state,
                trainer.parameters.state, feeds,
                jax.numpy.asarray(0, jax.numpy.int32),
                global_key_source().step("dropout", 0))
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        doc = footprint(aot.compile_for(
            device, trainer._plain_train_step, *abstract))
    finally:
        GLOBAL_FLAGS.set("compute_dtype", before)
    record_property("resnet50_b256_step", json.dumps(doc))
    print("resnet50_b256_step", doc)
    assert doc["total"] < HBM
    assert doc["total"] > 0.125 * 16e9      # the cell is not a toy

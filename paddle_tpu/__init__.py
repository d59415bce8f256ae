"""paddle_tpu — a TPU-native deep learning framework.

A from-scratch rebuild of the capability surface of 2017-era PaddlePaddle
(reference: wanghaox/Paddle) designed idiomatically for TPU hardware:

- traced pure-function programs compiled by XLA (replaces the ModelConfig /
  ProgramDesc protobuf graphs executed by GradientMachine / Executor,
  reference: paddle/gserver/gradientmachines/, paddle/framework/executor.cc)
- in-graph XLA collectives over ICI/DCN via ``jax.sharding`` meshes
  (replaces the C++/Go parameter servers, reference: paddle/pserver/, go/pserver/)
- ``lax.scan`` / masked segment kernels for variable-length sequences
  (replaces LoDTensor / Argument.sequenceStartPositions,
  reference: paddle/framework/lod_tensor.h:82, paddle/parameter/Argument.h:84)
- Pallas kernels where XLA fusion is insufficient (replaces the hand-written
  CUDA in paddle/cuda/src/).

Public API mirrors the v2 Python API (reference: python/paddle/v2/__init__.py):

    import paddle_tpu as paddle
    img  = paddle.layer.data(name="pixel", type=paddle.data_type.dense_vector(784))
    fc   = paddle.layer.fc(input=img, size=10, act=paddle.activation.Softmax())
    cost = paddle.layer.classification_cost(input=fc, label=lbl)
    params  = paddle.parameters.create(cost)
    trainer = paddle.trainer.SGD(cost=cost, parameters=params,
                                 update_equation=paddle.optimizer.Momentum(...))
    trainer.train(reader=..., event_handler=...)
"""

import importlib

from paddle_tpu.version import __version__

# Submodules exposed lazily (PEP 562) so partial builds stay importable and
# `import paddle_tpu` stays fast.
_SUBMODULES = (
    "utils", "core", "ops", "layer", "activation", "attr", "data_type",
    "initializer", "networks", "optimizer", "parameters", "pooling",
    "topology", "trainer", "event", "reader", "dataset", "inference",
    "evaluator", "parallel", "models", "io", "runtime", "recurrent",
    "projection", "image", "plot", "distributed", "observe", "pipeline",
)


def __getattr__(name):
    if name in _SUBMODULES:
        mod = importlib.import_module(f"paddle_tpu.{name}")
        globals()[name] = mod
        return mod
    if name == "infer":
        from paddle_tpu.inference import infer
        return infer
    if name == "batch":
        from paddle_tpu.reader.minibatch import batch
        return batch
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES) + ["infer", "batch"])


# historical flag names (paddle/utils/Flags.cpp) mapped to their TPU-native
# equivalents for v2-API source compatibility
_LEGACY_FLAG_ALIASES = {"use_gpu": "use_tpu"}


def init(**kwargs):
    """Global initialisation (reference: paddle.init / initMain,
    paddle/utils/Flags.cpp, python/paddle/v2/__init__.py:123).

    Accepts the historical flags (use_gpu, trainer_count, ...) for source
    compatibility; aliased names map onto their TPU equivalents, other
    unknown flags are ignored as the reference's init did. The JAX
    platform is chosen the JAX way: the ``JAX_PLATFORMS`` env var.
    """
    from paddle_tpu.utils import flags as _flags
    from paddle_tpu.utils import rng as _rng
    for k, v in kwargs.items():
        _flags.GLOBAL_FLAGS.set_if_known(_LEGACY_FLAG_ALIASES.get(k, k), v)
    if kwargs.get("seed"):
        _rng.reset_global_seed(int(kwargs["seed"]))
    # FP-exception tripwires (reference: feenableexcept(FE_INVALID|
    # FE_DIVBYZERO|FE_OVERFLOW), paddle/trainer/TrainerMain.cpp:49) — the XLA
    # equivalent re-runs jitted computations op-by-op on a non-finite result
    # and raises at the producing op.
    if _flags.GLOBAL_FLAGS.get("debug_nans") or \
            _flags.GLOBAL_FLAGS.get("debug_infs"):
        import jax
        if _flags.GLOBAL_FLAGS.get("debug_nans"):
            jax.config.update("jax_debug_nans", True)
        if _flags.GLOBAL_FLAGS.get("debug_infs"):
            jax.config.update("jax_debug_infs", True)
    return _flags.GLOBAL_FLAGS

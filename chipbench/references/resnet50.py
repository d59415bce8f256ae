"""Plain reference: the bottleneck ResNet v1 of He et al. 2015, table 1
(ResNet-50 unless the configuration's ``stage_blocks`` say otherwise),
float32.

Forward, softmax cross-entropy, gradients by ``jax.grad`` and the
heavy-ball momentum update, in straightforward ``jax.numpy``: NHWC
convolutions at ``highest`` precision, training-mode batch norm (biased
batch variance, eps 1e-5), bottleneck blocks with the stride on the
first 1x1 (the original v1 placement, which the program's recipe keeps),
3x3/2 max pool, global average pool, a biased classifier. The stem is
the plain 7x7/2 convolution: the program's space-to-depth stem computes
the same function. Nothing is imported from the program.

Each bottleneck is wrapped in ``jax.checkpoint`` so that batch 256 in
float32 fits one chip; that recomputes and changes no value.

``precision`` 'f32' is the reference proper; 'fp8' / 'int8' are the
controls, the recipe a later PR would be tempted by: both operands of
every convolution rounded to the lower format forward (e4m3 / int8,
per-image and per-channel scales) and the incoming gradient rounded
backward (e5m2 / int8), as fp8 training does; the classifier's operands
rounded with straight-through gradients.
"""

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
EPS = 1e-5


def stages_of(config: dict) -> tuple:
    """((blocks, width), ...) from the configuration's ``stage_blocks``
    and ``stage_widths``; the reference is the bottleneck net of the
    paper's table 1 and says so where the configuration asks for
    another."""
    if int(config.get("bottleneck_expansion", 4)) != 4:
        raise SystemExit("chipbench: the plain reference is the "
                         "bottleneck ResNet (expansion 4); the "
                         f"configuration states "
                         f"{config['bottleneck_expansion']}")
    return tuple((int(n), int(c)) for n, c in
                 zip(config["stage_blocks"], config["stage_widths"]))


def leaf_shapes(classes: int = 1000, stages: tuple = STAGES) -> dict:
    """name -> shape of every trainable leaf, in the names the v2 layer
    graph gives them (the interchange format between benchmark, program
    and reference)."""
    out = {}

    def conv_bn(name, k, cin, cout):
        out[f"{name}_conv.w"] = (k, k, cin, cout)
        out[f"{name}_bn.gamma"] = (cout,)
        out[f"{name}_bn.beta"] = (cout,)

    conv_bn("res_conv1", 7, 3, 64)
    cin = 64
    for stage, (n, c) in enumerate(stages):
        for i in range(n):
            name = f"res{stage + 2}_{i}"
            stride = 2 if (i == 0 and stage > 0) else 1
            if cin != 4 * c or stride != 1:
                conv_bn(f"{name}_proj", 1, cin, 4 * c)
            conv_bn(f"{name}_a", 1, cin, c)
            conv_bn(f"{name}_b", 3, c, c)
            conv_bn(f"{name}_c", 1, c, 4 * c)
            cin = 4 * c
    out["res_fc.w"] = (cin, classes)
    out["res_fc.b"] = (classes,)
    return out


def _ste_round(x, fmt, axes):
    if fmt == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if fmt == "fp8":
        s = amax / 448.0
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    elif fmt == "int8":
        s = amax / 127.0
        q = jnp.clip(jnp.round(x / s), -127, 127) * s
    else:
        raise ValueError(f"unknown precision {fmt!r}")
    return x + jax.lax.stop_gradient(q - x)


def _plain_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


def _round(x, fmt, axes, grad=False):
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    amax = jnp.where(amax > 0, amax, 1.0)
    if fmt == "fp8":
        dt, top = (jnp.float8_e5m2, 57344.0) if grad \
            else (jnp.float8_e4m3fn, 448.0)
        s = amax / top
        return (x / s).astype(dt).astype(jnp.float32) * s
    s = amax / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _low_conv(x, w, stride, pad, fmt):
    return _plain_conv(_round(x, fmt, (1, 2, 3)), _round(w, fmt, (0, 1, 2)),
                       stride, pad)


def _low_conv_fwd(x, w, stride, pad, fmt):
    xq, wq = _round(x, fmt, (1, 2, 3)), _round(w, fmt, (0, 1, 2))
    return _plain_conv(xq, wq, stride, pad), (xq, wq)


def _low_conv_bwd(stride, pad, fmt, res, dy):
    xq, wq = res
    _, vjp = jax.vjp(lambda a, b: _plain_conv(a, b, stride, pad), xq, wq)
    return vjp(_round(dy, fmt, (1, 2, 3), grad=True))


_low_conv.defvjp(_low_conv_fwd, _low_conv_bwd)


def _conv(x, w, stride, pad, fmt):
    if fmt == "f32":
        return _plain_conv(x, w, stride, pad)
    if fmt not in ("fp8", "int8"):
        raise ValueError(f"unknown precision {fmt!r}")
    return _low_conv(x, w, stride, pad, fmt)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * gamma + beta


def _conv_bn(w, name, x, stride, pad, fmt, relu):
    y = _bn(_conv(x, w[f"{name}_conv.w"], stride, pad, fmt),
            w[f"{name}_bn.gamma"], w[f"{name}_bn.beta"])
    return jax.nn.relu(y) if relu else y


def _bottleneck(w, name, x, stride, project, fmt):
    short = _conv_bn(w, f"{name}_proj", x, stride, 0, fmt, False) \
        if project else x
    y = _conv_bn(w, f"{name}_a", x, stride, 0, fmt, True)
    y = _conv_bn(w, f"{name}_b", y, 1, 1, fmt, True)
    y = _conv_bn(w, f"{name}_c", y, 1, 0, fmt, False)
    return jax.nn.relu(y + short)


def _block_at(name, stride, project, fmt, w, x):
    return _bottleneck(w, name, x, stride, project, fmt)


def loss(w: dict, rows, labels, fmt: str = "f32", stages: tuple = STAGES):
    """Mean softmax cross-entropy of a batch of flat CHW float rows."""
    n = rows.shape[0]
    side = int(round((rows.shape[1] // 3) ** 0.5))
    x = rows.reshape(n, 3, side, side).transpose(0, 2, 3, 1)
    x = _conv_bn(w, "res_conv1", x, 2, 3, fmt, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    cin = 64
    for stage, (nb, c) in enumerate(stages):
        for i in range(nb):
            name = f"res{stage + 2}_{i}"
            stride = 2 if (i == 0 and stage > 0) else 1
            project = cin != 4 * c or stride != 1
            keys = [k for k in w if k.startswith(name + "_")]
            block = jax.checkpoint(functools.partial(
                _block_at, name, stride, project, fmt))
            x = block({k: w[k] for k in keys}, x)
            cin = 4 * c
    x = jnp.mean(x, axis=(1, 2))
    xq = _ste_round(x, fmt, (1,))
    wq = _ste_round(w["res_fc.w"], fmt, (0,))
    logits = jnp.matmul(xq, wq, precision=HI) + w["res_fc.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(logp[jnp.arange(n), labels])


@functools.partial(jax.jit, static_argnames=("fmt", "stages"),
                   donate_argnums=(0, 1))
def momentum_step(w, v, rows, labels, lr, mu, fmt="f32", stages=STAGES):
    """One step of heavy-ball momentum: v <- mu v + g, w <- w - lr v.
    Returns (loss, w, v, per-leaf gradient norms)."""
    lo, g = jax.value_and_grad(loss)(w, rows, labels, fmt, stages)
    v = {k: mu * v[k] + g[k] for k in w}
    w = {k: w[k] - lr * v[k] for k in w}
    return lo, w, v, g


def first_steps(w0: dict, batches, lr: float, mu: float, fmt: str = "f32",
                drop_half: bool = False, stages: tuple = STAGES):
    """Follow the first ``len(batches)`` steps from ``w0``. Returns the
    losses, the first gradient (its weight matrices, and every leaf's
    norm) and the norm of each leaf's change over the steps.
    ``drop_half`` plants the fault 'half of the batch left out, the mean
    taken over the rest'."""
    w = {k: jnp.array(v, jnp.float32, copy=True) for k, v in w0.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    losses, g1, g1_w = [], None, None
    for rows, labels in batches:
        if drop_half:
            rows, labels = rows[: rows.shape[0] // 2], \
                labels[: labels.shape[0] // 2]
        lo, w, v, g = momentum_step(w, v, jnp.asarray(rows),
                                    jnp.asarray(labels, jnp.int32),
                                    lr, mu, fmt=fmt, stages=stages)
        losses.append(float(lo))
        if g1 is None:
            g1 = leaf_norms(g)
            g1_w = weight_leaves(g)
        del g
    change = leaf_norms_of_change(w, w0)
    return {"losses": losses, "grad_norm": g1, "change_norm": change,
            "grad_weights": g1_w}


def weight_leaves(t: dict) -> dict:
    """The weight matrices (convolutions and the classifier) of a tree:
    the leaves whose gradient is a sum without built-in cancellation."""
    return {k: v for k, v in t.items() if k.endswith(".w")}


@jax.jit
def _diff_rel(a, b):
    num = sum(jnp.sum(jnp.square(a[k].astype(jnp.float32) - b[k]))
              for k in b)
    den = sum(jnp.sum(jnp.square(b[k])) for k in b)
    return jnp.sqrt(num / den)


def diff_rel(a: dict, b: dict, prefix: str = "") -> float:
    """||a - b|| / ||b|| over the leaves of ``b`` whose names start
    with ``prefix``, taken as one vector."""
    names = [k for k in b if k.startswith(prefix)]
    return float(_diff_rel({k: jnp.asarray(a[k]) for k in names},
                           {k: b[k] for k in names}))


@jax.jit
def _change(w, w0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        w[k].astype(jnp.float32) - w0[k].astype(jnp.float32)))) for k in w}


def leaf_norms_of_change(w, w0) -> dict:
    return {k: float(x) for k, x in _change(w, w0).items()}


@jax.jit
def _norms(t):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in t.items()}


def leaf_norms(t) -> dict:
    return {k: float(x) for k, x in _norms(t).items()}

"""Operations and bytes the algorithms need, from shapes.

Counted for the work the algorithm needs whatever implements it: 2 per
multiply-add, weights once per program execution at the configuration's
compute width, live K and V once, no padding, nothing recomputed.
"""


def lm_dims(config: dict) -> dict:
    """The GPT-2 configuration's own keys (Hugging Face ``config.json``)
    under the short names the arithmetic uses."""
    m = config
    return {"d_model": m["n_embd"], "n_heads": m["n_head"],
            "n_layers": m["n_layer"], "d_ff": m["n_inner"],
            "vocab": m["vocab_size"], "max_len": m["n_positions"],
            "eps": m["layer_norm_epsilon"]}


def lm_matmul_params(d: dict) -> int:
    """Weights that every token multiplies: 12 D^2 a layer for F = 4D
    (qkv 3D^2, out D^2, mlp 2DF)."""
    D, F, L = d["d_model"], d["d_ff"], d["n_layers"]
    return L * (4 * D * D + 2 * D * F)


def lm_token_flops(d: dict, context: float, with_head: bool = True) -> float:
    """Forward FLOPs of ONE token that attends over ``context`` keys:
    the linear maps, QK^T and PV over the context, and the tied head."""
    D, L, V = d["d_model"], d["n_layers"], d["vocab"]
    f = 2.0 * lm_matmul_params(d) + 4.0 * L * D * context
    if with_head:
        f += 2.0 * V * D
    return f


def lm_prefill_flops(d: dict, new_tokens: int, context_before: int) -> float:
    """Forward FLOPs of prefilling ``new_tokens`` after ``context_before``
    cached ones (causal: token i sees context_before + i + 1 keys); the
    head runs once, for the last position."""
    D, L, V = d["d_model"], d["n_layers"], d["vocab"]
    keys = new_tokens * context_before + new_tokens * (new_tokens + 1) / 2.0
    return (2.0 * lm_matmul_params(d) * new_tokens + 4.0 * L * D * keys
            + 2.0 * V * D)


def lm_weight_bytes(d: dict, width: int = 2) -> float:
    """Bytes of the weights one decode step has to read at the compute
    width: the blocks, the tied embedding (as the head) and the final
    norm; the position table and the embedding rows are a gather."""
    D, V = d["d_model"], d["vocab"]
    return width * (lm_matmul_params(d) + V * D) + 4.0 * d["n_layers"] * 4 * D


def lm_kv_bytes_per_token(d: dict, width: int = 2) -> float:
    """K and V of one cached token over all layers."""
    return 2.0 * d["n_layers"] * d["d_model"] * width


# -- ResNet-50 ----------------------------------------------------------------

RESNET50 = ((3, 4, 6, 3), (64, 128, 256, 512))


def resnet_convs(image: int = 224, blocks=RESNET50[0], widths=RESNET50[1]):
    """(k, cin, cout, out_h) of every convolution of a bottleneck ResNet
    v1 (He et al. 2015, table 1; ResNet-50 by default), stride on the
    1x1 that enters a stage as the program's recipe has it."""
    convs = [(7, 3, 64, image // 2)]
    h = image // 4
    cin = 64
    for stage, (n, c) in enumerate(zip(blocks, widths)):
        for i in range(n):
            stride = 2 if (i == 0 and stage > 0) else 1
            ho = h // stride
            if cin != 4 * c or stride != 1:
                convs.append((1, cin, 4 * c, ho))          # projection
            convs.append((1, cin, c, ho))
            convs.append((3, c, c, ho))
            convs.append((1, c, 4 * c, ho))
            cin, h = 4 * c, ho
    return convs


def resnet_train_flops(batch: int, image: int = 224, classes: int = 1000,
                       blocks=RESNET50[0], widths=RESNET50[1]) -> float:
    """Forward + backward FLOPs of one step: every convolution and the
    classifier cost 2 FLOPs a multiply-add forward and twice that
    backward (gradients to the input and to the weights); the first
    convolution needs no input gradient. BN, ReLU and pooling are not
    counted."""
    fwd = 0.0
    first = None
    for k, cin, cout, ho in resnet_convs(image, blocks, widths):
        f = 2.0 * k * k * cin * cout * ho * ho
        first = f if first is None else first
        fwd += f
    fc = 2.0 * 4 * widths[-1] * classes
    return batch * (3.0 * (fwd + fc) - first)

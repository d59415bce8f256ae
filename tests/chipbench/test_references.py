"""The plain references against the program at a tiny size, and the
controls against the references."""

import numpy as np
import pytest

DIMS = {"d_model": 32, "n_heads": 2, "n_layers": 2, "d_ff": 64,
        "vocab": 96, "max_len": 64, "eps": 1e-5}


@pytest.fixture(scope="module")
def lm():
    import jax.numpy as jnp
    from chipbench import weights
    from paddle_tpu.models import transformer
    w = weights.lm_weights(2 ** 31 + 77, DIMS)
    cfg = transformer.TransformerConfig(
        vocab=96, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
        dtype=jnp.float32)
    toks = np.random.RandomState(5).randint(0, 96, 40)
    return w, cfg, toks


def test_weights_are_a_pure_function_of_a_large_seed():
    from chipbench import weights
    a = weights.lm_weights(2 ** 31 + 77, DIMS)
    b = weights.lm_weights(2 ** 31 + 77, DIMS)
    c = weights.lm_weights(77, DIMS)
    assert np.array_equal(a["blocks"]["qkv"], b["blocks"]["qkv"])
    assert not np.array_equal(a["embed"], c["embed"])
    assert a["blocks"]["qkv"].shape == (2, 32, 96)
    assert abs(float(a["blocks"]["ln1"].mean()) - 1.0) < 0.05
    assert float(np.abs(a["blocks"]["ln1_b"]).max()) > 0.0


def test_lm_reference_agrees_with_the_programs_block_in_float32(lm):
    from chipbench.references import gpt2_nobias
    from paddle_tpu.models import transformer
    w, cfg, toks = lm
    prog = np.asarray(transformer.forward(w, toks[None], cfg))[0]
    rows = list(range(len(toks)))
    ref = np.asarray(gpt2_nobias.logits_at(w, toks, rows, n_heads=2,
                                           eps=1e-5))
    assert np.abs(prog - ref).max() < 2e-4 * np.abs(ref).max()
    # padding at the end changes no earlier row
    padded = np.concatenate([toks, np.zeros(24, toks.dtype)])
    ref2 = np.asarray(gpt2_nobias.logits_at(w, padded, rows, n_heads=2,
                                            eps=1e-5))
    assert np.abs(ref - ref2).max() < 1e-5


@pytest.mark.parametrize("fmt", ["fp8", "int8"])
def test_lm_controls_depart_from_the_reference(lm, fmt):
    from chipbench.references import gpt2_nobias
    w, _, toks = lm
    rows = list(range(len(toks)))
    ref = np.asarray(gpt2_nobias.logits_at(w, toks, rows, n_heads=2,
                                           eps=1e-5))
    low = np.asarray(gpt2_nobias.logits_at(w, toks, rows, n_heads=2,
                                           eps=1e-5, precision=fmt))
    rel = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < rel < 0.5


def test_served_gap_reads_zero_for_the_references_own_choice(lm):
    from chipbench import compare
    from chipbench.references import gpt2_nobias
    w, _, toks = lm
    prompt = [int(t) for t in toks[:10]]
    out = []
    for _ in range(6):                       # greedy decoding by hand
        seq = prompt + out
        lg = gpt2_nobias.logits_at(w, seq, [len(seq) - 1], n_heads=2,
                                   eps=1e-5)
        out.append(int(np.argmax(np.asarray(lg)[0])))
    got = compare.served_gaps(w, [{"id": 0, "prompt": prompt,
                                   "tokens": out}], DIMS)
    assert got["tokens_compared"] == 6 and got["gap_max"] < 1e-4
    wrong = list(out)
    wrong[3] = (wrong[3] + 1) % 96           # a token altered
    bad = compare.served_gaps(w, [{"id": 0, "prompt": prompt,
                                   "tokens": wrong}], DIMS)
    assert bad["gap_max"] > 0.05


def test_worst_leaf_gap_measures_against_the_median_leaf():
    from chipbench import compare
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)      # c: against median
    gap, leaf = compare.worst_leaf_gap({"a": 1.0, "b": 4.0, "c": 0}, ref,
                                       skip=["b"])
    assert gap < 1e-6
    j = compare.judge({"x": 0.5}, {"x": {"limit": 0.4}, "y": {"limit": 1}})
    assert not j["x"]["ok"] and not j["y"]["ok"] and j["y"]["value"] is None

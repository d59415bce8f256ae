"""The comparisons that decide ``correct``.

Serving: over a sample of finished greedy requests, the reference runs
once over prompt + served tokens, and at every served position the gap
``best reference logit - reference logit of the served token`` is read;
the widest is compared with the cell's limit. A control (the same
equations in a lower precision) reads, at the same positions, the gap of
the token IT puts first.

Training: norms by the worst leaf, as ``worst_leaf_gap`` says.
"""

import numpy as np


def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def served_gaps(weights: dict, sample, dims: dict,
                control: str = None) -> dict:
    """``sample``: [{"prompt": [...], "tokens": [...]}]. Returns the
    widest gap of the served tokens, the number of tokens compared, and
    with ``control`` the widest gap of the control's own first choices."""
    import jax.numpy as jnp
    from chipbench.references.gpt2_nobias import logits_at
    widest, n_tok, ctrl_widest, worst_req = 0.0, 0, 0.0, None
    flips = 0
    detail = []
    for req in sample:
        prompt, toks = list(req["prompt"]), list(req["tokens"])
        n = len(toks)
        if n == 0:
            continue
        seq = prompt + toks[:-1]          # the last token is fed to no one
        T = _pad_to(len(seq), 256)
        T = min(T, dims["max_len"])
        seq_p = seq + [0] * (T - len(seq))
        rows = [len(prompt) - 1 + i for i in range(n)]
        R = _pad_to(n, 128)
        rows_p = rows + [rows[-1]] * (R - n)
        kw = dict(n_heads=dims["n_heads"], eps=dims["eps"])
        ref = logits_at(weights, seq_p, rows_p, precision="f32", **kw)[:n]
        best = jnp.max(ref, axis=-1)
        served = ref[jnp.arange(n), jnp.asarray(toks, jnp.int32)]
        gaps = np.asarray(best - served)
        flips += int((gaps > 0).sum())
        far = np.flatnonzero(gaps > 0.5)
        detail.append({"id": req.get("id"), "n_prompt": len(prompt),
                       "n_out": n, "gap_max": float(gaps.max()),
                       "far_off": int(far.size),
                       "first_far_off": int(far[0]) if far.size else None})
        if float(gaps.max()) >= widest:
            widest, worst_req = float(gaps.max()), req.get("id")
        n_tok += n
        if control:
            low = logits_at(weights, seq_p, rows_p, precision=control,
                            **kw)[:n]
            pick = jnp.argmax(low, axis=-1)
            cg = np.asarray(best - ref[jnp.arange(n), pick])
            ctrl_widest = max(ctrl_widest, float(cg.max()))
    out = {"gap_max": widest, "tokens_compared": n_tok,
           "tokens_off_best": flips, "worst_request": worst_req,
           "requests": detail}
    if control:
        out["control_gap_max"] = ctrl_widest
    return out


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple:
    """Per leaf, |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf; returns the
    worst such share and the leaf it sits on. Norms, not the norm of a
    difference: some gradients are all but zero."""
    names = [k for k in reference if k not in skip]
    ref = np.asarray([float(reference[k]) for k in names])
    med = float(np.median(ref)) if len(ref) else 0.0
    worst, where = 0.0, None
    for k, r in zip(names, ref):
        denom = max(r, med)
        if denom <= 0:
            continue
        g = abs(float(program[k]) - r) / denom
        if g >= worst:
            worst, where = g, k
    return worst, where


def median_leaf_gap(program: dict, reference: dict, skip=()) -> float:
    """The median over leaves of the same share ``worst_leaf_gap`` takes
    the worst of: steady from seed to seed where the worst leaf is one
    small leaf's noise."""
    names = [k for k in reference if k not in skip]
    ref = np.asarray([float(reference[k]) for k in names])
    med = float(np.median(ref))
    gaps = [abs(float(program[k]) - r) / max(r, med)
            for k, r in zip(names, ref) if max(r, med) > 0]
    return float(np.median(gaps))


def judge(values: dict, limits: dict) -> dict:
    """name -> {"value", "limit", "ok"} for every number that has a
    limit; a number without one is not compared."""
    out = {}
    for name, lim in limits.items():
        if name not in values:
            out[name] = {"value": None, "limit": lim["limit"], "ok": False}
            continue
        v = float(values[name])
        ok = bool(np.isfinite(v)) and v <= float(lim["limit"])
        out[name] = {"value": v, "limit": float(lim["limit"]), "ok": ok}
    return out

"""Serving cells of the Qwen3-Next configuration: the program's paged
engine behind its replica wire, as ``systems/lm_serving.py`` runs the
GPT-2 one (whose driver, end-to-end reduction and counter plumbing this
module imports), with what that module ties to GPT-2 brought anew: the
program's config from the published keys, the artifact export, the
weights, the kernel-path check and the output check against
``references/qwen3_next.py``.

The path is ``save_lm_artifact(engine_paged=True)`` (once per checkout
and state of the program's source) -> ``load_lm_artifact`` ->
``.engine()`` -> ``precompile()`` -> ``ReplicaServer``. As in the other
serving cell, and named in the configuration's ``stands_in_for``: the
engine is handed the seed's weights, made on the device here, and the
programs are exported on the XLA path.
"""

import copy
import gc
import hashlib
import json
import math
import os
import time

from chipbench import compare, harness, q3next_work
from chipbench.systems.lm_serving import (counts, delta, drive, end_to_end,
                                          program_sources_hash,
                                          traced_interval)


def program_config(cfg: dict):
    """The program's config from the published keys. On a program that
    has no such skeleton this raises (``TypeError``: an unknown field)
    before anything is exported or compiled."""
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    m = cfg
    return transformer.TransformerConfig(
        vocab=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        n_layers=m["num_hidden_layers"], d_ff=m["moe_intermediate_size"],
        max_len=m["max_position_embeddings"],
        dtype=jnp.dtype(m["compute_dtype"]), use_rope=True,
        rope_theta=float(m["rope_theta"]), skeleton="gated_hybrid",
        attn_head_dim=m["head_dim"],
        rotary_dim=int(m["head_dim"] * m["partial_rotary_factor"]),
        norm_eps=m["rms_norm_eps"],
        full_attn_interval=m["full_attention_interval"],
        rec_key_heads=m["linear_num_key_heads"],
        rec_value_heads=m["linear_num_value_heads"],
        rec_key_dim=m["linear_key_head_dim"],
        rec_value_dim=m["linear_value_head_dim"],
        rec_conv=m["linear_conv_kernel_dim"],
        moe_experts=m["num_experts"], moe_top_k=m["num_experts_per_tok"],
        moe_held=(int(m.get("expert_first", 0)), m["num_experts_held"]),
        moe_shared_ff=m["shared_expert_intermediate_size"])


# -- weights ------------------------------------------------------------------

BLOCK = 1 << 26     # elements one draw makes at a time (256 MB float32)


def weight_specs(cfg: dict) -> dict:
    """name -> (shape, dtype name, scale, offset | 'decay') in the
    pytree layout the program's block consumes (``models/gated_hybrid.
    init_params``): layers stacked per period and per kind."""
    m = cfg
    D, V = m["hidden_size"], m["vocab_size"]
    H, Hkv, Dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    Hk, Hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    E, held = m["num_experts"], m["num_experts_held"]
    F, Fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    K = m["linear_conv_kernel_dim"]
    Pn = m["num_hidden_layers"] // m["full_attention_interval"]
    R = m["full_attention_interval"] - 1
    wd = m["weights_dtype"]
    s = 1.0 / math.sqrt(D)
    g = m["weight_gains"]

    def experts(lead):
        return {"router": (lead + (D, E), "float32", g["router"] * s, 0.0),
                "w1": (lead + (held, D, F), wd, s, 0.0),
                "w3": (lead + (held, D, F), wd, s, 0.0),
                "w2": (lead + (held, F, D), wd,
                       g["expert_out"] / math.sqrt(F), 0.0),
                "s_gate": (lead + (D,), wd, s, 0.0),
                "s_w1": (lead + (D, Fs), wd, s, 0.0),
                "s_w3": (lead + (D, Fs), wd, s, 0.0),
                "s_w2": (lead + (Fs, D), wd,
                         g["shared_out"] / math.sqrt(Fs), 0.0)}

    def norms(lead):
        return {"ln1": (lead + (D,), "float32", 0.1, 0.0),
                "ln2": (lead + (D,), "float32", 0.1, 0.0)}

    rec = {**norms((Pn, R)),
           "in_qkvz": ((Pn, R, D, 2 * Hk * dk + 2 * Hv * dv), wd, s, 0.0),
           "in_ba": ((Pn, R, D, 2 * Hv), wd, s, 0.0),
           "conv": ((Pn, R, 2 * Hk * dk + Hv * dv, K), wd, 0.5, 0.0),
           "A_log": ((Pn, R, Hv), "float32", None, "decay"),
           "dt_bias": ((Pn, R, Hv), "float32", 0.3, 0.0),
           "norm": ((Pn, R, dv), "float32", 0.1, 1.0),
           "out": ((Pn, R, Hv * dv, D), wd,
                   g["rec_out"] / math.sqrt(Hv * dv), 0.0),
           "moe": experts((Pn, R))}
    full = {**norms((Pn,)),
            "q": ((Pn, D, H * 2 * Dh), wd, s, 0.0),
            "k": ((Pn, D, Hkv * Dh), wd, s, 0.0),
            "v": ((Pn, D, Hkv * Dh), wd, s, 0.0),
            "q_norm": ((Pn, Dh), "float32", 0.1, g["qk_norm"]),
            "k_norm": ((Pn, Dh), "float32", 0.1, g["qk_norm"]),
            "o": ((Pn, H * Dh, D), wd, g["attn_out"] / math.sqrt(H * Dh),
                  0.0),
            "moe": experts((Pn,))}
    return {"embed": ((V, D), wd, 1.0, 0.0), "head": ((V, D), wd, s, 0.0),
            "ln_f": ((D,), "float32", 0.1, 0.0),
            "periods": {"rec": rec, "full": full}}


def make_weights(seed: int, cfg: dict) -> dict:
    """Weights from the seed in one jitted call on the device, chosen
    so that the output check can see every part: normal matrices at
    1/sqrt(fan_in) times the configuration's ``weight_gains`` (the
    expert, shared-expert and mixer outputs of the residual's order,
    q/k norm gains that sharpen attention to a few keys), every norm
    weight jittered off its default, and ``A_log`` log-uniform over
    ``decay_A`` so that exp(g) spans about 0.5-0.999 across heads (with
    the published init, U(0, 16), a state is forgotten in one token and
    no check of the recurrence sees anything). A large leaf is drawn in
    blocks of at most 2**26 elements, so its float32 temporary stays
    small beside the 7 GB of bfloat16 it fills."""
    import jax
    import jax.numpy as jnp
    from chipbench.weights import seed_key
    specs = weight_specs(cfg)
    lo, hi = (math.log(a) for a in cfg["decay_A"])

    def draw(key, shape, dtype, scale, offset):
        if offset == "decay":
            return jax.random.uniform(key, shape, jnp.float32, lo, hi)
        # blocks of whole trailing dims (and a divisor of the dim before
        # them), so that putting the blocks together only regroups
        # LEADING dims: a reshape that touches the two minor dims of a
        # tiled 800 MB array is a re-layout the compiler chokes on
        i = len(shape) - 1
        while i > 0 and math.prod(shape[i:]) * 16 <= BLOCK:
            i -= 1
        trail = math.prod(shape[i + 1:])
        rows = max((r for r in range(1, shape[i] + 1)
                    if shape[i] % r == 0 and r * trail <= BLOCK
                    and (r % 16 == 0 or r == shape[i])), default=shape[i])
        block = (rows,) + tuple(shape[i + 1:])
        count = math.prod(shape[:i]) * (shape[i] // rows)
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, block, jnp.float32)
                       * scale + offset).astype(dtype),
            jax.random.split(key, count))
        return out.reshape(shape)

    @jax.jit
    def make(key):
        flat, tree = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree_util.tree_unflatten(tree, [
            draw(jax.random.fold_in(key, i), shape, jnp.dtype(dt), sc, off)
            for i, (shape, dt, sc, off) in enumerate(flat)])

    return make(seed_key(seed, 3))


# -- set-up -------------------------------------------------------------------

def artifact_path(cell, work: str) -> str:
    import jax
    c = cell.config
    key = json.dumps([{k: v for k, v in c.items()
                       if isinstance(v, (int, float, str, bool))},
                      c["serving"], jax.__version__, jax.default_backend(),
                      program_sources_hash()], sort_keys=True)
    h = hashlib.sha1(key.encode()).hexdigest()[:12]
    return os.path.join(work, cell.config_name, f"artifact-{h}.tar")


def ensure_artifact(cell, params, pcfg, work: str) -> tuple:
    from paddle_tpu.io import lm_serving
    path = artifact_path(cell, work)
    if os.path.exists(path):
        return path, False
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    for old in os.listdir(d):
        os.remove(os.path.join(d, old))
    sv = cell.config["serving"]
    tmp = path + ".part"
    lm_serving.save_lm_artifact(
        tmp, params, pcfg, batch=sv["slots"], prompt_len=8,
        cache_len=sv["cache_len"],
        engine_buckets=tuple(sv["chunk_buckets"]), engine_paged=True,
        engine_block_size=sv["block_size"],
        engine_num_blocks=sv["num_blocks"])
    os.replace(tmp, path)
    return path, True


def check_kernel_paths(eng, want: str):
    paths = eng.kernel_paths or {}
    placed = {v for rec in paths.values() for v in rec.values()}
    sites = {s for rec in paths.values() for s in rec}
    if placed != {want} or not {"attention", "span_write",
                                "sampler"} <= sites or "decode" not in paths:
        raise SystemExit(f"chipbench: the engine's programs placed {paths}; "
                         f"the configuration asks for {want!r} at every "
                         f"kernel site")
    if not (eng.recurrent and eng.moe_stats):
        raise SystemExit("chipbench: the engine does not hold recurrent "
                         "rows beside its pool, or its programs do not "
                         "return the expert layer's counts")


def build(cell, seed: int, work: str):
    """Set-up up to a warm engine: (server object, engine, spans)."""
    try:
        pcfg = program_config(cell.config)
    except TypeError as e:
        raise SystemExit(f"chipbench: this program cannot state the "
                         f"configuration {cell.config_name!r}: {e}")
    from paddle_tpu.io import lm_serving
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    os.environ["PADDLE_TPU_PALLAS"] = cell.config["serving"]["pallas"]
    spans = {}
    t = time.time()
    params = make_weights(seed, cell.config)
    path, exported = ensure_artifact(cell, params, pcfg, work)
    spans["export_s"] = time.time() - t if exported else 0.0
    t_load = time.time()
    srv = lm_serving.load_lm_artifact(path)
    spans["artifact_load_s"] = time.time() - t_load
    srv.params = params
    eng = srv.engine(seed=int(seed) % (2 ** 31))
    eng.precompile()
    spans["replica_ready_s"] = time.time() - t_load
    spans["compile_cache"] = compile_cache.stats()
    check_kernel_paths(eng, cell.config["serving"]["kernel_paths"])
    return srv, eng, spans


# -- the output check ---------------------------------------------------------

def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def served_gaps(weights: dict, sample, config: dict, controls=(),
                keep_gaps: bool = False) -> dict:
    """``compare.served_gaps`` over ``references/qwen3_next.py``. At
    every served greedy position the gap ``reference's best logit -
    reference's logit of the served token``; two numbers of it are held
    to limits. ``gap_mean``, over all the positions compared, is what
    the precision moves: rounding noise of size s puts a token off the
    best wherever the best two lie within s, by about s, so the mean
    grows as s squared, and a router that picks another tenth expert at
    a tie (one token in ten lies off the best for it, most by under
    0.1) adds little to it. ``gap_max`` is what a fault at one place
    moves; ties alone carry it to 0.3 - 0.6. Each of ``controls`` (the
    reference in a lower precision) reads, at the same positions, the
    same two numbers of the token IT puts first, under
    ``["controls"][name]``; ``keep_gaps`` keeps every position's gap."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.references import qwen3_next as ref
    dims = ref.dims_of(config)
    gaps = {who: [] for who in ("program",) + tuple(controls)}
    detail, worst, worst_req = [], 0.0, None
    for req in sample:
        prompt, toks = list(req["prompt"]), list(req["tokens"])
        n = len(toks)
        if n == 0:
            continue
        seq = prompt + toks[:-1]          # the last token is fed to no one
        seq_p = seq + [0] * (_pad_to(len(seq), 256) - len(seq))
        rows = [len(prompt) - 1 + i for i in range(n)]
        rows_p = rows + [rows[-1]] * (_pad_to(n, 128) - n)
        out = ref.logits_at(weights, seq_p, rows_p, dims=dims)[:n]
        best = jnp.max(out, axis=-1)
        at = jnp.arange(n)
        g = np.asarray(best - out[at, jnp.asarray(toks, jnp.int32)])
        gaps["program"].append(g)
        far = np.flatnonzero(g > 0.5)
        detail.append({"id": req.get("id"), "n_prompt": len(prompt),
                       "n_out": n, "gap_max": float(g.max()),
                       "gap_mean": float(g.mean()), "far_off": int(far.size),
                       "first_far_off": int(far[0]) if far.size else None})
        if float(g.max()) >= worst:
            worst, worst_req = float(g.max()), req.get("id")
        for c in controls:
            low = ref.logits_at(weights, seq_p, rows_p, dims=dims,
                                precision=c)[:n]
            gaps[c].append(np.asarray(
                best - out[at, jnp.argmax(low, axis=-1)]))

    def numbers(parts):
        g = np.concatenate(parts) if parts else np.zeros(0, np.float32)
        doc = {"gap_max": float(g.max()) if g.size else 0.0,
               "gap_mean": float(g.mean()) if g.size else 0.0,
               "tokens_compared": int(g.size),
               "tokens_off_best": int((g > 0).sum())}
        if keep_gaps:
            doc["gaps"] = [round(float(v), 5) for v in g]
        return doc

    got = dict(numbers(gaps["program"]), worst_request=worst_req,
               requests=detail)
    got["controls"] = {c: numbers(gaps[c]) for c in controls}
    return got


def check(cell, seed: int, final: dict, controls=()) -> dict:
    """The reference over the sample, on weights made again from the
    seed (the program's are freed by now)."""
    return served_gaps(make_weights(seed, cell.config), final["sample"],
                       cell.config, controls=controls)


def run(cell, *, seed, seconds, trace, device, t_start, keep_trace=False,
        work: str = None, break_engine=None) -> int:
    work = work or harness.WORK
    srv, eng, spans = build(cell, seed, work)
    if break_engine is not None:
        break_engine(eng)
    tracer = harness.TraceWindow(keep=keep_trace) if trace else None
    box = drive(for_drive(cell), eng, seed, seconds, tracer)
    final = box["final"]
    e2e = end_to_end(final)
    e2e["setup_s"] = box["open_wall"] - t_start
    attempted, failed, unanswered = counts(final)
    mem = harness.memory_peak_bytes(cell.chips)
    window = delta(box["snaps"]["open"], box["snaps"]["close"])
    ctx = None
    if trace:
        ctx = {"cell": cell, "dims": q3next_work.dims(cell.config),
               "spans": spans, "peaks": harness.peaks_for(device["kind"]),
               "counters": window,
               "traced_counters": delta(box["snaps"]["trace_start"],
                                        box["snaps"]["trace_stop"]),
               "trace": tracer.read(cell.chips), "records": final["records"],
               "traced_interval": traced_interval(final, box, tracer),
               "kv_bytes_per_token": eng.kv_bytes_per_token,
               "slots": eng.batch, "block_size": eng.block_size}
    state = {"recurrent_state_bytes": eng.recurrent_state_bytes,
             "kv_pool_bytes": eng.pool_bytes}
    # free the program's state before the reference touches the chip
    del srv.params
    eng.params = eng.cache = None
    del srv, eng
    gc.collect()
    got = check(cell, seed, final)
    values = {"unanswered": unanswered}
    if got["tokens_compared"] > 0:      # nothing compared is not correct
        values.update(gap_max=got["gap_max"], gap_mean=got["gap_mean"])
    compared = compare.judge(values, cell.limits)
    correct = all(c["ok"] for c in compared.values()) and bool(compared)
    dev = dict(device, memory_peak_bytes=mem)
    per_layer, breakdown = {}, None
    if trace:
        dev["busy_s"] = ctx["trace"]["busy_s"]
        dev["window_s"] = ctx["trace"]["window_s"]
        per_layer = harness.read_per_layer(cell, ctx)
        breakdown = ctx["trace"]["breakdown"]
    notes = {"generator_late_ms": final["generator_late_ms"],
             "requests": attempted, "tokens_compared": got["tokens_compared"],
             "tokens_off_best": got["tokens_off_best"],
             "compared_requests": got["requests"],
             "engine_tokens_in_window": window.get("engine_tokens_total"),
             "moe_assignments_in_window": window.get(
                 "engine_moe_assignments_total"),
             "spans": spans, "state": state,
             "ttft_p50_ms": e2e.get("ttft_p50_ms")}
    harness.finish(cell, trace=trace, correct=correct, attempted=attempted,
                   failed=failed, end_to_end=e2e, per_layer=per_layer,
                   device=dev, compared=compared, breakdown=breakdown,
                   notes=notes)
    return 0


def for_drive(cell):
    """``lm_serving.drive`` reads the vocabulary through
    ``flops.lm_dims``, which wants the GPT-2 keys: the cell with a
    config that also answers to them (``vocab_size`` is the one drive
    uses; the generator draws ids from the held rows)."""
    c = cell.config
    cell = copy.copy(cell)
    cell.config = dict(
        c, n_embd=c["hidden_size"], n_head=c["num_attention_heads"],
        n_layer=c["num_hidden_layers"], n_inner=c["moe_intermediate_size"],
        n_positions=c["serving"]["cache_len"],
        layer_norm_epsilon=c["rms_norm_eps"])
    return cell

"""Serving cells of the GLM-4.7-Flash configuration: the program's paged
engine behind its replica wire, as ``systems/q3next_serving.py`` runs
the Qwen3-Next one (whose artifact export and drive adapter this module
imports beside what that module imports from ``systems/lm_serving.py``),
with what is this configuration's own: the program's config from the
published keys, the weights, the kernel-path check and the output check
against ``references/glm4_moe_lite.py``.

The path is ``save_lm_artifact(engine_paged=True)`` (once per checkout
and state of the program's source) -> ``load_lm_artifact`` ->
``.engine()`` -> ``precompile()`` -> ``ReplicaServer``. As in the other
serving cells, and named in the configuration's ``stands_in_for``: the
engine is handed the seed's weights, made on the device here, and the
programs are exported on the XLA path.
"""

import gc
import math
import os
import time

from chipbench import compare, glm47f_work, harness, reduce
from chipbench.systems.lm_serving import \
    check_kernel_paths as paths_placed
from chipbench.systems.lm_serving import (counts, delta, drive, end_to_end,
                                          traced_interval)
from chipbench.systems.q3next_serving import (BLOCK, _pad_to,
                                              ensure_artifact, for_drive)


def program_config(cfg: dict):
    """The program's config from the published keys. On a program that
    has no such skeleton this raises (``TypeError``: an unknown field)
    before anything is exported or compiled. The multi-token-prediction
    module is not served (``departures``): ``mtp_layers`` stays 0."""
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
        rope_theta=float(m["rope_theta"]), skeleton="latent_moe",
        norm_eps=m["rms_norm_eps"], q_lora_rank=m["q_lora_rank"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        dense_layers=m["first_k_dense_replace"],
        dense_ff=m["intermediate_size"], moe_experts=m["n_routed_experts"],
        moe_top_k=m["num_experts_per_tok"],
        moe_held=(int(m.get("expert_first", 0)),
                  int(m.get("num_experts_held", m["n_routed_experts"]))),
        moe_shared_ff=m["moe_intermediate_size"] * m["n_shared_experts"],
        moe_route_scale=float(m["routed_scaling_factor"]))


# -- weights ------------------------------------------------------------------

def weight_specs(cfg: dict) -> dict:
    """name -> (shape, dtype name, scale, offset) in the pytree layout
    the program's block consumes (``models/latent_moe.init_params``):
    layers stacked per kind, no prediction module."""
    m = cfg
    D, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    E = m["n_routed_experts"]
    held = int(m.get("num_experts_held", E))
    F, Fd = m["moe_intermediate_size"], m["intermediate_size"]
    Fs = F * m["n_shared_experts"]
    Ld = m["first_k_dense_replace"]
    Ls = m["num_hidden_layers"] - Ld
    wd = m["weights_dtype"]
    s = 1.0 / math.sqrt(D)
    g = m["weight_gains"]

    def mixer(n):
        return {"ln1": ((n, D), "float32", 0.1, 1.0),
                "ln2": ((n, D), "float32", 0.1, 1.0),
                "q_a": ((n, D, rq), wd, s, 0.0),
                "q_a_norm": ((n, rq), "float32", 0.1, 1.0),
                "q_b": ((n, rq, H * (dn + dr)), wd,
                        g["query"] / math.sqrt(rq), 0.0),
                "kv_a": ((n, D, rkv + dr), wd, s, 0.0),
                "kv_a_norm": ((n, rkv), "float32", 0.1, 1.0),
                "kv_b": ((n, rkv, H * (dn + dv)), wd, 1.0 / math.sqrt(rkv),
                         0.0),
                "o": ((n, H * dv, D), wd,
                      g["attn_out"] / math.sqrt(H * dv), 0.0)}

    dense = dict(mixer(Ld), gate=((Ld, D, Fd), wd, s, 0.0),
                 up=((Ld, D, Fd), wd, s, 0.0),
                 down=((Ld, Fd, D), wd, g["dense_out"] / math.sqrt(Fd),
                       0.0))
    moe = {"router": ((Ls, D, E), "float32", g["router"] * s, 0.0),
           "router_bias": ((Ls, E), "float32", g["router_bias"], 0.0),
           "w1": ((Ls, held, D, F), wd, s, 0.0),
           "w3": ((Ls, held, D, F), wd, s, 0.0),
           "w2": ((Ls, held, F, D), wd, g["expert_out"] / math.sqrt(F),
                  0.0),
           "s_w1": ((Ls, D, Fs), wd, s, 0.0),
           "s_w3": ((Ls, D, Fs), wd, s, 0.0),
           "s_w2": ((Ls, Fs, D), wd, g["shared_out"] / math.sqrt(Fs), 0.0)}
    return {"embed": ((V, D), wd, 1.0, 0.0), "head": ((V, D), wd, s, 0.0),
            "ln_f": ((D,), "float32", 0.1, 1.0),
            "dense": dense, "sparse": dict(mixer(Ls), moe=moe)}


def make_weights(seed: int, cfg: dict) -> dict:
    """Weights from the seed in one jitted call on the device, chosen
    so that the output check can see every part (``assumed.weights`` in
    the configuration): normal matrices at 1/sqrt(fan_in) times the
    configuration's ``weight_gains``, every norm weight jittered off 1,
    the selection bias small and not zero. A large leaf is drawn in
    blocks of at most 2**26 elements of whole trailing dims, as
    ``systems/q3next_serving.make_weights`` draws them and for its
    reasons."""
    import jax
    import jax.numpy as jnp
    from chipbench.weights import seed_key
    specs = weight_specs(cfg)

    def draw(key, shape, dtype, scale, offset):
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

    return make(seed_key(seed, 4))


# -- set-up -------------------------------------------------------------------

def check_kernel_paths(eng, want: str):
    paths_placed(eng, want)
    if eng.recurrent or not eng.moe_stats or "latent" not in eng.cache:
        raise SystemExit("chipbench: the engine's pool is not a latent "
                         "page table alone, or its programs do not "
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

def served_gaps(weights: dict, sample, config: dict, controls=(),
                keep_gaps: bool = False) -> dict:
    """``compare.served_gaps`` over ``references/glm4_moe_lite.py``, as
    ``systems/q3next_serving.served_gaps`` reads it: at every served
    greedy position the gap ``reference's best logit - reference's
    logit of the served token``; ``gap_mean`` over all the positions
    compared is what the precision moves, ``gap_max`` what a fault at
    one place moves. Each of ``controls`` puts the reference, changed,
    in the program's place and reads, at the same positions, the same
    two numbers of the token IT puts first: a precision (``fp8``,
    ``int8``) or one of the reference's planted ``FAULTS``."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.references import glm4_moe_lite as ref
    dims = ref.dims_of(config)
    pad = 4 * max(config["serving"]["chunk_buckets"])
    gaps = {who: [] for who in ("program",) + tuple(controls)}
    detail, worst, worst_req = [], 0.0, None
    for req in sample:
        prompt, toks = list(req["prompt"]), list(req["tokens"])
        n = len(toks)
        if n == 0:
            continue
        seq = prompt + toks[:-1]          # the last token is fed to no one
        # few distinct lengths (four chunks of the prefill grid: 4096 at
        # the published size): each is a compilation of every layer
        seq_p = seq + [0] * (_pad_to(len(seq), pad) - len(seq))
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
            kw = {"fault": c} if c in ref.FAULTS else {"precision": c}
            low = ref.logits_at(weights, seq_p, rows_p, dims=dims, **kw)[:n]
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
        ctx = {"cell": cell, "dims": glm47f_work.dims(cell.config),
               "spans": spans, "peaks": harness.peaks_for(device["kind"]),
               "counters": window,
               "traced_counters": delta(box["snaps"]["trace_start"],
                                        box["snaps"]["trace_stop"]),
               "trace": tracer.read(cell.chips), "records": final["records"],
               "traced_interval": traced_interval(final, box, tracer),
               "kv_bytes_per_token": eng.kv_bytes_per_token,
               "slots": eng.batch, "block_size": eng.block_size}
    eng_slots = eng.batch
    state = {"kv_pool_bytes": eng.pool_bytes,
             "kv_bytes_per_token": eng.kv_bytes_per_token,
             "slots_decoding_at_open": box["snaps"]["open"].get(
                 "engine_slots_active")}
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
    last = max((r["arrive"] for r in final["records"]
                if r.get("arrive") is not None), default=final["t_close"])
    # when the first wave (a prompt a slot, all sent at once) had its
    # first tokens: the ramp has to cover it
    wave = [reduce.first_token_at(r) for r in final["records"][:eng_slots]
            if reduce.answered(r)]
    notes = {"generator_late_ms": final["generator_late_ms"],
             "requests": attempted, "tokens_compared": got["tokens_compared"],
             "tokens_off_best": got["tokens_off_best"],
             "compared_requests": got["requests"],
             "engine_tokens_in_window": window.get("engine_tokens_total"),
             "moe_assignments_in_window": window.get(
                 "engine_moe_assignments_total"),
             "last_answer_after_close_s": last - final["t_close"],
             "first_wave_prefilled_s": max(wave) - final["t0"] if wave
             else None,
             "spans": spans, "state": state,
             "ttft_p50_ms": e2e.get("ttft_p50_ms")}
    harness.finish(cell, trace=trace, correct=correct, attempted=attempted,
                   failed=failed, end_to_end=e2e, per_layer=per_layer,
                   device=dev, compared=compared, breakdown=breakdown,
                   notes=notes)
    return 0

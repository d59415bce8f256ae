"""LM serving artifact — the functional-transformer counterpart of
io/merged.py (reference slot: paddle/capi + MergeModel's one-file
deployment, and the SWIG SequenceGenerator serving surface,
paddle/api/PaddleAPI.h:1025).

One tar holds the parameter pytree, the TransformerConfig, and TWO AOT
StableHLO modules (jax.export):
- ``prefill``: [B, Tp] prompt → (last-position logits, KV cache)
- ``decode``:  one incremental token step against the cache
A loading process needs paddle_tpu for the tar/np plumbing only — no
model code, no tracing, no recompilation on the same platform; greedy
or temperature sampling happens host-side between compiled calls.
"""

import dataclasses
import functools
import io as _io
import json
import os
import tarfile
import time
from typing import Optional, Sequence

import numpy as np

from paddle_tpu.io.checkpoint import _flatten          # shared pytree walk
from paddle_tpu.io.merged import _add_member as _add   # shared tar append
from paddle_tpu.observe import costs as _costs
from paddle_tpu.observe import metrics as _metrics
from paddle_tpu.observe import trace as _trace
from paddle_tpu.serving import blocks as _blocks

FORMAT_VERSION = 5   # max supported; plain artifacts still save as v1,
#                      int8-weight ones as v2; v4 adds the PAGED engine
#                      modules (chunked block-pool prefill per chunk
#                      bucket + page-table decode with on-device
#                      sampling — prefix caching and chunked prefill
#                      are host-side scheduling over them; v3 was the
#                      row-arena engine's, no longer written or served);
#                      v5 additionally stamps a DRAFT model for
#                      speculative decoding (draft params + its chunk
#                      prefill / fused k-step propose / batched verify
#                      modules — LMServer.engine() then schedules a
#                      SpecDecodeEngine over the shared block table)


def _unflatten(flat):
    """Rebuild the nested pytree from checkpoint-style '/'-joined paths
    WITHOUT a template (the loader has no model code): dict nodes whose
    keys are all '__i' were list/tuple nodes in _flatten's encoding."""
    tree = {}
    for key, v in flat.items():
        parts = key.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def fix(node):
        if isinstance(node, dict):
            node = {k: fix(v) for k, v in node.items()}
            if node and all(k.startswith("__") for k in node):
                return [node[f"__{i}"] for i in range(len(node))]
        return node

    return fix(tree)


def _cfg_to_dict(cfg):
    import jax.numpy as jnp
    d = dataclasses.asdict(cfg)
    d["dtype"] = jnp.dtype(cfg.dtype).name
    return d


def _cfg_from_dict(d):
    import jax.numpy as jnp
    from paddle_tpu.models.transformer import TransformerConfig
    d = dict(d)
    d["dtype"] = jnp.dtype(d["dtype"])
    return TransformerConfig(**d)


# the big matmul weights of the transformer pytree, with the axis the
# consuming einsum CONTRACTS over (the quantization-scale reduce axis):
# blocks.* are [L, in, out] (contract axis -2); embed [V, D] doubles as
# the logits projection contracting over D (axis -1), which also makes
# embedding-row gathers dequantize per row
_W8_LEAVES = {("blocks", "qkv"): -2, ("blocks", "attn_out"): -2,
              ("blocks", "mlp_in"): -2, ("blocks", "mlp_out"): -2,
              ("embed",): -1}


def quantize_lm_params(params):
    """Per-output-channel int8 for the big matmul weights (ops/q8
    helpers); layer norms, biases, and position tables stay fp32.
    Returns a pytree whose quantized leaves are {"q8","scale"} nodes —
    HBM (and artifact) weight bytes halve, and every weight read in the
    decode step becomes 1 byte/elt with the dequant multiply fused into
    the matmul operand read (decode is weight-read-bound, so this is the
    serving-throughput lever)."""
    from paddle_tpu.ops import q8 as ops_q8

    out = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in params.items()}
    for path, axis in _W8_LEAVES.items():
        node = out
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = ops_q8.quantize_weight(node[path[-1]], axis)
    return out


def save_lm_artifact(path: str, params, cfg, *, batch: int,
                     prompt_len: int, cache_len: int,
                     platforms: Optional[Sequence[str]] = None,
                     weights_int8: bool = False,
                     engine_buckets: Optional[Sequence[int]] = None,
                     engine_paged: bool = True,
                     engine_block_size: int = _blocks.DEFAULT_BLOCK_SIZE,
                     engine_num_blocks: Optional[int] = None,
                     engine_kv_dtype: Optional[str] = None,
                     engine_draft_params=None,
                     engine_draft_config=None,
                     engine_spec_k: int = 4
                     ) -> None:
    """Export the serving pair at fixed shapes and pack the artifact.

    batch/prompt_len/cache_len fix the exported shapes (AOT modules are
    shape-specialized; export several artifacts for several shapes).
    ``platforms`` e.g. ["tpu", "cpu"] widens where the module may run.
    The engine modules' ``PADDLE_TPU_PALLAS`` policy resolves against
    that TARGET (``auto`` places the compiled kernels iff every target
    platform is TPU), never against the exporting process's backend;
    what each module placed is stamped in ``meta.engine_kernel_paths``.
    Exporting compiled kernels from a host without the chip needs the
    target named: ``ops.pallas.policy.compile_target(device_kind)``.
    DTYPES: the four stacked block matrices (``qkv``, ``attn_out``,
    ``mlp_in``, ``mlp_out``) are stored, and taken by every exported
    program (lockstep pair, paged prefill and decode, the speculative
    members and their draft tree), in ``cfg.dtype``
    (``transformer.compute_dtype_params``; a bf16 leaf is a
    ``<path>@bfloat16`` member of the ``.npz``), every other leaf as
    handed in: a decode step reads each weight byte once instead of
    casting float32 leaves every step. The engine of
    ``LMServer.engine()`` conforms whatever ``params`` it is handed to
    the programs' input dtypes, once.
    ``weights_int8`` stores the big matmul weights as per-output-channel
    int8 (see quantize_lm_params) — the exported modules dequantize
    inline, so the loader and LMServer are unchanged.
    ``engine_buckets`` additionally exports the continuous-batching
    engine's programs (format v4), with on-device
    greedy/temperature/top-k sampling: the CHUNK buckets, one
    ``engine_prefill_paged_<C>_<P>.bin`` chunk-prefill module per
    (chunk bucket C, page-vector length P) pair on the fixed chunk grid
    (``max(engine_buckets)`` tokens — the context span a chunk attends
    over is encoded in its page-vector SHAPE), plus
    one ``engine_decode_paged.bin`` page-table decode; ``batch`` is the
    engine's slot count and the KV pool is
    ``engine_num_blocks`` (default ``batch * cache_len/block_size``)
    blocks of ``engine_block_size``
    tokens. ``LMServer.engine()`` schedules a
    ``serving.PagedDecodeEngine`` (chunked prefill + prefix cache)
    over them; without ``engine_buckets`` the artifact holds the
    lockstep pair alone (v1/v2). ``engine_paged`` accepts only ``True``
    (there is no other engine; the keyword waits on its two callers).
    ``engine_kv_dtype`` ("int8"/"int4") exports the engine
    modules over a QUANTIZED pool (``transformer.init_block_pool``
    kv_dtype semantics: int8 / nibble-packed values + per-(position,
    head) fp32 scale tables): the stamp lands in
    ``meta.engine_paged.kv_dtype`` so the loader rebuilds the exact
    pool layout with no model code, and the compiled modules carry the
    write-time quantization + fused-dequant reads.
    """
    import jax
    import jax.export  # noqa: F401 — jax.export needs an explicit import
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    from paddle_tpu.ops import q8 as ops_q8

    if cache_len > cfg.max_len:
        raise ValueError(f"cache_len {cache_len} exceeds cfg.max_len "
                         f"{cfg.max_len}")
    if not engine_paged:
        raise ValueError("engine_paged=False: the row-arena engine and "
                         "its artifact format v3 are gone (PR 28) — "
                         "engine_buckets= exports the paged engine's "
                         "modules; drop the keyword")
    if not engine_buckets:
        # checked up front: an export that silently dropped the
        # requested quantized pool or draft would only be discovered at
        # serve time
        for on, what in ((engine_kv_dtype, "engine_kv_dtype"),
                         (engine_draft_params is not None,
                          "engine_draft_params")):
            if on:
                raise ValueError(f"{what} needs engine_buckets= "
                                 f"(the chunk buckets to export)")
    if (engine_draft_params is None) != (engine_draft_config is None):
        raise ValueError("engine_draft_params and engine_draft_config "
                         "come together (the draft model for "
                         "speculative decoding)")
    if engine_draft_config is not None \
            and engine_draft_config.vocab != cfg.vocab:
        raise ValueError(f"draft vocab {engine_draft_config.vocab} != "
                         f"target vocab {cfg.vocab}")

    hybrid = cfg.skeleton != "gpt2"
    if hybrid:
        # such a skeleton runs through the paged engine's programs alone
        # (bf16 weight leaves, the model's own KV width); the lockstep
        # pair is not exported for it
        for on, what in ((weights_int8, "int8 weights"),
                         (engine_kv_dtype, "an int8 / int4 KV pool"),
                         (engine_draft_params is not None,
                          "speculative decoding"),
                         (not engine_buckets, "an artifact without "
                          "engine_buckets")):
            if on:
                transformer.require_gpt2(cfg, what)
    if weights_int8:
        # from the leaves as handed in: int8 of a float32 value, not of
        # its bfloat16 rounding (the helper below passes q8 nodes on)
        params = quantize_lm_params(params)

        def _p(p):
            return ops_q8.dequantize_tree(p)
    else:
        def _p(p):
            return p
    # the block matrices in the compute dtype BEFORE the programs are
    # typed and the leaves written: every exported program takes them
    # as it multiplies by them, and the artifact stores them so
    params = transformer.compute_dtype_params(params, cfg)
    if engine_draft_params is not None:
        engine_draft_params = transformer.compute_dtype_params(
            engine_draft_params, engine_draft_config)

    def prefill_fn(p, tokens):
        return transformer.prefill(_p(p), tokens, cfg, cache_len)

    def decode_fn(p, cache, tokens, pos):
        return transformer.decode_step(_p(p), cache, tokens, pos, cfg)

    kw = {"platforms": list(platforms)} if platforms else {}
    p_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a),
            a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype),
        params)
    toks = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    jit_prefill, jit_decode = jax.jit(prefill_fn), jax.jit(decode_fn)
    lockstep = {}
    if not hybrid:
        exp_prefill = jax.export.export(jit_prefill, **kw)(
            p_shapes, toks)
        cache_shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            transformer.init_cache(cfg, batch, cache_len))
        decode_args = (p_shapes, cache_shapes,
                       jax.ShapeDtypeStruct((batch,), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32))
        exp_decode = jax.export.export(jit_decode, **kw)(*decode_args)
        lockstep = {"prefill.bin": exp_prefill.serialize(),
                    "decode.bin": exp_decode.serialize()}

    # the engine's programs: chunk prefill per (chunk bucket, context
    # span) + one page-table decode step, the sampler fused into both
    # (token ids are the only host-bound output)
    engine_members = {}
    if engine_buckets:
        from paddle_tpu.ops.pallas import policy as _pallas_policy
        from paddle_tpu.serving import sampling as _sampling
        # the policy the engine modules are built under, resolved
        # against the platform they are exported FOR (a Mosaic kernel
        # lowers for TPU only, so a mixed target list is the XLA path);
        # stamped with the per-module placement record — a loader
        # cannot re-derive either from the .bin
        targets = set(platforms or [jax.default_backend()])
        engine_pallas = _pallas_policy.pallas_mode(
            None, platform=targets.pop() if len(targets) == 1
            else "mixed")
        buckets = sorted({int(b) for b in engine_buckets})
        bad = [b for b in buckets if b < 1 or b > cache_len]
        if bad:
            raise ValueError(f"engine_buckets {bad} outside "
                             f"[1, cache_len={cache_len}]")
        dequant = ops_q8.dequantize_tree if weights_int8 else None
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        f32 = jax.ShapeDtypeStruct((), jnp.float32)

        def _vec(dt):
            return jax.ShapeDtypeStruct((batch,), dt)

        bs = int(engine_block_size)
        if bs < 1 or cache_len % bs:
            raise ValueError(f"cache_len {cache_len} must be a "
                             f"positive multiple of "
                             f"engine_block_size {bs}")
        pages = cache_len // bs
        nb = int(engine_num_blocks if engine_num_blocks is not None
                 else batch * pages)
        chunk = max(buckets)        # the engine's prefill chunk grid
        if chunk % bs or cache_len % chunk:
            raise ValueError(
                f"paged export needs block_size {bs} | chunk "
                f"{chunk} | cache_len {cache_len} (each dividing "
                f"the next): the chunk grid anchors the exported "
                f"context spans")
        engine_paged_meta = {"block_size": bs, "num_blocks": nb,
                             "pages_per_slot": pages,
                             "chunk_tokens": chunk,
                             "pallas": engine_pallas,
                             "kv_dtype": engine_kv_dtype or "none",
                             # the pool array layout the modules
                             # were shaped against — the loader
                             # refuses to schedule programs from a
                             # different layout generation (the
                             # pre-relayout slot-major pool)
                             "pool_layout":
                                 transformer.POOL_LAYOUT}
        eng_prefill, eng_decode = _sampling.paged_step_fns(
            cfg, bs, dequant=dequant, pallas=engine_pallas)
        pool_shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            transformer.init_block_pool(
                cfg, nb, bs, kv_dtype=engine_kv_dtype, slots=batch))
        # one chunk-prefill module per (bucket, context span) the
        # fixed chunk grid can reach: a chunk's context length is
        # encoded in its page-vector SHAPE (span specialization —
        # cold chunks attend over C tokens, not cache_len), so each
        # (C, P) pair is its own AOT program
        for ctx in range(0, cache_len, chunk):
            for b in buckets:
                pv = ctx // bs + -(-b // bs)
                ep = jax.export.export(jax.jit(eng_prefill), **kw)(
                    p_shapes, pool_shapes,
                    jax.ShapeDtypeStruct((1, b), jnp.int32), i32,
                    jax.ShapeDtypeStruct((pv,), jnp.int32),
                    *((i32,) if hybrid and transformer.skeleton_module(
                        cfg).SLOT_STATE else ()),   # the slot
                    f32, i32, i32)
                engine_members[
                    f"engine_prefill_paged_{b}_{pv}.bin"] = \
                    ep.serialize()
        # the decode signature: tokens, pos, active, pages,
        # temperature, top_k, seed
        eng_decode_args = (
            p_shapes, pool_shapes, _vec(jnp.int32), _vec(jnp.int32),
            _vec(jnp.bool_),
            jax.ShapeDtypeStruct((batch, pages), jnp.int32),
            _vec(jnp.float32), _vec(jnp.int32), i32)
        if engine_draft_params is not None:
            # v5: the draft's program set — chunk prefill mirroring
            # the target grid, fused k-step propose, the target's
            # batched verify, and the draft-side forced-window
            # write the preempt-resume replay needs
            dcfg = engine_draft_config
            k = int(engine_spec_k)
            W = k + 1
            spec = _sampling.paged_spec_fns(
                cfg, dcfg, bs, k, dequant=dequant,
                pallas=engine_pallas,
                paths=eng_decode.kernel_paths)
            dp_shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a),
                    a.dtype if hasattr(a, "dtype")
                    else np.asarray(a).dtype), engine_draft_params)
            dpool_shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                transformer.init_block_pool(dcfg, nb, bs))
            for ctx in range(0, cache_len, chunk):
                for b in buckets:
                    pv = ctx // bs + -(-b // bs)
                    ep = jax.export.export(
                        jax.jit(spec["draft_prefill"]), **kw)(
                        dp_shapes, dpool_shapes,
                        jax.ShapeDtypeStruct((1, b), jnp.int32),
                        i32,
                        jax.ShapeDtypeStruct((pv,), jnp.int32))
                    engine_members[
                        f"engine_draft_prefill_{b}_{pv}.bin"] = \
                        ep.serialize()
            pages_s = jax.ShapeDtypeStruct((batch, pages),
                                           jnp.int32)
            win_s = jax.ShapeDtypeStruct((batch, W), jnp.int32)
            engine_members["engine_propose.bin"] = \
                jax.export.export(jax.jit(spec["propose"]), **kw)(
                    dp_shapes, dpool_shapes, _vec(jnp.int32),
                    _vec(jnp.int32), _vec(jnp.bool_),
                    _vec(jnp.int32), pages_s).serialize()
            jit_verify = jax.jit(spec["verify"])
            verify_args = (p_shapes, pool_shapes, win_s,
                           _vec(jnp.int32), _vec(jnp.int32),
                           _vec(jnp.bool_), pages_s,
                           _vec(jnp.float32), _vec(jnp.int32), i32)
            engine_members["engine_verify.bin"] = \
                jax.export.export(jit_verify, **kw)(
                    *verify_args).serialize()
            engine_members["engine_draft_verify.bin"] = \
                jax.export.export(
                    jax.jit(spec["draft_verify"]), **kw)(
                    dp_shapes, dpool_shapes, win_s,
                    _vec(jnp.int32), _vec(jnp.int32),
                    _vec(jnp.bool_), pages_s).serialize()
        jit_eng_decode = jax.jit(eng_decode)
        engine_members["engine_decode_paged.bin"] = jax.export.export(
            jit_eng_decode, **kw)(*eng_decode_args).serialize()

    # per-phase cost accounting, stamped into the artifact at export
    # time (the loader has no model code to re-derive it from): the MFU
    # denominator's numerator for any host that serves this file
    cost_analysis = {}
    phases = [] if hybrid else [
        ("prefill", jit_prefill, (p_shapes, toks)),
        ("decode", jit_decode, decode_args)]
    if engine_buckets:
        phases.append(("engine_decode", jit_eng_decode, eng_decode_args))
    if engine_draft_params is not None:
        # the spec engine dispatches VERIFY rounds, not decode steps —
        # its MFU numerator is the verify program's model FLOPs
        phases.append(("engine_verify", jit_verify, verify_args))
    for phase, fn, args in phases:
        ca = _costs.lowered_cost(fn, *args, platforms=platforms)
        if ca:
            cost_analysis[phase] = ca

    meta = {
        # quantized artifacts carry nested {"q8","scale"} params — a v2
        # encoding; plain artifacts stay v1 for older loaders; engine
        # modules (whose member names older loaders would not recognise)
        # bump to v4 (3 was the row-arena engine's); a stamped draft to
        # v5
        "format_version": (5 if engine_draft_params is not None else 4)
        if engine_buckets else (2 if weights_int8 else 1),
        "batch": batch, "prompt_len": prompt_len, "cache_len": cache_len,
        "weights_int8": weights_int8, "config": _cfg_to_dict(cfg),
        "cost_analysis": cost_analysis}
    if engine_buckets:
        meta["engine_buckets"] = buckets
        meta["engine_pallas"] = engine_pallas
        meta["engine_kernel_paths"] = eng_decode.kernel_paths
        meta["engine_paged"] = engine_paged_meta
    draft_blob = None
    if engine_draft_params is not None:
        meta["engine_spec"] = {
            "k": int(engine_spec_k),
            "draft_config": _cfg_to_dict(engine_draft_config)}
        dbuf = _io.BytesIO()
        np.savez(dbuf, **_npz_leaves(_flatten(engine_draft_params)))
        draft_blob = dbuf.getvalue()
    with tarfile.open(path, "w") as tar:
        _add(tar, "meta.json", json.dumps(meta).encode())
        # through a file beside the artifact, not through memory: an
        # .npz built in a BytesIO and handed on as bytes holds the
        # weights four times over (the leaves, the buffer, its value,
        # the member's stream), which a 7 GB model does not survive
        part = path + ".params.part"
        try:
            with open(part, "wb") as f:
                np.savez(f, **_npz_leaves(_flatten(params)))
            tar.add(part, arcname="params.npz")
        finally:
            if os.path.exists(part):
                os.remove(part)
        if draft_blob is not None:
            _add(tar, "draft_params.npz", draft_blob)
        for name, blob in lockstep.items():
            _add(tar, name, blob)
        for name, blob in engine_members.items():
            _add(tar, name, blob)


# decode steps run single-digit ms; prefill tens-to-hundreds — buckets
# must resolve both (default Prometheus buckets start too coarse at 1 ms)
_LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5, 5.0)


class LMServer:
    """Loaded artifact: compiled prefill + decode, host-side sampling.

    ``generate(prompt, max_new)`` mirrors models/transformer.generate
    greedy/temperature semantics but never traces or imports the model.

    Each server carries its own metrics ``Registry`` (serving several
    artifacts in one process must not cross-pollute counters):
    prefill/decode call counts, generated-token count, and per-phase
    latency histograms; ``metrics_text()`` renders the Prometheus text
    snapshot a scrape endpoint serves verbatim.
    """

    def __init__(self, meta, params, prefill_bin, decode_bin,
                 engine_bins=None, draft_params=None):
        import jax
        import jax.export  # noqa: F401 — needs an explicit import
        self.meta = meta
        self.cfg = _cfg_from_dict(meta["config"])
        self.params = params
        # v5: the stamped speculative-decoding draft (None below v5)
        self.draft_params = draft_params
        # the lockstep pair (absent from the artifact of a skeleton
        # other than "gpt2", which
        # serves through engine() alone)
        self._prefill = prefill_bin and jax.export.deserialize(prefill_bin)
        self._decode = decode_bin and jax.export.deserialize(decode_bin)
        # the continuous-batching engine's modules (absent on v1/v2):
        # deserialized lazily by engine() — lockstep-only consumers of
        # the artifact pay nothing for them
        self._engine_bins = dict(engine_bins or {})
        self.engine_buckets = tuple(meta.get("engine_buckets", ()))
        reg = self.metrics = _metrics.Registry()
        self._m_prefill = reg.counter(
            "lm_prefill_calls_total", "prefill (prompt) passes served")
        self._m_decode = reg.counter(
            "lm_decode_calls_total", "incremental decode steps served")
        self._m_tokens = reg.counter(
            "lm_tokens_generated_total", "tokens sampled across all calls")
        self._m_requests = reg.counter(
            "lm_generate_requests_total", "generate() calls",)
        self._m_prefill_s = reg.histogram(
            "lm_prefill_seconds", "prefill latency (device call + sample)",
            buckets=_LATENCY_BUCKETS)
        self._m_decode_s = reg.histogram(
            "lm_decode_seconds", "per-token decode latency "
            "(device call + sample)", buckets=_LATENCY_BUCKETS)
        # cost accounting stamped at export time (older artifacts: {})
        self.cost_analysis = meta.get("cost_analysis", {})
        self._m_mfu = reg.gauge(
            "lm_decode_mfu", "model-FLOPs utilisation of the last decode "
            "step (0 until the artifact carries cost_analysis)")
        # constant for the process — resolved once, not per decoded token
        self._peak_flops = _costs.device_peak_flops()
        self._last_generate = None

    def metrics_text(self) -> str:
        """Prometheus text exposition snapshot of this server's metrics."""
        return self.metrics.render_prometheus()

    def health(self) -> dict:
        """/healthz document: request/token progress of this server."""
        since = (round(time.perf_counter() - self._last_generate, 3)
                 if self._last_generate is not None else None)
        return {"requests": int(self._m_requests.value()),
                "tokens_generated": int(self._m_tokens.value()),
                "decode_steps": int(self._m_decode.value()),
                "seconds_since_request": since,
                "batch": self.meta["batch"],
                "cache_len": self.meta["cache_len"]}

    def serve(self, host: str = "127.0.0.1", port: int = 0):
        """Start an ``observe.HealthServer`` over THIS server's registry
        (``/metrics``) and ``health()`` (``/healthz``). Returns the
        server; callers own its ``close()``."""
        from paddle_tpu.observe.health import HealthServer
        return HealthServer(registry=self.metrics, health_fn=self.health,
                            host=host, port=port)

    def _engine_exported(self, member: str):
        """One of the engine's exported modules, deserialised."""
        import jax.export
        with _trace.trace_scope("artifact/programs"):
            return jax.export.deserialize(self._engine_bins[member])

    def engine(self, *, seed: Optional[int] = None, registry=None,
               tracker=None, chunk_tokens: Optional[int] = None,
               tiers=None):
        """Continuous-batching engine over this artifact's modules:
        a ``serving.PagedDecodeEngine`` (paged block pool + chunked
        prefill + prefix cache; the chunk grid is the artifact's —
        ``chunk_tokens`` may only restate it, the prefill modules are
        span-specialized), a ``serving.SpecDecodeEngine`` when the
        artifact stamps a draft (v5). The engine conforms
        ``self.params`` ONCE to what the programs take (:func:`_conform`
        as its ``conform``: float32 training weights set on the server
        become the bf16 matrices of the exported signature) and
        ``self.params`` is rebound to the result, the draft's likewise.
        Raises on v1/v2 artifacts and on
        a v3 one (the row-arena engine's modules, which nothing runs
        any more) — re-export with ``engine_buckets=`` to serve
        continuously; ``generate()`` stays the lockstep fallback."""
        from paddle_tpu.models import transformer
        from paddle_tpu.serving.engine import (PagedDecodeEngine,
                                               SpecDecodeEngine)
        if not self._engine_bins:
            raise ValueError(
                f"artifact (format v{self.meta['format_version']}) has "
                f"no engine modules — re-export with "
                f"save_lm_artifact(..., engine_buckets=(...)) for "
                f"continuous batching")
        cfg = self.cfg
        paged = self.meta.get("engine_paged")
        if not paged:
            raise ValueError(
                f"artifact (format v{self.meta['format_version']}) "
                f"holds the row-arena engine's modules, which this "
                f"build no longer runs — re-export with "
                f"save_lm_artifact(..., engine_buckets=(...)) to "
                f"serve it")
        # layout fencing: the exported modules bake the pool array
        # shapes, so a legacy slot-major artifact (pre-head-major
        # relayout; no pool_layout stamp) cannot be scheduled over
        # the pool this build constructs — the failure would
        # otherwise surface as an opaque shape mismatch at the
        # first prefill call
        stamped = paged.get("pool_layout", "slot_major")
        if stamped != transformer.POOL_LAYOUT:
            raise ValueError(
                f"artifact's paged-engine modules were exported "
                f"against a {stamped!r} KV pool but this build "
                f"uses {transformer.POOL_LAYOUT!r} — re-export "
                f"with save_lm_artifact(..., engine_buckets=(...)) "
                f"to serve it")
        meta_chunk = int(paged.get("chunk_tokens",
                                   max(self.engine_buckets)))
        if chunk_tokens is not None and int(chunk_tokens) != \
                meta_chunk:
            raise ValueError(
                f"artifact exported on a chunk grid of "
                f"{meta_chunk} tokens (its prefill modules are "
                f"(bucket, context-span)-specialized); "
                f"chunk_tokens={chunk_tokens} has no programs — "
                f"re-export to change the grid")
        prefills = {}
        for name in self._engine_bins:
            if not name.startswith("engine_prefill_paged_"):
                continue
            b, pv = name[len("engine_prefill_paged_"):
                         -len(".bin")].split("_")
            prefills[(int(b), int(pv))] = _program(
                self._engine_exported(name), donate_pool=True)
        decode_exported = self._engine_exported("engine_decode_paged.bin")
        decode = _program(decode_exported, donate_pool=True)

        def prefill(params, pool, tokens, length, pagevec, *rest):
            key = (tokens.shape[1], pagevec.shape[0])
            return prefills[key](params, pool, tokens, length,
                                 pagevec, *rest)

        # zero-filled block pool from the meta geometry + kv_dtype
        # stamp, built by the SAME constructor the export shaped
        # the modules against (one source of truth for the pool
        # layout — the loader already imports the transformer
        # module for TransformerConfig, so this adds no dependency)
        kvd = paged.get("kv_dtype", "none")
        if kvd == "none":
            kvd = None
        pool = transformer.init_block_pool(
            cfg, paged["num_blocks"], paged["block_size"],
            kv_dtype=kvd, slots=self.meta["batch"])
        eng_kw = dict(
            batch=self.meta["batch"],
            cache_len=self.meta["cache_len"],
            block_size=paged["block_size"],
            num_blocks=paged["num_blocks"],
            chunk_tokens=meta_chunk,
            chunk_buckets=self.engine_buckets, seed=seed,
            registry=registry, tracker=tracker,
            decode_flops=self.cost_analysis.get(
                "engine_decode", {}).get("flops"),
            pallas_mode=self.meta.get("engine_pallas"),
            kernel_paths=self.meta.get("engine_kernel_paths"),
            kv_dtype=kvd, tiers=tiers,
            # whatever the engine is handed (training weights, the
            # seed's float32 tree of a benchmark) is cast ONCE to what
            # the programs take, and not by every decode step
            conform=functools.partial(_conform, exported=decode_exported))
        spec = self.meta.get("engine_spec")
        if spec:
            # v5: schedule the SpecDecodeEngine over the stamped
            # draft — its pool rebuilt from the draft config at
            # the SAME block geometry (one page table, two pools)
            dcfg = _cfg_from_dict(spec["draft_config"])
            draft_pool = transformer.init_block_pool(
                dcfg, paged["num_blocks"], paged["block_size"])
            dprefills = {}
            for name in self._engine_bins:
                if not name.startswith("engine_draft_prefill_"):
                    continue
                b, pv = name[len("engine_draft_prefill_"):
                             -len(".bin")].split("_")
                dprefills[(int(b), int(pv))] = _program(
                    self._engine_exported(name))

            def draft_prefill(dp, dpool, tokens, length, pagevec):
                key = (tokens.shape[1], pagevec.shape[0])
                return dprefills[key](dp, dpool, tokens, length,
                                      pagevec)

            eng_kw["decode_flops"] = self.cost_analysis.get(
                "engine_verify", {}).get(
                "flops", eng_kw["decode_flops"])
            propose_exported = self._engine_exported("engine_propose.bin")
            self.draft_params = _conform(self.draft_params,
                                         propose_exported)
            eng = SpecDecodeEngine(
                prefill, decode, self.params, pool,
                draft_params=self.draft_params,
                draft_cache=draft_pool,
                draft_prefill=draft_prefill,
                propose=_program(propose_exported),
                verify=_program(
                    self._engine_exported("engine_verify.bin")),
                draft_verify=_program(
                    self._engine_exported("engine_draft_verify.bin")),
                spec_k=spec["k"], **eng_kw)
        else:
            eng = PagedDecodeEngine(
                prefill, decode, self.params, pool, **eng_kw)
        # no float32 copy of the matrices outlives the call
        self.params = eng.params
        return eng

    def generate(self, prompt: np.ndarray, max_new: int,
                 temperature: float = 0.0,
                 seed: Optional[int] = None,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """Lockstep batch generation (every row decodes in unison).

        ``seed=None`` draws fresh OS entropy — two unseeded sampling
        calls differ; pass an int for reproducibility. ``eos_id`` stops
        the decode loop early once EVERY row has emitted it (rows that
        finish first keep emitting ``eos_id`` as padding), so the result
        is ``[B, prompt_len + n]`` with ``n <= max_new``."""
        if self._prefill is None:
            from paddle_tpu.models import transformer
            transformer.require_gpt2(self.cfg, "LMServer.generate (the "
                                     "lockstep path)")
        import jax.numpy as jnp
        if max_new < 1:
            raise ValueError(f"generate: max_new must be >= 1, "
                             f"got {max_new}")
        b, tp = prompt.shape
        if b != self.meta["batch"] or tp != self.meta["prompt_len"]:
            raise ValueError(
                f"artifact exported for batch={self.meta['batch']} "
                f"prompt_len={self.meta['prompt_len']}, got {prompt.shape}")
        if tp + max_new > self.meta["cache_len"]:
            raise ValueError(f"{tp + max_new} positions exceed the "
                             f"exported cache_len {self.meta['cache_len']}")
        # seed=None must NOT collapse to RandomState(0): that made every
        # "unseeded" sampling call deterministically identical. None lets
        # RandomState pull fresh OS entropy.
        rng = np.random.RandomState(seed)

        def sample(logits):
            if temperature <= 0:
                return logits.argmax(-1).astype(np.int32)
            z = np.asarray(logits, np.float64) / temperature
            z = z - z.max(-1, keepdims=True)
            p = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
            return np.asarray([rng.choice(p.shape[-1], p=row)
                               for row in p], np.int32)

        self._m_requests.inc()
        self._last_generate = time.perf_counter()
        decode_flops = self.cost_analysis.get("decode", {}).get("flops")
        t0 = time.perf_counter()
        logits, cache = self._prefill.call(
            self.params, jnp.asarray(prompt, jnp.int32))
        # np.asarray inside sample() is the host sync — latency measured
        # after it is the latency a caller actually observes
        toks = [sample(np.asarray(logits))]
        self._m_prefill.inc()
        self._m_prefill_s.observe(time.perf_counter() - t0)
        self._m_tokens.inc(b)
        done = (toks[0] == eos_id) if eos_id is not None else None
        # device-side position carry: pos advances with an on-device add
        # instead of re-uploading a fresh host scalar every token
        pos = jnp.asarray(tp, jnp.int32)
        for i in range(max_new - 1):
            if eos_id is not None and done.all():
                break          # every row terminated: drop the wasted
            t0 = time.perf_counter()   # lockstep tail steps
            logits, cache = self._decode.call(
                self.params, cache, jnp.asarray(toks[-1], jnp.int32),
                pos)
            pos = pos + 1
            tok = sample(np.asarray(logits))
            if eos_id is not None:
                # rows already finished pad with eos_id from here on
                tok = np.where(done, eos_id, tok).astype(np.int32)
                done = done | (tok == eos_id)
            toks.append(tok)
            dt = time.perf_counter() - t0
            self._m_decode.inc()
            self._m_decode_s.observe(dt)
            self._m_tokens.inc(b)
            if self._peak_flops:
                mfu = _costs.mfu(decode_flops, dt, self._peak_flops)
                if mfu is not None:
                    self._m_mfu.set(mfu)
        return np.concatenate([prompt,
                               np.stack(toks, axis=1)], axis=1)


def _program(exported, donate_pool: bool = False):
    """The call of one of the engine's exported modules.
    ``donate_pool`` donates argument 1 (the KV pool) at the call, so
    the program's pool writes land in the caller's buffer: the paged
    decode and prefill programs, whose caller rebinds the pool from
    every result."""
    import jax
    if not donate_pool:
        return exported.call

    # the name is the traced module's: a bare ``exported.call``
    # runs as ``jit_call_exported`` and the benchmark's readers
    # find the decode program by that name
    def call_exported(*args):
        return exported.call(*args)

    return jax.jit(call_exported, donate_argnums=(1,))


def _conform(params, exported):
    """``params`` as ``exported`` (a step program, the tree its first
    argument) takes them: every leaf a device array in the dtype of
    the program's own input aval. Leaves in another dtype are cast in
    ONE jitted call (seconds in ``artifact/conform``), a host leaf in
    the right dtype is put on the device, a device array in the right
    dtype is the one handed in (``is``): for a float32 config, an int8
    tree, leaves that come from the artifact itself or programs
    exported from float32 leaves there is nothing to cast. A tree of
    another structure than the program's raises."""
    import jax
    (want, *_), _ = jax.tree_util.tree_unflatten(exported.in_tree,
                                                 exported.in_avals)
    with _trace.trace_scope("artifact/conform"):
        avals, treedef = jax.tree_util.tree_flatten(want)
        leaves = treedef.flatten_up_to(params)
        off = [i for i, (x, a) in enumerate(zip(leaves, avals))
               if x.dtype != a.dtype]
        if off:
            dtypes = [avals[i].dtype for i in off]
            cast = jax.jit(lambda xs: [x.astype(d)
                                       for x, d in zip(xs, dtypes)])
            for i, x in zip(off, cast([leaves[i] for i in off])):
                leaves[i] = x
        leaves = [x if isinstance(x, jax.Array) else jax.device_put(x)
                  for x in leaves]
        return jax.block_until_ready(treedef.unflatten(leaves))


_BF16 = "@bfloat16"     # numpy's .npy format has no bfloat16: such a
#                         leaf is stored as its uint16 bits under a
#                         marked key


def _npz_leaves(flat: dict) -> dict:
    return {(k + _BF16 if v.dtype.name == "bfloat16" else k):
            (v.view(np.uint16) if v.dtype.name == "bfloat16" else v)
            for k, v in flat.items()}


def _load_params(f):
    """``f``: the ``.npz`` as a seekable file object (a member of the
    open tar: decoded where it lies, never read whole beside its own
    arrays)."""
    import ml_dtypes
    with np.load(f, allow_pickle=False) as z:
        return _unflatten({
            (k[:-len(_BF16)] if k.endswith(_BF16) else k):
            (z[k].view(ml_dtypes.bfloat16) if k.endswith(_BF16) else z[k])
            for k in z.files})


def load_lm_artifact(path: str) -> LMServer:
    """Set-up spans (``utils.stat.global_stats``): ``artifact/read`` the
    tar's index, ``meta.json`` and the exported modules' bytes,
    ``artifact/params`` the ``.npz`` members read out of the open tar
    and decoded (one pass: the weights are never held twice),
    ``artifact/programs`` every exported module deserialised (here the
    lockstep pair, the engine's in :meth:`LMServer.engine`)."""
    with tarfile.open(path, "r") as tar:
        with _trace.trace_scope("artifact/read"):
            npz = {m.name: m for m in tar.getmembers()
                   if m.name.endswith(".npz")}
            members = {m.name: tar.extractfile(m).read()
                       for m in tar.getmembers() if m.name not in npz}
        meta = json.loads(members["meta.json"])
        if meta["format_version"] > FORMAT_VERSION:
            raise ValueError(
                f"artifact format {meta['format_version']} newer "
                f"than this loader ({FORMAT_VERSION})")
        with _trace.trace_scope("artifact/params"):
            params = _load_params(tar.extractfile(npz["params.npz"]))
            draft_params = None
            if "draft_params.npz" in npz:
                draft_params = _load_params(
                    tar.extractfile(npz["draft_params.npz"]))
    engine_bins = {k: v for k, v in members.items()
                   if k.startswith("engine_")}
    with _trace.trace_scope("artifact/programs"):
        return LMServer(meta, params, members.get("prefill.bin"),
                        members.get("decode.bin"), engine_bins=engine_bins,
                        draft_params=draft_params)

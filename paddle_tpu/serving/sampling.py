"""On-device token sampling for the continuous-batching decode engine.

The legacy LMServer samples host-side: every decoded token ships the
full ``[B, vocab]`` logits to numpy and loops ``RandomState.choice`` per
row — exactly the host/device sync PAPERS' non-GPU-inference field study
(arxiv 2607.08215) names as the decode-loop throughput killer. Here the
sampler is a pure jnp function that runs INSIDE the compiled decode
step, so only the sampled ids ``[B] int32`` ever cross to the host.

Per-slot controls are runtime vectors (static shapes, one compile):

- ``temperature`` [B] float32 — ``<= 0`` means greedy argmax for that
  row; the categorical draw still happens but is discarded by a
  ``where``, keeping the program shape-identical for any mix.
- ``top_k`` [B] int32 — ``<= 0`` (or ``>= vocab``) disables filtering.
  A runtime k can't use ``lax.top_k`` (static k), and a sort of the
  row for that one number costs a quarter of a decode step at a
  vocabulary's width: the k-th largest value is found by selection
  (``ops/topk.kth_largest``: 32 compare-and-count passes, bitwise the
  value a sort would give) and everything below it is masked to
  ``-inf``; ties at the threshold survive, matching the usual top-k
  convention.
"""

import jax
import jax.numpy as jnp

from paddle_tpu.ops import topk as ops_topk


def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperature: jax.Array, top_k: jax.Array) -> jax.Array:
    """logits [B, V] fp32, per-slot temperature [B] / top_k [B] →
    sampled ids [B] int32 (greedy rows use argmax, first-index ties —
    the same convention as the host-side legacy path)."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)[:, None]
    kth = ops_topk.kth_largest(logits, jnp.maximum(k, 1))    # [B, 1]
    keep = (k <= 0) | (logits >= kth)
    z = jnp.where(keep, logits, -jnp.inf)
    t = jnp.where(temperature > 0, temperature, 1.0)  # div-safe for
    z = z / t[:, None].astype(jnp.float32)            # greedy rows
    sampled = jax.random.categorical(key, z, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def spec_accept(sampled: jax.Array, draft: jax.Array,
                valid: jax.Array) -> jax.Array:
    """Accept/reject fold of one speculative verify window: ``sampled``
    [B, W] are the TARGET's own tokens at each window position (greedy
    argmax or a categorical draw, per row), ``draft`` [B, W-1] the
    proposals those positions were conditioned on, ``valid`` [B] the
    usable window rows. Returns ``n`` [B]: how many leading sampled
    tokens are emitted — position j+1's sample only counts if every
    draft token before it matched (``cumprod`` of the leading run), so
    ``n = 1 + run`` emits the accepted drafts plus exactly one
    correction/bonus token. Because an accepted draft token EQUALS the
    target's sample at its position, the emitted tokens are always
    ``sampled[:, :n]`` — distribution-exact for sampled rows, bitwise
    the target-only sequence for greedy rows."""
    B, W = sampled.shape
    if W == 1:
        return jnp.minimum(jnp.ones((B,), jnp.int32),
                           valid.astype(jnp.int32))
    m = ((sampled[:, :W - 1] == draft)
         & (jnp.arange(1, W, dtype=jnp.int32)[None, :]
            < valid[:, None]))
    run = jnp.cumprod(m.astype(jnp.int32), axis=1).sum(axis=1)
    return jnp.minimum(1 + run, valid).astype(jnp.int32)


def spec_verify_tokens(logits: jax.Array, draft: jax.Array,
                       key: jax.Array, temperature: jax.Array,
                       top_k: jax.Array, valid: jax.Array):
    """Verify-window sampling + accept/reject: logits [B, W, V] from
    ``transformer.verify_step_paged``, draft [B, W-1] proposals,
    per-slot temperature/top_k [B] (broadcast over the window), valid
    [B] usable rows → (sampled [B, W] int32, n_emitted [B] int32).
    Each window row samples through :func:`sample_tokens` — the same
    greedy/top-k/categorical conventions as the decode step, over the
    same vocab axis length, so greedy rows are bitwise the target-only
    engine's argmax."""
    B, W, V = logits.shape
    X = sample_tokens(logits.reshape(B * W, V), key,
                      jnp.repeat(temperature, W),
                      jnp.repeat(top_k, W)).reshape(B, W)
    return X, spec_accept(X, draft, valid)


def _prefill_live(dequant):
    """Prefill-side weight resolution: an explicit ``dequant`` wins;
    otherwise {"q8","scale"} trees dequantize wholesale (prefill is
    compute-bound — one fp32 materialization amortizes over the whole
    chunk, unlike the weight-read-bound decode step, which handles q8
    natively inside its layer scan)."""
    from paddle_tpu.models import transformer
    from paddle_tpu.ops import q8 as ops_q8

    def _live(params):
        if dequant is not None:
            return dequant(params)
        if transformer._blocks_quantized(params):
            return ops_q8.dequantize_tree(params)
        return params

    return _live


def _decode_live(dequant):
    """Decode-side weight resolution: {"q8","scale"} trees pass through
    UNTOUCHED (the decode steps dequantize in-scan — pre-dequantizing
    here would rebuild the fp32 stack per token, the 4-byte-read
    regression this path exists to kill); a custom ``dequant`` still
    applies to non-quantized trees."""
    from paddle_tpu.models import transformer

    def _live(params):
        if transformer._blocks_quantized(params):
            return params
        return dequant(params) if dequant is not None else params

    return _live


def _epilogue(mode):
    """The sampling tail of a step program under the resolved
    ``PADDLE_TPU_PALLAS`` mode: ``sample_tokens`` for ``off``, the
    Pallas ``fused_sample`` kernel otherwise. Both find the top-k
    threshold by the same selection (``ops/topk.kth_largest``) and
    compare it as a float, so the greedy ids and the kept top-k set
    are the same in both, ``-0.0`` / ``+0.0`` ties included; the
    categorical draw matches in distribution (another stream). Nothing
    falls back: a backend that cannot compile the kernel fails the
    compile."""
    from paddle_tpu.ops.pallas import decode as _pallas_decode
    from paddle_tpu.ops.pallas import policy as _pallas_policy
    path = _pallas_policy.kernel_path(mode)

    def tail(logits, seed, temperature, top_k):
        _pallas_policy.note_path("sampler", path)
        if mode == "off":
            key = jax.random.PRNGKey(seed)
            return sample_tokens(logits, key, temperature, top_k)
        return _pallas_decode.fused_sample(
            logits, seed, temperature, top_k,
            interpret=(mode == "interpret"))

    return tail


def _recorded(fn, paths, name):
    """``fn`` with every kernel site it traces recorded into
    ``paths[name(*args)]`` — the per-compiled-program record of what
    was actually placed (``policy.record_paths``). ``name`` maps the
    call's arguments to the program's name, so shape-specialized
    programs (one per chunk bucket and context span) each get their
    own entry."""
    from paddle_tpu.ops.pallas import policy as _pallas_policy

    def wrapped(*args):
        with _pallas_policy.record_paths(
                paths.setdefault(name(*args), {})):
            return fn(*args)

    return wrapped


def paged_step_fns(cfg, block_size: int, dequant=None, pallas=None):
    """(prefill_chunk_fn, decode_fn) for the PAGED block-pool engine —
    compiled once per chunk bucket / once for decode, and exported by
    ``save_lm_artifact`` as the format-v4 modules.

    prefill_fn(params, pool, tokens [1, C], length (), pages [P],
               temperature (), top_k (), seed ()) → (token (), pool)
    decode_fn(params, pool, tokens [B], pos [B], active [B] bool,
              pages [B, P], temperature [B], top_k [B], seed ())
              → (tokens [B], pool)

    Both programs update the pool IN PLACE when their caller donates
    it (argument 1): decode carries it through its layer loop and
    scatters the step's rows into it, prefill writes the chunk's spans
    by ``dynamic_update_slice`` after its scan, and both read context
    by gathering whole pages where they lie. The engine donates at
    ``jax.jit(..., donate_argnums=(1,))`` and, for an artifact, around
    ``Exported.call`` (``io/lm_serving``), and rebinds its pool from
    every result: the pool passed in is dead after the call.

    The chunk's context length is implied by the SHAPES: the pages
    vector covers context + chunk, so each (chunk bucket, context
    pages) pair is its own compiled program. Sampling runs inside both:
    the prefill token only matters on a prompt's FINAL chunk (the
    engine discards the others), but sampling unconditionally keeps the
    exported signature uniform.

    ``pallas`` resolves the ``PADDLE_TPU_PALLAS`` policy (explicit arg
    > env > auto): unless it resolves ``off`` (the pure-XLA path), the
    decode step's attention runs the flash-decode kernel over the pool,
    the chunk prefill runs the ``ops/pallas/prefill.py`` pair (chunk
    attention off the pool + span-write kernel), and both sampling
    tails the fused epilogue — placed or raising, never degrading. Both
    closures carry ``.kernel_paths``: per compiled program (``decode``,
    ``prefill_<C>_<P>``), the path each kernel site placed, filled in
    as the program is traced. ``dequant``
    applies to PREFILL only — decode consumes {"q8","scale"} trees
    natively (in-scan dequant, 1-byte weight reads per token). The
    pool may be QUANTIZED (``init_block_pool(kv_dtype=...)``): both
    step programs detect the layout from the pytree and carry the
    write-time KV quantization + dequantizing reads on every path.
    """
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import policy as _pallas_policy

    mode = _pallas_policy.pallas_mode(pallas)
    if cfg.skeleton != "gpt2":
        if dequant is not None:
            transformer.require_gpt2(cfg, "int8 weights")
        return _skeleton_paged_step_fns(cfg, block_size, mode)
    _live = _prefill_live(dequant)
    _live_d = _decode_live(dequant)
    tail = _epilogue(mode)

    def prefill_fn(params, pool, tokens, length, pages,
                   temperature, top_k, seed):
        logits, pool = transformer.prefill_into_blocks(
            _live(params), pool, tokens, length, pages, cfg,
            block_size=block_size, pallas=mode)
        tok = tail(logits, seed, jnp.reshape(temperature, (1,)),
                   jnp.reshape(top_k, (1,)))
        return tok[0], pool

    def decode_fn(params, pool, tokens, pos, active, pages, temperature,
                  top_k, seed):
        logits, pool = transformer.decode_step_paged(
            _live_d(params), pool, tokens, pos, active, pages, cfg,
            block_size=block_size, pallas=mode)
        return tail(logits, seed, temperature, top_k), pool

    paths = {}
    prefill_fn = _recorded(
        prefill_fn, paths, lambda p, c, tokens, length, pages, *_:
        f"prefill_{tokens.shape[1]}_{pages.shape[0]}")
    decode_fn = _recorded(decode_fn, paths, lambda *_: "decode")
    prefill_fn.kernel_paths = decode_fn.kernel_paths = paths
    return prefill_fn, decode_fn


def _skeleton_paged_step_fns(cfg, block_size: int, mode: str):
    """``paged_step_fns`` for a skeleton other than "gpt2"
    (``transformer.skeleton_module``): where the skeleton keeps rows
    per slot (``SLOT_STATE``: ``gated_hybrid``) the chunk program also
    takes the ``slot`` whose rows it updates (after ``pages``), and
    both programs append the expert layer's three counts (the
    skeleton's ``_run_layers``) to the ids they return (``token [1 +
    3]``, ``tokens [B + 3]``, int32), so the engine reads them back in
    the transfer it already makes."""
    from paddle_tpu.models import transformer
    tail = _epilogue(mode)

    def prefill_fn(params, pool, tokens, length, pages, *rest):
        *slot, temperature, top_k, seed = rest
        logits, pool, stats = transformer.prefill_into_blocks(
            params, pool, tokens, length, pages, cfg,
            block_size=block_size, pallas=mode,
            slot=slot[0] if slot else None, return_stats=True)
        tok = tail(logits, seed, jnp.reshape(temperature, (1,)),
                   jnp.reshape(top_k, (1,)))
        return jnp.concatenate([tok.astype(jnp.int32), stats]), pool

    def decode_fn(params, pool, tokens, pos, active, pages, temperature,
                  top_k, seed):
        logits, pool, stats = transformer.decode_step_paged(
            params, pool, tokens, pos, active, pages, cfg,
            block_size=block_size, pallas=mode, return_stats=True)
        ids = tail(logits, seed, temperature, top_k)
        return jnp.concatenate([ids.astype(jnp.int32), stats]), pool

    paths = {}
    prefill_fn = _recorded(
        prefill_fn, paths, lambda p, c, tokens, length, pages, *_:
        f"prefill_{tokens.shape[1]}_{pages.shape[0]}")
    decode_fn = _recorded(decode_fn, paths, lambda *_: "decode")
    prefill_fn.kernel_paths = decode_fn.kernel_paths = paths
    return prefill_fn, decode_fn


def _spec_epilogue(mode):
    """The accept/reject sampling tail of a verify program under the
    resolved ``PADDLE_TPU_PALLAS`` mode: :func:`spec_verify_tokens` for
    ``off``, the Pallas ``fused_sample`` kernel per window row + the
    accept fold (``ops.pallas.decode.fused_spec_verify``) otherwise.
    Both emit the same greedy tokens exactly (the PR-9 fused_sample
    contract), so the spec engine's bitwise-greedy promise holds on
    either path."""
    from paddle_tpu.ops.pallas import decode as _pallas_decode
    from paddle_tpu.ops.pallas import policy as _pallas_policy
    path = _pallas_policy.kernel_path(mode)

    def tail(logits, draft, seed, temperature, top_k, valid):
        _pallas_policy.note_path("sampler", path)
        if mode == "off":
            key = jax.random.PRNGKey(seed)
            return spec_verify_tokens(logits, draft, key, temperature,
                                      top_k, valid)
        return _pallas_decode.fused_spec_verify(
            logits, draft, seed, temperature, top_k, valid,
            interpret=(mode == "interpret"))

    return tail


def paged_spec_fns(cfg, draft_cfg, block_size: int, spec_k: int,
                   dequant=None, pallas=None, paths=None):
    """The speculative-decoding program set for the paged spec engine —
    the three DRAFT-side programs plus the target VERIFY, compiled next
    to (never instead of) the ``paged_step_fns`` pair. ``spec_k`` fixes
    the proposal depth; the verify window is ``W = spec_k + 1`` rows
    (last accepted token + the k proposals).

    Returns a dict of closures:

    - ``propose(draft_params, draft_pool, last [B], pos [B],
      active [B], valid [B], pages [B, P])`` → (proposals [B, k]
      int32, draft_pool) — k GREEDY draft decode steps fused into one
      program via ``lax.scan`` (one dispatch per engine step, the
      host-overhead half of the spec win; the draft's small weights
      are re-read per scan step, which is what makes a small draft
      the right draft). Scan step j's pool write is masked to
      ``j < valid``: the engine allocates pages only through
      ``pos + valid - 1``, and an unmasked write past that would land
      through the zeroed page-table tail in ANOTHER slot's physical
      block 0 rows of the draft pool. Proposals past the mask are
      garbage and unused (the verify window masks the same rows).
    - ``verify(params, pool, window [B, W], pos [B], valid [B],
      active [B], pages, temperature [B], top_k [B], seed)`` →
      (sampled [B, W], n_emitted [B], pool) — ONE batched W-token pass
      (``transformer.verify_step_paged``) with the accept/reject
      sampling tail fused in; only the small int outputs cross to host.
    - ``draft_verify(draft_params, draft_pool, window [B, W], pos,
      valid, active, pages)`` → draft_pool — the draft-side forced
      window write (no sampling, logits dead-coded): keeps the draft
      pool position-faithful when a preempted request replays known
      tokens, where the propose program's own proposals would diverge
      from the forced history.
    - ``draft_prefill(draft_params, draft_pool, tokens [1, C], length,
      pages [P])`` → draft_pool — the draft's chunk prefill on the SAME
      chunk grid/page vectors as the target's (one draft program per
      (bucket, span) the target compiles — the draft's own program
      set), logits discarded (the sampled first token is the target
      prefill's).

    ``dequant``/``pallas`` follow ``paged_step_fns`` semantics and
    apply to the TARGET side; the draft runs its params as given (pass
    a quantized draft tree for int8 draft weights — decode-side
    consumption is native). ``paths`` is the kernel-path record to fill
    (pass the target pair's ``kernel_paths`` so one dict covers the
    engine's whole program set); each closure carries it as
    ``.kernel_paths``."""
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import policy as _pallas_policy

    for c in (cfg, draft_cfg):
        transformer.require_gpt2(c, "speculative decoding")

    mode = _pallas_policy.pallas_mode(pallas)
    _live_d = _decode_live(dequant)
    spec_tail = _spec_epilogue(mode)
    k = int(spec_k)
    if k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")

    def propose_fn(draft_params, draft_pool, last, pos, active, valid,
                   pages):
        valid = jnp.asarray(valid, jnp.int32)

        def body(carry, j):
            pool, toks, p = carry
            lg, pool = transformer.decode_step_paged(
                draft_params, pool, toks, p, active & (j < valid),
                pages, draft_cfg, block_size=block_size, pallas=mode)
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return (pool, nxt, p + 1), nxt

        (draft_pool, _, _), props = jax.lax.scan(
            body, (draft_pool, jnp.asarray(last, jnp.int32),
                   jnp.asarray(pos, jnp.int32)),
            jnp.arange(k, dtype=jnp.int32))
        return jnp.transpose(props), draft_pool        # [k, B] -> [B, k]

    def verify_fn(params, pool, window, pos, valid, active, pages,
                  temperature, top_k, seed):
        logits, pool = transformer.verify_step_paged(
            _live_d(params), pool, window, pos, valid, active, pages,
            cfg, block_size=block_size)
        sampled, n = spec_tail(logits, window[:, 1:], seed, temperature,
                               top_k, valid)
        return sampled, n, pool

    def draft_verify_fn(draft_params, draft_pool, window, pos, valid,
                        active, pages):
        _, draft_pool = transformer.verify_step_paged(
            draft_params, draft_pool, window, pos, valid, active,
            pages, draft_cfg, block_size=block_size)
        return draft_pool

    def draft_prefill_fn(draft_params, draft_pool, tokens, length,
                         pages):
        _, draft_pool = transformer.prefill_into_blocks(
            draft_params, draft_pool, tokens, length, pages, draft_cfg,
            block_size=block_size, pallas=mode)
        return draft_pool

    paths = {} if paths is None else paths
    fns = {"propose": propose_fn, "verify": verify_fn,
           "draft_verify": draft_verify_fn}
    fns = {n: _recorded(f, paths, lambda *_, n=n: n)
           for n, f in fns.items()}
    fns["draft_prefill"] = _recorded(
        draft_prefill_fn, paths, lambda p, c, tokens, length, pages:
        f"draft_prefill_{tokens.shape[1]}_{pages.shape[0]}")
    for f in fns.values():
        f.kernel_paths = paths
    return fns

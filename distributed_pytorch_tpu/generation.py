"""Autoregressive text generation with a KV cache — the inference side of the
LM family.

No reference analog (the reference is a training tutorial); a complete
framework needs a sampling path, and on TPU it must be a SINGLE compiled
program, not a Python token loop: the whole generate pass is one
``lax.fori_loop`` inside ``jit``, so XLA pipelines the per-token steps and the
host is never in the loop.

Mechanics:

* the model is cloned with ``decode=True`` (:class:`..models.transformer
  .TransformerLM`); each attention layer carries ``cached_key``/``cached_value``
  buffers sized ``[B, max_len, H, D]`` plus a running ``cache_index``;
* each loop step feeds ONE token per sequence, updates the caches in place
  (functionally — donated buffers under jit), and samples the next token
  (greedy, temperature, optional top-k);
* the common prompt prefix (up to the shortest row's length) is PREFILLED
  in one batched forward — a single MXU-friendly pass instead of
  ``min_len`` serial single-token steps (``Attention._decode_step`` handles
  multi-token chunks: per-position RoPE and an intra-chunk causal mask);
* past the prefill, ragged prompts need no special casing: while ``t`` is
  inside a row's prompt the sampled token is discarded in favor of the
  prompt token, so one loop covers every row.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_pytorch_tpu.ops.quant import dequantize_pytree


def truncate_logits(
    logits: jnp.ndarray, top_k: int, top_p: float
) -> jnp.ndarray:
    """Apply top-k and/or nucleus truncation with ONE descending sort
    (the decode hot loop calls this per token; sorting the vocab twice —
    once for the k-th threshold, once for the nucleus cumsum — would be
    pure waste). Semantically identical to top-k masking followed by
    :func:`top_p_filter` over the renormalized survivors: the nucleus
    probabilities are computed over the top-k prefix of the sorted row,
    which IS the renormalized survivor distribution."""
    if top_k <= 0 and not (0.0 < top_p < 1.0):
        return logits
    # top_k beyond the vocab means "keep everything" (the pre-fusion code
    # clamped the same way via negative-index sort slicing).
    top_k = min(top_k, logits.shape[-1])
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    if top_k > 0:
        threshold = sorted_desc[..., top_k - 1 : top_k]  # k-th largest
        # Tie-inclusive survivor set, exactly like masking then re-sorting:
        # entries equal to the k-th value all survive (matters for bf16 /
        # quantized logits where ties are common), so the nucleus below is
        # computed over the same renormalized distribution the sequential
        # top-k -> top_p_filter composition sees.
        masked_sorted = jnp.where(
            sorted_desc >= threshold, sorted_desc, -jnp.inf
        )
    else:
        threshold = sorted_desc[..., -1:]  # keeps everything
        masked_sorted = sorted_desc
    if 0.0 < top_p < 1.0:
        probs = jax.nn.softmax(masked_sorted, axis=-1)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        n_keep = jnp.sum(keep, axis=-1, keepdims=True)  # >= 1
        # keep is a prefix of the survivor prefix, where masked_sorted ==
        # sorted_desc — so indexing the unmasked sort is safe.
        nucleus_thr = jnp.take_along_axis(sorted_desc, n_keep - 1, axis=-1)
        threshold = jnp.maximum(threshold, nucleus_thr)
    return jnp.where(logits < threshold, -jnp.inf, logits)


def top_p_filter(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Nucleus filter: mask ``logits`` ([..., V]) to the smallest set of
    tokens whose cumulative probability reaches ``top_p``, returning the
    filtered logits (masked entries at ``-inf``).

    The token that crosses the threshold is INCLUDED (the kept mass is
    always >= top_p), and at least one token always survives — the
    standard Holtzman et al. convention. Ties at the boundary logit are all
    kept. Delegates to :func:`truncate_logits` (top_k disabled) so the
    sort/cumsum/threshold convention has exactly ONE implementation —
    ``tests/test_generation.py`` pins the equivalence the public name
    promises."""
    return truncate_logits(logits, 0, top_p)


def make_sampler(temperature: float, top_k: int, top_p: float):
    """Return ``sample(logits [B, V], rng) -> tokens [B]`` for a STATIC
    sampling config: greedy argmax at ``temperature <= 0``, else categorical
    over the temperature-scaled, top-k/top-p-truncated logits. This is THE
    next-token rule — ``generate``'s loop body and the serving engine's
    continuous-batching decode step both call it, so offline and served
    sampling can never drift apart. ``bias`` is an optional additive
    ``[B, V]`` logit offset (per-request logit-bias / grammar masks);
    a zeros bias is a bitwise no-op on the sampled tokens."""

    def sample(logits, step_rng, bias=None):
        if bias is not None:
            logits = logits + bias
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = truncate_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(step_rng, scaled).astype(jnp.int32)

    return sample


def make_row_sampler(top_k: int, top_p: float):
    """Return ``sample(logits [B, V], temps [B], keys [B, 2], bias
    [B, V]) -> tokens [B]`` — the PER-ROW variant of :func:`make_sampler`
    for the serving engine's one compiled decode program, where each
    batch row carries its own temperature (``<= 0`` = greedy), fold-in
    RNG key, and additive logit bias (zeros = bitwise no-op; per-request
    logit-bias and grammar-mask rows land here as data, never as a
    recompile). Same math, same order of operations as the static rule,
    so mods-off serving stays token-identical to offline decode."""

    def sample(logits, temps, keys, bias):
        logits = logits + bias
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        safe_t = jnp.where(temps > 0, temps, 1.0)
        scaled = truncate_logits(logits / safe_t[:, None], top_k, top_p)
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

    return sample


def host_prng_key(seed: int) -> np.ndarray:
    """``jax.random.key_data(jax.random.PRNGKey(seed))`` as host data,
    ``uint32[2]``, with no dispatch to any device: threefry's seeding is
    ``[seed >> 32, seed & 0xFFFFFFFF]`` of the seed as JAX reads a Python
    int — an int64 (beyond it ``OverflowError``, as ``PRNGKey`` raises),
    cut to its low 32 bits unless ``jax_enable_x64`` is on. The serving
    engine keeps one of these a request and folds the step count in inside
    its compiled program (:func:`fold_row_keys`)."""
    bits = int(np.int64(seed)) & 0xFFFFFFFFFFFFFFFF
    high = bits >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([high, bits & 0xFFFFFFFF], np.uint32)


def fold_row_keys(staged):
    """``staged [B, 3] uint32`` — a row's base key (:func:`host_prng_key`)
    and, beside it, the index of the token the row is about to draw — to
    the ``[B, 2]`` per-step keys ``fold_in(base, index)``. Traced inside
    the serving programs, so no key costs the host a device round trip;
    the same integer arithmetic ``jax.random.fold_in`` does anywhere, bit
    for bit."""
    return jax.vmap(jax.random.fold_in)(staged[:, :2], staged[:, 2])


def decode_token_step(
    decode_model, params, cache, current, *, mutable=("cache",),
    **apply_kwargs,
):
    """ONE decode-mode forward: apply ``decode_model`` on ``current``
    ([B, T_step] token ids) against ``cache``, returning ``(last_logits,
    cache)`` where ``last_logits`` is ``[B, V]`` at the final position.
    A ``mutable`` that names more collections than ``"cache"`` adds a third
    result: what the model wrote to those, by collection.

    This is the single-token step extracted from ``generate``'s loop body so
    the serving engine (serving/engine.py) drives EXACTLY the same compiled
    math — dequant-inside-the-step and all. Extra ``apply_kwargs``
    (``block_tables``/``seq_lens``) flow to the model for the paged-cache
    path; callers that only need the cache update may discard the logits
    (XLA dead-code-eliminates the LM head when the output is unused)."""
    dtype = getattr(decode_model, "dtype", jnp.bfloat16)
    logits, updated = decode_model.apply(
        {"params": dequantize_pytree(params, dtype), "cache": cache},
        current,
        mutable=list(mutable),
        **apply_kwargs,
    )
    out = logits[:, -1, :], updated["cache"]
    if len(mutable) > 1:
        # A collection nobody wrote to in this call is not in ``updated``.
        out += ({k: updated.get(k, {}) for k in mutable if k != "cache"},)
    return out


def decode_chunk_step(decode_model, params, cache, current, **apply_kwargs):
    """Like :func:`decode_token_step` but keeps EVERY position's logits:
    ``(logits [B, T_step, V], cache)``. This is the speculative VERIFY
    forward — the target scores all ``gamma`` proposal positions in one
    chunked decode (per-position RoPE + intra-chunk causal mask come from
    the decode path itself), so acceptance is decided for the whole chunk
    from a single MXU-shaped program instead of ``gamma`` bandwidth-shaped
    single-token steps."""
    dtype = getattr(decode_model, "dtype", jnp.bfloat16)
    logits, updated = decode_model.apply(
        {"params": dequantize_pytree(params, dtype), "cache": cache},
        current,
        mutable=["cache"],
        **apply_kwargs,
    )
    return logits, updated["cache"]


def batch_sharding_placer(mesh: Mesh, data_axis: str, batch: int):
    """``(place, batch_sh, replicated)`` — THE decode placement rule,
    shared by :func:`generate`, :func:`beam_search`, and
    ``speculative.speculative_generate`` so the heuristic lives once:
    abstract arrays leading with the batch dim (tokens, KV caches and
    their scales) shard ``P(data_axis)``; scalars (``cache_index``) and
    anything else replicate."""
    batch_sh = NamedSharding(mesh, P(data_axis))
    replicated = NamedSharding(mesh, P())

    def place(s):
        sh = batch_sh if s.ndim > 0 and s.shape[0] == batch else replicated
        return jnp.zeros(s.shape, s.dtype, device=sh)

    return place, batch_sh, replicated


def bucketed_prefill_len(prompt_lengths) -> int:
    """Static prefill length, computed HOST-SIDE before any device placement
    (a batch-sharded array could span non-addressable devices). Clamped to
    1: a zero-length row means position 0 is already generated, so the
    serial loop must start at t=0 (the loop body at position t decides
    token t+1 — the last prefix token must go through the loop to produce
    the first prediction).

    Bucketed DOWN to a power of two: prefill_len is part of the
    compile-cache key, and with naturally varied prompt lengths an exact
    value would compile a fresh decode executable per distinct
    batch-minimum (thrashing the lru cache). Rounding down is always safe —
    positions between the bucketed prefill and each row's true prompt
    length are replayed by the serial loop's keep-prompt path — and costs
    at most 2x the prefill tokens while capping the variants at log2(T).
    Shared by :func:`generate` and ``speculative.speculative_generate`` so
    both paths bucket identically."""
    min_len = int(np.min(np.asarray(prompt_lengths)))
    if min_len < 0:
        raise ValueError(f"prompt lengths must be >= 0, got {min_len}")
    if min_len == 0:
        # A zero-length row has NO common prefix: any batched prefill would
        # feed that row's pad tokens as if they were prompt, corrupting its
        # cache before the serial loop's keep-prompt logic can take over.
        # Everything runs through the serial loop instead.
        return 1
    return 1 << (min_len.bit_length() - 1)


def generate(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    prompt_lengths: Optional[jnp.ndarray] = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
    rng: Optional[jax.Array] = None,
    pad_token: int = 0,
    mesh: Optional[Mesh] = None,
    data_axis: str = "data",
    param_shardings=None,
    quantize: bool = False,
    quantized_cache: bool = False,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` continuations for ``prompt`` ``[B, T0]``.

    ``temperature=0`` is greedy argmax; otherwise softmax sampling at the
    given temperature, optionally truncated to the ``top_k`` most likely
    tokens and/or the ``top_p`` nucleus (smallest set of tokens reaching
    ``top_p`` cumulative mass; 0 or >= 1 disables). When both are given,
    top-k truncates first and the nucleus is computed over the renormalized
    survivors. ``prompt_lengths`` ([B]) supports ragged prompts padded to T0
    with ``pad_token`` — generation for each row starts after its own length.
    Returns ``[B, T0 + max_new_tokens]`` token ids.

    With ``mesh``, decoding runs sharded: tokens and every KV-cache buffer are
    placed ``P(data_axis)`` (batch-sharded — at ``[B, 32k, H, D]`` the cache,
    not the params, is the memory that matters), params are replicated unless
    ``param_shardings`` provides a placement: e.g. megatron TP via
    ``make_param_specs(params, TRANSFORMER_TP_RULES, mesh=mesh)`` from
    ``parallel.partitioning``, with each spec wrapped in
    ``NamedSharding(mesh, spec)`` — GSPMD then shards the per-token matmuls
    and the caches' head dim follows (see tests/test_generation.py).
    The decode hot loop itself feeds ONE token per step, so the flash kernel
    (built for long query blocks) does not apply; cache reads stay the
    einsum-over-cache path, which XLA fuses well at ``T_step=1``.

    ``quantize=True`` stores the matmul weights as int8 + per-channel scales
    (``ops.quant``, symmetric absmax) and dequantizes INSIDE the compiled
    decode loop — decode is HBM-bound on weight reads, so int8 halves the
    traffic on the quantized weights. Greedy outputs typically match the
    full-precision path exactly (see tests/test_quant.py).

    ``quantized_cache=True`` additionally stores the KV caches as int8 with
    per-(token, head) scales (``models.transformer.Attention``): at long
    context the ``[B, T, H, D]`` caches dominate decode memory and traffic,
    and this halves both. Composes with ``quantize`` and with the mesh path
    (the scale buffers lead with the batch dim, so they shard ``P(data)``).
    """
    clone_kw = {"decode": True}
    if quantized_cache:  # only models with the attribute support it
        clone_kw["quantized_cache"] = True
    decode_model = model.clone(**clone_kw)
    if quantize:
        from distributed_pytorch_tpu.ops.quant import (
            QuantTensor,
            quantize_pytree,
            quantize_shardings,
        )

        already = any(
            isinstance(leaf, QuantTensor)
            for leaf in jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, QuantTensor)
            )
        )
        if param_shardings is not None:
            if already:
                raise ValueError(
                    "pass the UNquantized params when combining quantize="
                    "True with param_shardings; the sharding tree is lifted "
                    "onto the quantized tree internally"
                )
            # Lift the param shardings onto the quantized tree (int8 q keeps
            # the kernel's sharding; per-channel scales drop contract axes).
            param_shardings = quantize_shardings(param_shardings, params)
        # Accept a pre-quantized tree (quantize_pytree run once by the
        # caller) so repeated generate() calls don't pay re-quantization.
        if not already:
            params = quantize_pytree(params)
            from distributed_pytorch_tpu.ops.quant import quant_coverage

            coverage = quant_coverage(params)
            if coverage < 0.5:
                import warnings

                warnings.warn(
                    f"quantize=True matched only {coverage:.0%} of param "
                    "elements — the quant rules likely don't cover this "
                    "model's kernels (see ops.quant.TRANSFORMER_QUANT_RULES); "
                    "decode will still read the unmatched weights in full "
                    "precision",
                    stacklevel=2,
                )
    batch, prompt_len = prompt.shape
    total_len = prompt_len + max_new_tokens
    if prompt_lengths is None:
        prompt_lengths = jnp.full((batch,), prompt_len, jnp.int32)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    # Size the KV caches from abstract shapes only — eval_shape traces init
    # without running it, so no throwaway params and no full-length forward.
    abstract = jax.eval_shape(
        decode_model.init,
        jax.random.PRNGKey(0),
        jnp.zeros((batch, total_len), jnp.int32),
    )["cache"]

    tokens0 = jnp.concatenate(
        [
            jnp.asarray(prompt, jnp.int32),
            jnp.full((batch, max_new_tokens), pad_token, jnp.int32),
        ],
        axis=1,
    )
    prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
    prefill_len = bucketed_prefill_len(prompt_lengths)

    if mesh is not None:
        place, batch_sh, replicated = batch_sharding_placer(
            mesh, data_axis, batch
        )
        cache = jax.tree_util.tree_map(place, abstract)
        tokens0 = jax.device_put(tokens0, batch_sh)
        prompt_lengths = jax.device_put(prompt_lengths, batch_sh)
        params = jax.device_put(params, param_shardings or replicated)
        rng = jax.device_put(rng, replicated)
    else:
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), abstract
        )

    run = _compiled_run(
        decode_model, total_len, float(temperature), int(top_k),
        float(top_p), prefill_len,
    )
    return run(params, tokens0, cache, prompt_lengths, rng)


@functools.lru_cache(maxsize=32)
def _compiled_run(
    decode_model,
    total_len: int,
    temperature: float,
    top_k: int,
    top_p: float = 0.0,
    prefill_len: int = 1,
):
    """Jitted decode loop, cached per (model config, length, sampling config,
    prefill length) so repeated generate() calls with the same shapes reuse
    the executable (flax modules are frozen dataclasses, hence hashable
    cache keys)."""
    sample = make_sampler(temperature, top_k, top_p)

    def run(params, tokens, cache, prompt_lengths, rng):
        batch = tokens.shape[0]

        if prefill_len > 1:
            # One batched forward over the common prefix: every row's tokens
            # at positions [0, prefill_len-1) are true prompt tokens (it is
            # the MINIMUM prompt length), so the caches fill in parallel and
            # the serial loop starts at prefill_len-1 with cache_index
            # already there — the same invariant (cache_index == t at body
            # entry) the single-token path maintains.
            chunk = tokens[:, : prefill_len - 1]
            _, cache = decode_token_step(decode_model, params, cache, chunk)

        def body(t, carry):
            tokens, cache, rng = carry
            current = jax.lax.dynamic_slice(tokens, (0, t), (batch, 1))
            # decode_token_step dequantizes (a no-op tree_map when nothing
            # is quantized) INSIDE the loop body: the int8->compute-dtype
            # convert is a producer each weight's consumer matmul fuses, so
            # the loop reads int8 from HBM.
            last_logits, cache = decode_token_step(
                decode_model, params, cache, current
            )
            rng, step_rng = jax.random.split(rng)
            proposed = sample(last_logits, step_rng)  # [B]
            # Inside each row's prompt, keep the prompt token; past it, take
            # the sample. (t+1 is the position being decided.)
            keep_prompt = (t + 1) < prompt_lengths
            existing = jax.lax.dynamic_slice(tokens, (0, t + 1), (batch, 1))[:, 0]
            next_token = jnp.where(keep_prompt, existing, proposed)
            tokens = jax.lax.dynamic_update_slice(
                tokens, next_token[:, None], (0, t + 1)
            )
            return tokens, cache, rng

        tokens, _, _ = jax.lax.fori_loop(
            prefill_len - 1, total_len - 1, body, (tokens, cache, rng)
        )
        return tokens

    # No donate_argnums: the cache lives its whole life INSIDE the fori_loop
    # carry, where XLA already updates it in place; it is not a jit output, so
    # donating its input buffer has nothing to alias against and only produced
    # a "Some donated buffers were not usable" warning every call.
    return jax.jit(run)


def generate_text_ids(model, params, prompt_ids, max_new_tokens, **kw) -> np.ndarray:
    """Convenience wrapper returning numpy ids."""
    return np.asarray(
        generate(model, params, jnp.asarray(prompt_ids), max_new_tokens, **kw)
    )


def beam_search(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    beam_size: int = 4,
    length_penalty: float = 0.0,
    mesh: Optional[Mesh] = None,
    data_axis: str = "data",
):
    """Fixed-length beam search over the KV-cache decode path: maintain the
    ``beam_size`` highest-log-probability continuations per batch row, one
    compiled ``fori_loop`` like :func:`generate`.

    Returns ``(tokens, scores)``: ``tokens`` is ``[B, beam, T0 + new]``
    sorted best-first, ``scores`` is ``[B, beam]`` — the summed next-token
    log-probabilities of each continuation, divided by
    ``(new_tokens) ** length_penalty`` when a penalty is set (0 = raw sum;
    GNMT-style normalization at 1.0). The best row's raw score EQUALS the
    full-forward log-prob sum of its tokens (pinned by test — the cache
    reorder below is the part that could silently break this).

    TPU shape: beams live flattened in the batch dim (``[B*beam, ...]``),
    so every model call is the same single-token decode the greedy path
    compiles; the per-step beam reorder is a ``jnp.take`` of every cache
    leaf along that dim (a gather XLA schedules well, but it does copy the
    cache each step — O(T^2) bytes over a decode, the classic beam cost).
    Uniform prompts only (no ``prompt_lengths``): ragged beams inside a
    prompt would force per-row divergence bookkeeping nobody needs —
    left-pad ragged batches instead. No EOS handling: this framework's
    models are tokenizer-free LMs; fixed-horizon search keeps shapes
    static (and XLA happy).

    With ``mesh``, the flattened ``[B*beam]`` dim shards ``P(data_axis)``
    (cache + tokens; params replicated). The per-step reorder gather's
    indices never cross a batch row's beam block, so when ``beam_size``
    beams land on one shard the gather stays device-local; either way the
    output is token-identical to the single-device run (pinned by test).
    """
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    decode_model = model.clone(decode=True)
    batch, prompt_len = prompt.shape
    total_len = prompt_len + max_new_tokens
    flat = batch * beam_size

    # Cache sized for [B]: the prefill runs ONCE per batch row and the
    # leaves are repeated to [B*beam] inside the compiled run — every beam
    # starts from the identical prompt, so prefilling flat would burn
    # beam_size x the prefill FLOPs and cache writes on bit-equal rows.
    abstract = jax.eval_shape(
        decode_model.init,
        jax.random.PRNGKey(0),
        jnp.zeros((batch, total_len), jnp.int32),
    )["cache"]
    tokens0 = jnp.concatenate(
        [
            jnp.repeat(jnp.asarray(prompt, jnp.int32), beam_size, axis=0),
            jnp.full((flat, max_new_tokens), 0, jnp.int32),
        ],
        axis=1,
    )
    if mesh is not None:
        # The prefill cache is [B]-sized (batch dim), the token buffer
        # [B*beam]; both lead with the dim that shards.
        place_b, batch_sh, replicated = batch_sharding_placer(
            mesh, data_axis, batch
        )
        cache = jax.tree_util.tree_map(place_b, abstract)
        tokens0 = jax.device_put(tokens0, batch_sh)
        params = jax.device_put(params, replicated)
    else:
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), abstract
        )
    run = _compiled_beam_run(
        decode_model, total_len, prompt_len, beam_size,
        float(length_penalty),
    )
    return run(params, tokens0, cache)


@functools.lru_cache(maxsize=16)
def _compiled_beam_run(decode_model, total_len, prompt_len, beam_size,
                       length_penalty):
    """Jitted beam loop, cached per (model config, lengths, beam)."""

    def run(params, tokens, cache):
        flat = tokens.shape[0]
        batch = flat // beam_size
        dtype = getattr(decode_model, "dtype", jnp.bfloat16)

        if prompt_len > 1:
            # Prefill at [B] (the cache arrives [B]-sized; beams are
            # identical here), then fan the filled cache out to [B*beam].
            chunk = tokens[::beam_size, : prompt_len - 1]
            _, up = decode_model.apply(
                {"params": dequantize_pytree(params, dtype), "cache": cache},
                chunk,
                mutable=["cache"],
            )
            cache = up["cache"]
        cache = jax.tree_util.tree_map(
            lambda leaf: jnp.repeat(leaf, beam_size, axis=0)
            if leaf.ndim > 0 and leaf.shape[0] == batch
            else leaf,
            cache,
        )

        # Only beam 0 is live at the start — every beam holds the same
        # prompt, and without this mask the first top-k would pick the
        # same token beam_size times.
        scores = jnp.tile(
            jnp.where(jnp.arange(beam_size) == 0, 0.0, -jnp.inf)[None, :],
            (batch, 1),
        )  # [B, beam]

        def body(t, carry):
            tokens, cache, scores = carry
            current = jax.lax.dynamic_slice(tokens, (0, t), (flat, 1))
            logits, up = decode_model.apply(
                {"params": dequantize_pytree(params, dtype), "cache": cache},
                current,
                mutable=["cache"],
            )
            cache = up["cache"]
            logp = jax.nn.log_softmax(
                logits[:, -1, :].astype(jnp.float32), axis=-1
            )  # [B*beam, V]
            v = logp.shape[-1]
            cand = scores[..., None] + logp.reshape(batch, beam_size, v)
            top, idx = jax.lax.top_k(
                cand.reshape(batch, beam_size * v), beam_size
            )  # [B, beam]
            parent = idx // v  # which beam each winner extends
            token = (idx % v).astype(jnp.int32)
            # Reorder beams: winner k of row b continues beam parent[b, k]
            # — gather tokens and every cache leaf along the flattened dim.
            flat_src = (
                jnp.arange(batch)[:, None] * beam_size + parent
            ).reshape(-1)  # [B*beam]
            tokens = jnp.take(tokens, flat_src, axis=0)
            cache = jax.tree_util.tree_map(
                lambda leaf: jnp.take(leaf, flat_src, axis=0)
                if leaf.ndim > 0 and leaf.shape[0] == flat
                else leaf,
                cache,
            )
            tokens = jax.lax.dynamic_update_slice(
                tokens, token.reshape(-1)[:, None], (0, t + 1)
            )
            return tokens, cache, top

        tokens, _, scores = jax.lax.fori_loop(
            prompt_len - 1, total_len - 1, body, (tokens, cache, scores)
        )
        if length_penalty and total_len > prompt_len:
            # (max_new_tokens == 0 would divide by 0.0 ** penalty == 0.)
            scores = scores / (
                float(total_len - prompt_len) ** length_penalty
            )
        # Sort best-first (top_k returns sorted, but the last reorder
        # interleaves; make the contract explicit).
        order = jnp.argsort(-scores, axis=-1)
        tokens = tokens.reshape(batch, beam_size, -1)
        tokens = jnp.take_along_axis(tokens, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        return tokens, scores

    return jax.jit(run)

"""Decoder-only transformer language model — the long-context flagship.

No reference analog (the reference tops out at ResNet-50 / a commented-out
torchvision ViT, ``multigpu_profile.py:23-24``); this is the model family that
exercises the framework's first-class long-context machinery:

* attention is pluggable: dense (XLA-fused) or :func:`ring_attention`
  (sequence-parallel over the mesh's ``sequence`` axis with ppermute rotation);
* RoPE positions are *global* sequence positions — correct under jit whether or
  not the sequence dim is sharded, because jitted arrays have global semantics;
* ``remat=True`` wraps each block in ``jax.checkpoint`` (rematerialize
  activations in backward — the HBM-for-FLOPs trade that long sequences need);
* all matmul-bearing modules take a compute ``dtype`` (bfloat16 for the MXU),
  while parameters and layernorm statistics stay float32.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from distributed_pytorch_tpu.models.mamba import token_mask
from distributed_pytorch_tpu.models.moe import MoEMLP
from distributed_pytorch_tpu.ops.attention import (
    NEG_INF,
    ring_attention,
    ulysses_attention,
)
from distributed_pytorch_tpu.ops.flash_attention import flash_attention
from distributed_pytorch_tpu.ops.fused_cross_entropy import (
    fused_linear_cross_entropy,
)


def apply_rope(
    x: jnp.ndarray,
    *,
    theta: float = 10000.0,
    positions: Optional[jnp.ndarray] = None,
    scale: float = 1.0,
    rotary_dim: Optional[int] = None,
) -> jnp.ndarray:
    """Rotary position embedding over [B, T, H, D].

    ``rotary_dim`` (``None``: all of ``D``) rotates the FIRST that many of a
    head's dimensions, paired by halves inside them, and passes the rest (a
    configuration's ``partial_rotary_factor``).

    ``positions`` ([T] int/float) defaults to global positions 0..T-1; the
    decode path passes the cache offset so a single-token step rotates by its
    absolute position. A 2-D ``positions`` ([B, T]) gives every batch row its
    OWN absolute positions — the continuous-batching decode path, where slots
    sit at unrelated sequence offsets.

    Context extension knobs for running PAST the training length:
    ``scale > 1`` is linear position interpolation (positions divided by
    ``scale``, squeezing a longer context into the trained angle range);
    raising ``theta`` is the NTK-aware alternative (slower frequency decay).
    Both are plain parameterizations here — which to use, and any
    finetuning, is the caller's policy."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rope(
            x[..., :rotary_dim], theta=theta, positions=positions, scale=scale
        )
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.float32)
    positions = positions.astype(jnp.float32)
    if scale != 1.0:
        positions = positions / scale
    angles = positions[..., :, None] * freqs  # [T, D/2] or [B, T, D/2]
    if angles.ndim == 2:
        angles = angles[None]  # shared positions broadcast over batch
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :d_half], x[..., d_half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def pool_kv_heads(kv_heads: int) -> int:
    """KV heads a page pool ``[num_pages, page_size, heads, D]`` holds for a
    layer of ``kv_heads``: a count above 8 that is no multiple of 8 is padded
    to the next one (30 -> 32; the padding heads hold zeros, are attended to
    by padding query heads, and are sliced off the result). The chip stores a
    bf16 array in tiles of 16 x 128 over its last two sizes: with 30 heads
    there the compiler keeps the pool in a layout of its own and copies the
    WHOLE pool, there and back, round every write and every kernel call (8
    copies of a 378 MB pool a layer and decode step, 1.2 GB of temporaries,
    compiled for a described v5e: tests/test_chip_compile.py); with 1, 2, 8
    or 32 it copies nothing."""
    if kv_heads <= 8 or kv_heads % 8 == 0:
        return kv_heads
    return -(-kv_heads // 8) * 8


def page_slots(block_tables, positions, page: int, valid_lens=None):
    """Where a paged K/V layer writes the tokens at ``positions [S, T]`` of
    rows with ``block_tables [S, pages_per_seq]``: ``(physical page,
    in-page offset)``, each ``[S * T]``. A position at or past the row's table
    capacity (a speculative chunk's tail can overhang the final tokens of a
    sequence near max_seq_len) is routed to the reserved null page (id 0)
    instead of letting the clipped logical index alias into the row's LAST
    page, where it would clobber valid K/V at the same in-page offset. The
    null page absorbs the garbage exactly like inactive rows' writes; the
    visibility mask keeps it dead on every read. The padding of a prefill
    piece (a position at or past ``valid_lens``) goes the same way: a real
    token never attends to it (causal), and its own K/V must land on no page
    a read can see."""
    s, t_step = positions.shape
    pages_per_seq = block_tables.shape[1]
    flat_pos = positions.reshape(-1)  # [S*T_step]
    logical = jnp.clip(flat_pos // page, 0, pages_per_seq - 1)
    rows = jnp.repeat(jnp.arange(s, dtype=jnp.int32), t_step)
    phys = block_tables[rows, logical]  # [S*T_step]
    kept = flat_pos < pages_per_seq * page
    if valid_lens is not None:
        kept &= token_mask(valid_lens, t_step).reshape(-1)
    return jnp.where(kept, phys, 0), flat_pos % page


class Attention(nn.Module):
    """Multi-head attention with RoPE and a pluggable core.

    Core selection: when the mesh has a non-trivial sequence axis
    (cross-chip long context), ``sequence_mode`` picks the sequence-parallel
    strategy — ``"ring"`` (K/V rotation, O(T/sp) memory) or ``"ulysses"``
    (all-to-all seq->head redistribution, fully local full-T attention);
    otherwise the Pallas flash-attention kernel on TPU (which itself falls
    back to the dense XLA path on other backends or non-tiling shapes).
    """

    n_heads: int
    d_model: int
    dtype: Any = jnp.float32
    causal: bool = True
    # Grouped-query attention (GQA; 0 = MHA): K/V project to n_kv_heads
    # heads and each group of n_heads/n_kv_heads query heads shares one.
    # The WIN is the decode KV cache: it stores (and HBM re-reads, every
    # generated token) n_kv_heads instead of n_heads — at n_kv_heads=2,
    # H=16 that is an 8x cache cut, multiplicative with quantized_cache's
    # int8 halving. The query-side repeat happens compute-side after the
    # cache read, so the bandwidth saving is real. n_kv_heads=1 is MQA.
    # Under TP, the K/V kernels shard over n_kv_heads: needs
    # n_kv_heads % tp == 0 (keep kv heads >= the tensor axis).
    n_kv_heads: int = 0
    # Sliding-window (Mistral-style local) attention: position q attends
    # keys in (q - window, q]. 0 = full causal. Compute per layer drops
    # toward O(T * window) — the flash kernel skips out-of-band tiles —
    # and in decode the visibility mask bounds reads the same way. Not yet
    # composed with sequence parallelism (explicit error, no silent cap).
    window: int = 0
    # RoPE context-extension knobs (see apply_rope): linear position
    # interpolation factor and frequency base.
    rope_scale: float = 1.0
    rope_theta: float = 10000.0
    mesh: Optional[Mesh] = None
    sequence_axis: Optional[str] = None
    # How to parallelize attention over the sequence axis: "ring" (K/V
    # rotate via ppermute; memory O(T/sp) per chip — for T beyond one
    # chip's HBM) or "ulysses" (two all-to-alls redistribute seq->heads;
    # attention is then fully local full-T flash — for T that fits per
    # chip, needs (H/tp) % sp == 0). See ops/attention.py.
    sequence_mode: str = "ring"
    decode: bool = False  # autoregressive KV-cache mode (see generation.py)
    # int8 KV cache: at long context the [B, T, H, D] caches — not the
    # params — dominate decode memory and HBM traffic; symmetric absmax
    # per-(token, head) quantization (scale over D) halves both. Dequant
    # happens at the attention einsum, so the loop reads int8.
    quantized_cache: bool = False
    # Paged KV cache (the serving engine's layout, see serving/kv_cache.py):
    # instead of one contiguous [B, max_len, H, D] buffer per sequence, the
    # cache is a global pool [num_pages, page_size, Hkv, D] and each batch
    # row addresses it through a block table of physical page ids. Page 0 is
    # reserved as the NULL page: inactive slots write (and padded table
    # entries read) there, and the visibility mask guarantees nothing read
    # from it ever survives the softmax. Requires decode=True and the caller
    # to pass ``block_tables`` [S, pages_per_seq] + ``seq_lens`` [S] into
    # __call__ every step.
    page_size: int = 0
    num_pages: int = 0
    # Serving decode read path for the paged cache: "" is ops/paged_attention's
    # XLA gather reference (as "xla"), anything else names a kernel mode
    # ("auto" | "pallas" | "interpret" | "xla"). Only single-token decode
    # steps (t_step == 1) dispatch to the kernel; prefill chunks and
    # speculative verify are the op's blockwise walk over the blocks their
    # rows hold, whatever this says.
    paged_kernel: str = ""
    # "" = fp pages (pool dtype follows the activations); "int8" = symmetric
    # absmax per-(token, head) int8 pages with [num_pages, page_size, Hkv]
    # float32 scale pools, quantized at every page write, dequantized at
    # read (by ops/paged_attention: gather, walk or kernel).
    kv_quant: str = ""
    # False: no positional term at all (a hybrid model whose recurrent
    # layers carry position). Every path below then attends on the raw
    # projections.
    rope: bool = True
    use_bias: bool = True  # biases on the four projections
    # What the scores are multiplied by before the softmax, on every path
    # below and in the paged kernel; None is the usual ``head_dim ** -0.5``.
    score_scale: Optional[float] = None
    # An RMSNorm over the WHOLE query and the whole key projection (all heads
    # together, a scale an element) before the heads are split, the rotation
    # and the cache (the Olmo 2 family's QK-norm), statistics in float32 at
    # ``norm_eps``. ``"head"`` is the other published form: an RMSNorm a HEAD
    # (one learned ``[head_dim]`` vector for the queries, one for the keys),
    # likewise before the rotation and the cache.
    qk_norm: Any = False  # False | True (the whole projection) | "head"
    norm_eps: float = 1e-6
    # A head's size where it is not ``d_model // n_heads`` (64 heads of 128 on
    # a hidden size of 6144): the projections are ``[d_model, heads,
    # head_dim]`` and ``out`` ``[heads, head_dim, d_model]``. 0 = the quotient.
    head_dim: int = 0
    # The paged tables this layer is handed are its window GROUP's short ones
    # (``serving/kv_cache.py`` ``WindowTable``; ``ops/paged_attention.py``
    # ``paged_window_attention``): entry 0 stands for the page that holds the
    # window's first key, and the pages behind it have gone back to the
    # group's allocator. Needs ``window``; a paged layer with a window and
    # without this is refused below.
    paged_window: bool = False

    def _scale(self, head_dim: int) -> float:
        if self.score_scale is None:
            return head_dim**-0.5
        return self.score_scale

    def _rope(self, x, positions=None):
        if not self.rope:
            return x
        return apply_rope(
            x, theta=self.rope_theta, scale=self.rope_scale,
            positions=positions,
        )

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        block_tables: Optional[jnp.ndarray] = None,
        seq_lens: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        # Validate unconditionally: a typo'd mode must fail on the first
        # single-chip forward, not later when the job first meets an sp>1
        # mesh mid-launch.
        if self.sequence_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"unknown sequence_mode {self.sequence_mode!r} "
                "(expected 'ring' or 'ulysses')"
            )
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")
        if self.window and not self.causal:
            raise ValueError("window requires causal attention")
        if self.page_size:
            if not self.decode:
                raise ValueError("page_size > 0 requires decode=True")
            if self.num_pages < 2:
                raise ValueError(
                    "paged decode needs num_pages >= 2 (page 0 is the "
                    f"reserved null page), got {self.num_pages}"
                )
            if self.quantized_cache:
                raise ValueError(
                    "paged decode does not compose with quantized_cache yet"
                )
            if self.window and not self.paged_window:
                raise ValueError(
                    "a window in a K/V (non-latent) layer is served through "
                    "pages on its group's own tables alone (layer type "
                    "'attention_window'); the model's attention_window is "
                    "not. A latent layer's window is (models/mla.py)"
                )
            if self.paged_window and (
                not self.window or self.kv_quant or self.mesh is not None
            ):
                raise ValueError(
                    "a window group's layer needs a window and is served "
                    "with neither int8 pages nor a mesh yet"
                )
            if self.kv_quant not in ("", "int8"):
                raise ValueError(
                    f"unknown kv_quant {self.kv_quant!r} "
                    "(expected '' or 'int8')"
                )
            if self.paged_kernel:
                # Same fail-fast rule as sequence_mode: a typo'd kernel
                # mode dies on the cache-init forward, not mid-serve.
                from distributed_pytorch_tpu.ops.paged_attention import (
                    resolve_kernel,
                )

                resolve_kernel(self.paged_kernel)
        elif self.paged_kernel or self.kv_quant:
            raise ValueError(
                "paged_kernel / kv_quant require the paged cache "
                "(page_size > 0)"
            )
        head_dim = self.head_dim or self.d_model // self.n_heads
        kv_heads = self.n_kv_heads or self.n_heads
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(
                f"unknown qk_norm {self.qk_norm!r} (expected False, True "
                "for the whole projection, or 'head')"
            )
        if self.n_heads % kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} not divisible by n_kv_heads "
                f"{kv_heads}"
            )
        dense = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, head_dim), dtype=self.dtype, use_bias=self.use_bias,
            name=name,
        )
        out_proj = nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=self.dtype,
            use_bias=self.use_bias, name="out",
        )
        q_raw = dense(self.n_heads, "query")(x)
        k_raw = dense(kv_heads, "key")(x)
        v = dense(kv_heads, "value")(x)
        if self.qk_norm == "head":
            a_head = lambda name: nn.RMSNorm(  # noqa: E731
                epsilon=self.norm_eps, dtype=jnp.float32, name=name,
            )
            q_raw = a_head("q_norm")(q_raw).astype(self.dtype)
            k_raw = a_head("k_norm")(k_raw).astype(self.dtype)
        elif self.qk_norm:
            whole = lambda name: nn.RMSNorm(  # noqa: E731
                epsilon=self.norm_eps, dtype=jnp.float32, name=name,
                reduction_axes=(-2, -1), feature_axes=(-2, -1),
            )
            q_raw = whole("q_norm")(q_raw).astype(self.dtype)
            k_raw = whole("k_norm")(k_raw).astype(self.dtype)

        if self.decode and self.has_variable("cache", "cached_key"):
            if self.page_size:
                if block_tables is None or seq_lens is None:
                    raise ValueError(
                        "paged decode requires block_tables and seq_lens "
                        "every step (the serving engine passes them)"
                    )
                step = (
                    self._window_paged_step if self.paged_window
                    else self._paged_decode_step
                )
                out = step(q_raw, k_raw, v, block_tables, seq_lens, valid_lens)
            else:
                out = self._decode_step(q_raw, k_raw, v)
            return out_proj(out)
        if self.decode:
            # Cache init pass: size the KV cache — to this call's (max)
            # length in contiguous mode, to the global page pool in paged
            # mode — then fall through to the normal causal forward.
            if self.page_size:
                pool = (
                    self.num_pages, self.page_size, pool_kv_heads(kv_heads),
                    head_dim,
                )
                pool_dtype = jnp.int8 if self.kv_quant else k_raw.dtype
                self.variable("cache", "cached_key", jnp.zeros, pool, pool_dtype)
                self.variable("cache", "cached_value", jnp.zeros, pool, pool_dtype)
                if self.kv_quant:
                    # Per-(page-slot, head) float32 scales live alongside the
                    # int8 pools; they ride every pool-shaped program (CoW
                    # copy, spill/fetch) via the same tree_map genericity.
                    self.variable(
                        "cache", "key_scale", jnp.zeros, pool[:-1], jnp.float32
                    )
                    self.variable(
                        "cache", "value_scale", jnp.zeros, pool[:-1], jnp.float32
                    )
            else:
                cache_dtype = jnp.int8 if self.quantized_cache else k_raw.dtype
                self.variable("cache", "cached_key", jnp.zeros, k_raw.shape, cache_dtype)
                self.variable("cache", "cached_value", jnp.zeros, v.shape, cache_dtype)
                if self.quantized_cache:
                    self.variable(
                        "cache", "key_scale", jnp.zeros, k_raw.shape[:-1], jnp.float32
                    )
                    self.variable(
                        "cache", "value_scale", jnp.zeros, v.shape[:-1], jnp.float32
                    )
                self.variable(
                    "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
                )

        q = self._rope(q_raw)
        k = self._rope(k_raw)
        if self.score_scale is not None:
            # The cores below scale by head_dim ** -0.5 themselves.
            q = q * jnp.asarray(
                self.score_scale * head_dim**0.5, q.dtype
            )
        if kv_heads != self.n_heads:
            # Compute-side broadcast for the cores that need full heads
            # (flash, ulysses). Ring and decode take the UN-repeated k/v so
            # their HBM/ICI traffic stays at the kv-head size — that is
            # where GQA pays.
            group = self.n_heads // kv_heads
            kx = jnp.repeat(k, group, axis=2)
            vx = jnp.repeat(v, group, axis=2)
        else:
            kx, vx = k, v

        use_ring = (
            self.mesh is not None
            and self.sequence_axis is not None
            and self.mesh.shape.get(self.sequence_axis, 1) > 1
        )
        if use_ring and self.sequence_mode == "ulysses":
            # Pre-repeat is structural here: the all-to-all splits the
            # (query) head dim across the axis, so K/V must carry the same
            # head count. (validated mode at __call__ top) Sliding-window
            # composes trivially: post-exchange attention is full-sequence
            # local, the band is just a mask.
            out = ulysses_attention(
                q, kx, vx, mesh=self.mesh, axis_name=self.sequence_axis,
                causal=self.causal, window=self.window,
            )
        elif use_ring:
            # Ring rotates K/V around the ICI ring every hop: hand it the
            # UN-repeated kv-head blocks (kv_groups broadcasts per hop,
            # compute-side) so GQA cuts the interconnect bytes too. With
            # window > 0, hops wholly behind the band are never rotated
            # (ring_live_hops): ICI traffic and compute are O(window).
            out = ring_attention(
                q, k, v, mesh=self.mesh, axis_name=self.sequence_axis,
                causal=self.causal, kv_groups=self.n_heads // kv_heads,
                window=self.window,
            )
        else:
            out = flash_attention(
                q, kx, vx, causal=self.causal, window=self.window,
                mesh=self.mesh,
            )
        return out_proj(out)

    def _decode_step(self, q_raw, k_raw, v):
        """One autoregressive step: rotate q/k by their absolute positions,
        write k/v into the cache at the running index, attend q against the
        valid cache prefix. ``q_raw``: [B, T_step, H, D] (T_step usually 1).

        ``cache_index`` may be a scalar (every row at the same offset — the
        ``generate`` loop) or a ``[B]`` vector giving every row its OWN
        offset — the speculative per-row-acceptance path, where rows advance
        by their individual accepted counts. The vector path mirrors the
        paged decode step: per-row RoPE positions, a scatter write at
        (row, position), and a per-row visibility mask; out-of-range
        positions (a fast row's replay region past the buffer) are dropped
        by the scatter, and reads past a row's index are masked, so rolling
        a row back IS lowering its index — no zeroing or copies."""
        cached_key = self.variable("cache", "cached_key", lambda: None)
        cached_value = self.variable("cache", "cached_value", lambda: None)
        cache_index = self.variable("cache", "cache_index", lambda: None)
        index = cache_index.value
        t_step = q_raw.shape[1]
        max_len = cached_key.value.shape[1]
        per_row = index.ndim == 1  # [B] per-row offsets vs one scalar
        if per_row and self.quantized_cache:
            raise ValueError(
                "per-row cache_index does not compose with quantized_cache "
                "(the int8 write path slices at one shared offset)"
            )

        if per_row:
            positions = index[:, None] + jnp.arange(t_step)  # [B, T_step]
        else:
            positions = index + jnp.arange(t_step)  # [T_step]
        q = self._rope(q_raw, positions)
        k = self._rope(k_raw, positions)

        if self.quantized_cache:
            keys, values = self._update_quantized_cache(
                cached_key, cached_value, k, v, index
            )
        elif per_row:
            b, _, kv_h, d_h = k_raw.shape
            rows = jnp.repeat(jnp.arange(b, dtype=jnp.int32), t_step)
            flat_pos = positions.reshape(-1)
            cached_key.value = cached_key.value.at[rows, flat_pos].set(
                k.astype(cached_key.value.dtype).reshape(-1, kv_h, d_h),
                mode="drop",
            )
            cached_value.value = cached_value.value.at[rows, flat_pos].set(
                v.astype(cached_value.value.dtype).reshape(-1, kv_h, d_h),
                mode="drop",
            )
            keys, values = cached_key.value, cached_value.value
        else:
            cached_key.value = jax.lax.dynamic_update_slice(
                cached_key.value, k.astype(cached_key.value.dtype), (0, index, 0, 0)
            )
            cached_value.value = jax.lax.dynamic_update_slice(
                cached_value.value, v.astype(cached_value.value.dtype), (0, index, 0, 0)
            )
            keys, values = cached_key.value, cached_value.value
        cache_index.value = index + t_step
        scale = self._scale(q.shape[-1])
        # Position k is visible to step-q q when k <= index + q (and, with
        # a sliding window, within the last `window` positions). Per-row
        # indices make the mask [B, T_step, K] instead of [T_step, K].
        if per_row:
            q_abs = positions[:, :, None]  # [B, T_step, 1]
            k_abs = jnp.arange(max_len)[None, None, :]
        else:
            q_abs = (index + jnp.arange(t_step))[:, None]
            k_abs = jnp.arange(max_len)[None, :]
        visible = k_abs <= q_abs
        if self.window:
            visible = visible & (q_abs - k_abs < self.window)
        # ONE attention path for MHA and GQA: grouped einsums against the
        # (small) cache — the query is reshaped [B, t, Hkv, G, D] and
        # contracted directly with the [B, T, Hkv, D] cache, so the
        # n_heads-sized K/V tensors are never materialized (a jnp.repeat
        # here would make XLA write and re-read group x the cache bytes GQA
        # exists to avoid). MHA is simply group=1 (the reshape is a no-op
        # expand). Head order matches the forward path's jnp.repeat: query
        # head h shares kv head h // group, so kv leads group.
        kv_heads = keys.shape[2]
        b, t_q, h, d = q.shape
        group = h // kv_heads
        qg = q.reshape(b, t_q, kv_heads, group, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys) * scale
        mask = (
            visible[:, None, None] if visible.ndim == 3  # [B,1,1,T,K]
            else visible[None, None, None]
        )
        logits = jnp.where(mask, logits, NEG_INF)
        weights = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, values)
        return out.reshape(b, t_q, h, d)

    def _paged_decode_step(
        self, q_raw, k_raw, v, block_tables, seq_lens, valid_lens=None
    ):
        """One decode/prefill step against the PAGED cache pool.

        ``q_raw`` [S, T_step, H, D]: T_step is 1 for the batched decode step,
        or a prefill chunk length (then S is the chunked rows, usually 1).
        ``block_tables`` [S, pages_per_seq] maps each row's logical page to a
        physical page in the [num_pages, page_size, Hkv, D] pool (0 = the
        reserved null page). ``seq_lens`` [S] is each row's token count
        BEFORE this step, i.e. the absolute position of its first new token.
        ``valid_lens`` [S] (optional: a padded prefill piece) says how many of
        a row's ``T_step`` tokens are its own; ``None`` means all of them.

        Same math as :meth:`_decode_step` — RoPE at absolute positions,
        write-then-attend, grouped GQA products — except positions are
        per-row, the write is a scatter into (physical page, offset), and the
        read is ``ops/paged_attention.py``'s ``paged_attention`` at every
        ``T_step``: it alone knows how pages are read. Rows whose table is
        all zeros (inactive slots) write into the null page; what they read
        is discarded and finite either way: on the gather path and in a
        chunk's walk garbage that the visibility mask averages (the null
        page only ever holds finite values written by other inactive rows),
        in the Pallas kernel nothing at all (a row whose table starts at the
        null page gets zeros).
        """
        cached_key = self.variable("cache", "cached_key", lambda: None)
        cached_value = self.variable("cache", "cached_value", lambda: None)
        key_scale = value_scale = None
        if self.kv_quant:
            key_scale = self.variable("cache", "key_scale", lambda: None)
            value_scale = self.variable("cache", "value_scale", lambda: None)
        s, t_step, h, d = q_raw.shape
        kv_heads = k_raw.shape[2]
        page = self.page_size

        seq_lens = seq_lens.astype(jnp.int32)
        positions = seq_lens[:, None] + jnp.arange(t_step, dtype=jnp.int32)
        q = self._rope(q_raw, positions)
        k = self._rope(k_raw, positions)
        held = cached_key.value.shape[2]
        if held != kv_heads:
            # The pool holds padding heads (``pool_kv_heads``): zeros for
            # K and V, and zero query heads to attend to them, kv-major as
            # the grouped mapping is, so that the real heads come first.
            def widen(x, group):
                x = x.reshape(s, t_step, kv_heads, group, d)
                x = jnp.pad(
                    x, ((0, 0), (0, 0), (0, held - kv_heads), (0, 0), (0, 0))
                )
                return x.reshape(s, t_step, held * group, d)

            q, k, v = widen(q, h // kv_heads), widen(k, 1), widen(v, 1)
            kv_heads = held

        # Scatter this step's K/V into (physical page, in-page offset).
        phys, offset = page_slots(block_tables, positions, page, valid_lens)
        if self.kv_quant:
            # Quantize at the write: symmetric absmax per-(token, head) over
            # D — the pool holds int8, the [num_pages, page_size, Hkv] scale
            # pool holds one float32 per written (page-slot, head).
            from distributed_pytorch_tpu.ops.quant import quantize_int8

            def write(cache, scale_var, x):
                qt = quantize_int8(
                    x.astype(jnp.float32).reshape(-1, kv_heads, d), (2,)
                )
                cache.value = cache.value.at[phys, offset].set(qt.q)
                scale_var.value = scale_var.value.at[phys, offset].set(
                    jnp.squeeze(qt.scale, -1)
                )

            write(cached_key, key_scale, k)
            write(cached_value, value_scale, v)
        else:
            cached_key.value = cached_key.value.at[phys, offset].set(
                k.astype(cached_key.value.dtype).reshape(-1, kv_heads, d)
            )
            cached_value.value = cached_value.value.at[phys, offset].set(
                v.astype(cached_value.value.dtype).reshape(-1, kv_heads, d)
            )

        # The one read of K/V pages, behind ``ops/``: a decode step through the
        # Pallas kernel (with it off, the gather reference), a chunk (a
        # prefill piece, a speculative round's verification) as a walk over
        # the blocks its rows hold.
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_attention,
        )

        return paged_attention(
            q, cached_key.value, cached_value.value, block_tables, seq_lens,
            valid_lens=valid_lens,
            k_scale=None if key_scale is None else key_scale.value,
            v_scale=None if value_scale is None else value_scale.value,
            kernel=self.paged_kernel or "xla", mesh=self.mesh,
            sm_scale=self.score_scale,
        )[:, :, :h]

    def _window_paged_step(
        self, q_raw, k_raw, v, block_tables, seq_lens, valid_lens=None
    ):
        """:meth:`_paged_decode_step` for a layer of a WINDOW group: the same
        write-then-attend, through the group's short tables. ``block_tables``
        ``[S, width]`` hold each row's pages from the one that holds the
        first key of its first new token's window on (``window_first_page``
        of ``seq_lens``: the host stages them by the same rule), ``width``
        the pages a decode row (``window_pages``) or a prefill piece
        (``window_group_pages``) can meet. A position's page is found
        relative to that first one; what falls outside the table (a padded
        piece's tail) or past ``valid_lens`` is written to the null page. A
        query at ``t`` reads the keys ``(t - window, t]``: the decode kernel
        from its first live key, a piece through the gather path."""
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_window_attention,
            window_first_page,
        )

        cached_key = self.variable("cache", "cached_key", lambda: None)
        cached_value = self.variable("cache", "cached_value", lambda: None)
        s, t_step, h, d = q_raw.shape
        kv_heads = k_raw.shape[2]
        if cached_key.value.shape[2] != kv_heads:
            raise ValueError(
                f"a window group's pool holds its layer's {kv_heads} KV "
                f"heads as they are, not {cached_key.value.shape[2]}"
            )
        page, width = self.page_size, block_tables.shape[1]
        seq_lens = seq_lens.astype(jnp.int32)
        positions = seq_lens[:, None] + jnp.arange(t_step, dtype=jnp.int32)
        q = self._rope(q_raw, positions)
        k = self._rope(k_raw, positions)
        first = window_first_page(seq_lens, self.window, page)  # [S]
        logical = (positions // page - first[:, None]).reshape(-1)
        rows = jnp.repeat(jnp.arange(s, dtype=jnp.int32), t_step)
        kept = (logical >= 0) & (logical < width)
        if valid_lens is not None:
            kept &= token_mask(valid_lens, t_step).reshape(-1)
        phys = jnp.where(
            kept, block_tables[rows, jnp.clip(logical, 0, width - 1)], 0
        )
        offset = positions.reshape(-1) % page
        cached_key.value = cached_key.value.at[phys, offset].set(
            k.astype(cached_key.value.dtype).reshape(-1, kv_heads, d)
        )
        cached_value.value = cached_value.value.at[phys, offset].set(
            v.astype(cached_value.value.dtype).reshape(-1, kv_heads, d)
        )
        return paged_window_attention(
            q, cached_key.value, cached_value.value, block_tables, seq_lens,
            window=self.window, kernel=self.paged_kernel or "xla",
            sm_scale=self.score_scale,
        )

    def _update_quantized_cache(self, cached_key, cached_value, k, v, index):
        """Write this step's k/v as int8 + per-(token, head) float32 scales,
        and return the DEQUANTIZED full caches for the attention einsums —
        the dequant (int8 read, convert, scale) fuses into each einsum, so
        HBM sees int8 + one scale per head-token instead of bf16."""
        from distributed_pytorch_tpu.ops.quant import quantize_int8

        key_scale = self.variable("cache", "key_scale", lambda: None)
        value_scale = self.variable("cache", "value_scale", lambda: None)

        def write(cache, scale_var, x):
            qt = quantize_int8(x, (x.ndim - 1,))  # per-(token, head) over D
            q8, s = qt.q, jnp.squeeze(qt.scale, -1)  # [B, t, H]
            cache.value = jax.lax.dynamic_update_slice(
                cache.value, q8, (0, index, 0, 0)
            )
            scale_var.value = jax.lax.dynamic_update_slice(
                scale_var.value, s, (0, index, 0)
            )
            return (
                cache.value.astype(self.dtype)
                * scale_var.value[..., None].astype(self.dtype)
            )

        keys = write(cached_key, key_scale, k)
        values = write(cached_value, value_scale, v)
        return keys, values


class MLPBlock(nn.Module):
    d_ff: int
    d_model: int
    dtype: Any = jnp.float32
    # "gelu": down(gelu(up x)); "gated_silu": down(silu(gate x) * up x).
    kind: str = "gelu"
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, dtype=self.dtype, use_bias=self.use_bias, name=name
        )
        h = dense(self.d_ff, "up")(x)
        if self.kind == "gated_silu":
            h = nn.silu(dense(self.d_ff, "gate")(x)) * h
        elif self.kind == "gelu":
            h = nn.gelu(h)
        else:
            raise ValueError(
                f"unknown mlp kind {self.kind!r} "
                "(expected 'gelu' or 'gated_silu')"
            )
        return dense(self.d_model, "down")(h)


LAYER_TYPES = (
    "attention", "mamba", "mamba2", "latent", "latent_sparse", "latent_window",
    "gated_delta", "attention_window", "cca",
)
#: The layer types that are :class:`Attention`: the plain one keeps the
#: model's window (``attention_window``), ``rope`` and ``rope_theta``; an
#: ``"attention_window"`` layer takes its own from ``attention_variants`` and,
#: served through pages, stands on its window GROUP's block tables.
ATTENTION_TYPES = ("attention", "attention_window")
#: The layer types that keep a per-slot recurrent state in decode mode. A
#: ``"cca"`` layer (models/cca.py) is in BOTH this set and the next: its K and
#: V go to pages, and the last token's conv inputs and half value to its slot.
RECURRENT_TYPES = ("mamba", "mamba2", "gated_delta", "cca")
#: The layer types whose K and V pages stand on the sequence's ONE block table
#: and are read by ``ops/paged_attention.py``'s K/V calls (a model that names
#: no layer types has such a layer everywhere).
FULL_KV_TYPES = ("attention", "cca")
#: The layer types that are models/mla.py's LatentAttention: the plain one,
#: one with an indexer (learned sparse attention; a second page pool), one
#: with a window. The last two take their sizes from ``latent_variants``.
LATENT_TYPES = ("latent", "latent_sparse", "latent_window")
FFN_TYPES = ("dense", "routed")


def live_tokens(state_slots, valid_lens, t_step: int):
    """``RoutedExperts``' ``live`` for a call told ``state_slots [B]`` and
    ``valid_lens [B]`` (either may be ``None``): the rows that carry a
    request, as a row mask ``[B]``, and under a padded prefill piece only
    their own tokens, as a token mask ``[B, t_step]``."""
    live = None if state_slots is None else state_slots >= 0
    if valid_lens is None:
        return live
    own = token_mask(valid_lens, t_step)
    return own if live is None else own & live[:, None]


def make_norm(kind: str, eps: float, name: str) -> nn.Module:
    """A block's normalisation: statistics and output in float32."""
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=jnp.float32, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(epsilon=eps, dtype=jnp.float32, name=name)
    raise ValueError(
        f"unknown norm {kind!r} (expected 'layernorm' or 'rmsnorm')"
    )


class TransformerBlock(nn.Module):
    n_heads: int
    d_model: int
    d_ff: int
    dtype: Any = jnp.float32
    causal: bool = True
    mesh: Optional[Mesh] = None
    sequence_axis: Optional[str] = None
    sequence_mode: str = "ring"  # see Attention
    n_kv_heads: int = 0  # GQA (see Attention); 0 = MHA
    window: int = 0  # sliding-window attention (see Attention); 0 = full
    rope_scale: float = 1.0  # RoPE linear interpolation (see apply_rope)
    rope_theta: float = 10000.0
    dropout_rate: float = 0.0  # residual-branch dropout (see TransformerLM)
    n_experts: int = 0  # >0 swaps the dense MLP for an expert-parallel MoEMLP
    moe_top_k: int = 1  # router choices per token (see models/moe.py)
    decode: bool = False
    remat_mlp: bool = False  # rematerialize only the MLP branch (see TransformerLM)
    quantized_cache: bool = False  # int8 KV cache in decode (see Attention)
    page_size: int = 0  # paged KV cache in decode (see Attention); 0 = contiguous
    num_pages: int = 0
    paged_kernel: str = ""  # fused paged-decode read path (see Attention)
    kv_quant: str = ""  # int8 KV pages + scale pools (see Attention)
    # The block's options (see TransformerLM): the defaults are the block
    # every model had before them.
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    mlp: str = "gelu"
    use_bias: bool = True
    rope: bool = True
    mixer: str = "attention"  # one of LAYER_TYPES
    mamba: tuple = ()  # the recurrent mixer's sizes as (field, value) pairs
    latent: tuple = ()  # a "latent" layer's sizes (models/mla.py), likewise
    score_scale: Optional[float] = None  # see Attention
    residual_multiplier: float = 1.0  # on both branches before they are added
    ffn: str = "dense"  # one of FFN_TYPES
    routed: tuple = ()  # RoutedExperts' sizes as (field, value) pairs
    shared_d_ff: int = 0  # a routed layer's shared gated-SiLU MLP; 0 = none
    # Where the block's two norms stand: "input" is ``x + f(norm(x))``,
    # "output" the Olmo 2 family's ``x + norm(f(x))`` (no norm on the input;
    # the same two parameters, ``ln_attn`` and ``ln_mlp``).
    norm_placement: str = "input"
    qk_norm: Any = False  # see Attention
    head_dim: int = 0  # see Attention; 0 = d_model // n_heads
    # An "attention_window" layer's own ``window``, ``rope`` and
    # ``rope_theta`` as (field, value) pairs (TransformerLM.attention_variants).
    attention: tuple = ()
    cca: tuple = ()  # a "cca" layer's own fields (TransformerLM.cca_options)
    # ``x <- a * x + b * f(norm(x))`` with learned ``[d_model]`` vectors ``a``
    # and ``b`` a sublayer (``attn_skip_scale``, ``attn_branch_scale``,
    # ``mlp_skip_scale``, ``mlp_branch_scale``; float32, ones at init).
    residual_scales: bool = False

    @property
    def carries_router(self) -> bool:
        """Whether the block takes and returns its router's carry (a routed
        layer whose router has one: models/moe.py ``CARRY_ROUTERS``)."""
        from distributed_pytorch_tpu.models.moe import CARRY_ROUTERS

        return self.ffn == "routed" and dict(self.routed).get(
            "router", "linear") in CARRY_ROUTERS

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        block_tables: Optional[jnp.ndarray] = None,
        seq_lens: Optional[jnp.ndarray] = None,
        state_slots: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
        row_groups=None,
        router_carry: Optional[jnp.ndarray] = None,
    ):
        """``x`` after the block; where :attr:`carries_router`, ``(x, the
        router's carry for the next layer)``."""
        def drop(y):
            # Active only when a "dropout" rng is supplied (the train step
            # with TrainState.rng armed); eval/decode never pass one, so
            # they are deterministic with no flags to thread.
            if self.dropout_rate == 0.0:
                return y
            return nn.Dropout(self.dropout_rate)(
                y, deterministic=not self.has_rng("dropout")
            )

        # Only pass the paged-decode arrays when the caller supplied them:
        # the train/remat paths must see the exact pre-paging call signature.
        paged_kw = (
            {} if block_tables is None
            else {"block_tables": block_tables, "seq_lens": seq_lens}
        )
        # A padded prefill piece says how many of a row's tokens are its
        # own; every other call says nothing and lowers as it always did.
        piece_kw = {} if valid_lens is None else {"valid_lens": valid_lens}
        if self.norm_placement not in ("input", "output"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r} "
                "(expected 'input' or 'output')"
            )
        on_output = self.norm_placement == "output"
        ln_attn = make_norm(self.norm, self.norm_eps, "ln_attn")
        normed = x if on_output else ln_attn(x)
        if self.mixer == "mamba":
            from distributed_pytorch_tpu.models.mamba import MambaMixer

            mixed = MambaMixer(
                self.d_model, dtype=self.dtype, norm_eps=self.norm_eps,
                decode=self.decode, name="mamba", **dict(self.mamba),
            )(normed, seq_lens=seq_lens, state_slots=state_slots, **piece_kw)
        elif self.mixer == "mamba2":
            from distributed_pytorch_tpu.models.mamba2 import Mamba2Mixer

            mixed = Mamba2Mixer(
                self.d_model, dtype=self.dtype, norm_eps=self.norm_eps,
                decode=self.decode, name="mamba", **dict(self.mamba),
            )(normed, seq_lens=seq_lens, state_slots=state_slots, **piece_kw)
        elif self.mixer == "gated_delta":
            from distributed_pytorch_tpu.models.gated_delta import (
                GatedDeltaMixer,
            )

            mixed = GatedDeltaMixer(
                self.d_model, dtype=self.dtype, norm_eps=self.norm_eps,
                decode=self.decode, kernel=self.paged_kernel,
                name="gated_delta", **dict(self.mamba),
            )(normed, seq_lens=seq_lens, state_slots=state_slots, **piece_kw)
        elif self.mixer == "cca":
            from distributed_pytorch_tpu.models.cca import CCAttention

            mixed = CCAttention(
                self.d_model, self.n_heads, self.n_kv_heads or self.n_heads,
                self.head_dim or self.d_model // self.n_heads,
                rope_theta=self.rope_theta, dtype=self.dtype,
                decode=self.decode, page_size=self.page_size,
                num_pages=self.num_pages, paged_kernel=self.paged_kernel,
                name="cca", **dict(self.cca),
            )(normed, state_slots=state_slots, **paged_kw, **piece_kw)
        elif self.mixer in LATENT_TYPES:
            from distributed_pytorch_tpu.models.mla import LatentAttention

            # ``latent`` holds the layer type's own heads and rotary base
            # (TransformerLM.latent_sizes).
            mixed = LatentAttention(
                d_model=self.d_model, dtype=self.dtype,
                norm_eps=self.norm_eps,
                decode=self.decode, page_size=self.page_size,
                num_pages=self.num_pages, paged_kernel=self.paged_kernel,
                name="mla", **dict(self.latent),
            )(normed, row_groups=row_groups, **paged_kw, **piece_kw)
        elif self.mixer in ATTENTION_TYPES:
            own = dict(self.attention)  # a window layer's; else nothing
            mixed = Attention(
                self.n_heads, self.d_model, self.dtype, self.causal,
                n_kv_heads=self.n_kv_heads,
                window=own.get("window", self.window),
                rope_scale=self.rope_scale,
                rope_theta=own.get("rope_theta", self.rope_theta),
                mesh=self.mesh, sequence_axis=self.sequence_axis,
                sequence_mode=self.sequence_mode, decode=self.decode,
                quantized_cache=self.quantized_cache,
                page_size=self.page_size, num_pages=self.num_pages,
                paged_kernel=self.paged_kernel, kv_quant=self.kv_quant,
                rope=own.get("rope", self.rope), use_bias=self.use_bias,
                score_scale=self.score_scale, qk_norm=self.qk_norm,
                norm_eps=self.norm_eps, head_dim=self.head_dim,
                paged_window=(
                    self.mixer == "attention_window" and self.page_size > 0
                ),
                name="attention",
            )(normed, **paged_kw, **piece_kw)
        else:
            raise ValueError(
                f"unknown layer type {self.mixer!r} "
                f"(expected one of {LAYER_TYPES})"
            )
        def scaled(branch):
            if self.residual_multiplier == 1.0:
                return branch
            return branch * jnp.asarray(self.residual_multiplier, branch.dtype)

        def merged(x, branch, sublayer):
            branch = drop(scaled(branch))
            if not self.residual_scales:
                return x + branch
            skip, gain = (
                self.param(
                    f"{sublayer}_{name}_scale", nn.initializers.ones_init(),
                    (self.d_model,), jnp.float32,
                ).astype(jnp.float32)
                for name in ("skip", "branch")
            )
            return (
                x.astype(jnp.float32) * skip
                + branch.astype(jnp.float32) * gain
            ).astype(x.dtype)

        if on_output:
            mixed = ln_attn(mixed).astype(x.dtype)
        x = merged(x, mixed, "attn")
        ln_mlp = make_norm(self.norm, self.norm_eps, "ln_mlp")
        normed = x if on_output else ln_mlp(x)
        if self.ffn == "routed":
            from distributed_pytorch_tpu.models.moe import RoutedExperts

            fed = RoutedExperts(
                d_ff=self.d_ff, d_model=self.d_model, dtype=self.dtype,
                paged_kernel=self.paged_kernel, name="experts",
                **dict(self.routed),
            )(
                normed, live=live_tokens(state_slots, valid_lens, x.shape[1]),
                **({"carry": router_carry} if self.carries_router else {}),
            )
            if self.carries_router:
                fed, router_carry = fed
            if self.shared_d_ff:
                fed = fed + MLPBlock(
                    self.shared_d_ff, self.d_model, self.dtype,
                    kind="gated_silu", use_bias=False, name="shared_mlp",
                )(normed).astype(fed.dtype)
        elif self.ffn != "dense":
            raise ValueError(
                f"unknown feed-forward {self.ffn!r} "
                f"(expected one of {FFN_TYPES})"
            )
        elif self.n_experts > 0:
            cls = nn.remat(MoEMLP) if self.remat_mlp else MoEMLP
            fed = cls(
                self.n_experts, self.d_ff, self.d_model, self.dtype,
                router_top_k=self.moe_top_k, mesh=self.mesh, name="moe",
            )(normed)
        else:
            cls = nn.remat(MLPBlock) if self.remat_mlp else MLPBlock
            fed = cls(
                self.d_ff, self.d_model, self.dtype, kind=self.mlp,
                use_bias=self.use_bias, name="mlp",
            )(normed)
        if on_output:
            fed = ln_mlp(fed).astype(x.dtype)
        x = merged(x, fed, "mlp")
        return (x, router_carry) if self.carries_router else x


class LMHead(nn.Module):
    """The LM projection with an optional fused-loss path.

    Parameters are declared directly (``kernel``/``bias``) with the same
    names, shapes, and initializers ``nn.Dense(name="lm_head")`` would create,
    so the param tree — and pinned-seed initialization — is byte-identical
    whether or not the fused path is enabled, and checkpoints move freely
    between the two. EXCEPT under weight tying: with ``tied_kernel`` passed
    (``TransformerLM(tie_embeddings=True)``) no params are declared at all
    and the ``lm_head`` scope is absent from the tree — tied and untied
    checkpoints are different layouts by design.

    * ``targets is None`` (or ``fused_chunk == 0``): returns float32 logits
      ``[..., vocab]`` — the standard path, used by generation and eval.
    * fused path: returns the scalar mean cross-entropy via
      :func:`fused_linear_cross_entropy` — the ``[N, vocab]`` logits tensor
      (an LM's largest activation) is never materialized in forward or
      backward.
    """

    vocab_size: int
    fused_chunk: int = 0

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        targets: Optional[jnp.ndarray] = None,
        tied_kernel: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        if tied_kernel is not None:
            # Weight tying (GPT-2 style): the head IS the transposed token
            # embedding — no kernel or bias params are declared here, so
            # the lm_head scope vanishes from the param tree and gradients
            # flow to the embedding from both its uses. bias stays None:
            # the fused path then skips the bias add AND its dead gradient
            # accumulator in the backward scan.
            kernel = tied_kernel.astype(jnp.float32)
            bias = None
        else:
            kernel = self.param(
                "kernel",
                nn.initializers.lecun_normal(),
                (x.shape[-1], self.vocab_size),
                jnp.float32,
            )
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.vocab_size,),
                jnp.float32,
            )
        if self.fused_chunk and targets is not None:
            return fused_linear_cross_entropy(
                x.reshape(-1, x.shape[-1]),
                kernel,
                bias,
                targets.reshape(-1),
                self.fused_chunk,
            )
        # Logits in float32 for a numerically stable softmax-cross-entropy.
        logits = x.astype(jnp.float32) @ kernel
        return logits if bias is None else logits + bias


class TransformerLM(nn.Module):
    """GPT-style causal LM over token ids ``[batch, seq] -> [batch, seq, vocab]``.

    With ``fused_head_chunk > 0`` AND ``targets`` passed to ``__call__``, the
    model instead returns the scalar mean next-token cross-entropy computed by
    the fused LM head (the logits tensor is never materialized); the train
    step passes targets through when built with ``apply_takes_targets=True``.
    """

    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 6
    n_heads: int = 8
    d_ff: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False
    # "full": jax.checkpoint around each whole block — maximal memory saving,
    # but the backward pass re-runs the flash-attention forward kernel
    # (measured 18% step-time tax at T=8192 on v5e). "mlp": rematerialize only
    # the MLP branch — the big d_ff activations are recomputed (cheap matmuls)
    # while attention kernels and their residuals stay saved. With the flash
    # kernel, activations are linear in T, so "mlp" (or remat=False) is the
    # right choice until HBM actually runs out.
    remat_policy: str = "full"  # "full" | "mlp"
    mesh: Optional[Mesh] = None
    sequence_axis: Optional[str] = None
    sequence_mode: str = "ring"  # "ring" | "ulysses" (see Attention)
    n_kv_heads: int = 0  # grouped-query attention (see Attention); 0 = MHA
    attention_window: int = 0  # sliding-window attention; 0 = full causal
    rope_scale: float = 1.0  # RoPE linear position interpolation factor
    rope_theta: float = 10000.0  # RoPE frequency base (NTK-aware extension)
    # Residual-branch + embedding dropout (GPT-2 placement). Active ONLY
    # when the apply carries a "dropout" rng — the train step does so when
    # TrainState.rng is armed (create_train_state(dropout_rng=...) /
    # Trainer(dropout_seed=...)); eval and generation never do, so they
    # stay deterministic with no train/eval flag plumbing.
    dropout_rate: float = 0.0
    n_experts: int = 0  # >0: MoE MLPs in every `moe_every`-th block
    moe_top_k: int = 1  # MoE router choices per token (1=Switch, 2=GShard)
    moe_every: int = 2
    # GPT-2-style weight tying: the LM head reuses the token embedding
    # (transposed, no bias) — vocab*d_model fewer params, gradients reach
    # the embedding from both ends. The lm_head scope then holds no params
    # (TP/quant rules for it simply don't match; the embedding stays a
    # gather + full-precision head reads under quantize=True). TP caveat:
    # TRANSFORMER_TP_RULES shard the embedding over d_model, so the tied
    # head contracts over the SHARDED axis — GSPMD inserts an all-reduce
    # and the [N, vocab] logits land replicated, where the untied
    # lm_head/kernel kept them vocab-sharded with no collective. For
    # vocab-sharded-head TP training at scale, prefer untied (or use the
    # fused CE head, which never materializes the logits at all).
    tie_embeddings: bool = False
    decode: bool = False  # KV-cache autoregressive mode (see generation.py)
    quantized_cache: bool = False  # int8 KV cache in decode (see Attention)
    fused_head_chunk: int = 0  # >0: vocab chunk size for the fused CE head
    # Paged KV cache for continuous-batching decode (see Attention and
    # serving/): the serving engine clones with decode=True, page_size=P,
    # num_pages=N and passes block_tables/seq_lens through __call__.
    page_size: int = 0
    num_pages: int = 0
    paged_kernel: str = ""  # fused paged-decode read path (see Attention)
    kv_quant: str = ""  # int8 KV pages + scale pools (see Attention)
    # The block, by option. The defaults are the one block every model had
    # before these fields existed (LayerNorm at flax's epsilon, biased
    # tanh-GELU MLP, RoPE, attention in every layer): its parameters and
    # compiled programs are unchanged.
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    mlp: str = "gelu"  # "gelu" | "gated_silu" (gate, up, down)
    use_bias: bool = True  # on the attention and MLP projections
    rope: bool = True  # False: attention has no positional term
    # One of LAYER_TYPES per layer; None = attention everywhere. A "mamba"
    # layer is models/mamba.py's mixer in the attention's place; in decode
    # mode it keeps a per-slot recurrent state in the ``cache`` collection
    # beside the KV pools, and ``__call__`` must be told ``state_slots``.
    layer_types: Optional[tuple] = None
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0  # required where a layer is "mamba"
    # A "mamba2" layer is models/mamba2.py's mixer (a state [H, P, N] a
    # slot, one decay a head, evaluated in blocks); it shares mamba_d_state
    # and mamba_d_conv with "mamba".
    mamba_n_heads: int = 0  # required where a layer is "mamba2"
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    # A "gated_delta" layer is models/gated_delta.py's mixer (the gated
    # delta rule: a MATRIX state [heads, d_k, d_v] a slot, read before it is
    # written); ``linear_neg_eigval`` is its ``beta``'s factor 2.
    linear_n_heads: int = 0  # required where a layer is "gated_delta"
    linear_d_k: int = 0
    linear_d_v: int = 0
    linear_d_conv: int = 4
    linear_neg_eigval: bool = True
    # The Olmo 2 family's block (see TransformerBlock and Attention): the
    # norms on each sublayer's OUTPUT, and an RMSNorm over the whole q and k.
    norm_placement: str = "input"  # "input" | "output"
    qk_norm: Any = False  # False | True (the whole projection) | "head"
    # A head's size where it is not ``d_model // n_heads`` (see Attention).
    head_dim: int = 0
    # An "attention_window" layer is :class:`Attention` with a window, a
    # ``rope`` and a ``rope_theta`` of its OWN: ``(("attention_window",
    # (("window", 128), ("rope", True), ...)),)``, the way ``latent_variants``
    # gives the latent layers theirs; what a variant leaves out is the
    # model's. The plain "attention" layers keep the model's
    # (``attention_window``, ``rope``, ``rope_theta``). In paged decode mode
    # such layers are a block-table GROUP of their own: their pools hold
    # ``window_num_pages`` pages (0: ``num_pages``), and ``__call__`` must be
    # handed the group's short tables as ``window_tables``.
    attention_variants: Optional[tuple] = None
    window_num_pages: int = 0
    # A "cca" layer is models/cca.py's CCAttention on the model's ``n_heads``,
    # ``n_kv_heads``, ``head_dim`` and ``rope_theta``; these are its own
    # fields as (field, value) pairs (``time0``, ``time1``, ``rotary_dim`` and
    # the options that file lists). In decode mode it keeps K and V pages on
    # the sequence's table AND a per-slot state (``recurrent_layers`` counts
    # it), so ``__call__`` must be told ``state_slots``.
    cca_options: tuple = ()
    # ``x <- a * x + b * f(norm(x))`` with learned vectors a sublayer
    # (TransformerBlock.residual_scales).
    residual_scales: bool = False
    # Scalars some families put on the residual stream (defaults: none).
    # The scores' scale (None = head_dim ** -0.5) reaches every attention path
    # and the paged kernel; the others multiply the embedding, each branch
    # before it is added, and divide the logits.
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # One of FFN_TYPES per layer; None = the dense MLP (or ``n_experts``'
    # MoEMLP) everywhere. A "routed" layer is models/moe.py's dropless
    # RoutedExperts of width ``d_ff`` over ``routed_experts`` router outputs
    # (``routed_top_k`` a token) of which this program holds
    # ``experts_held = (lo, hi)`` (None: all), plus, where ``shared_d_ff``,
    # one shared gated-SiLU MLP on every token.
    ffn_types: Optional[tuple] = None
    routed_experts: int = 0
    routed_top_k: int = 0
    experts_held: Optional[tuple] = None
    shared_d_ff: int = 0
    routed_gating: str = "softmax_of_top_k"  # one of models/moe.py's GATINGS
    routed_scale: float = 1.0  # on the routed gates (``routed_scaling_factor``)
    # The routed layers' router, one of models/moe.py's ROUTERS, and the
    # width of one that is a network. A router with a carry (``"mlp_carry"``)
    # hands its mixed hidden state from each routed layer to the next: the
    # layer loop below carries it beside ``x``.
    routed_router: str = "linear"
    routed_router_hidden: int = 0
    # A "dense" layer's width where it is not ``d_ff`` (which stays the
    # routed experts'); 0 = ``d_ff``.
    dense_d_ff: int = 0
    # A "latent" layer is models/mla.py's LatentAttention in the attention's
    # place: in decode mode it keeps ONE page pool [num_pages, page_size,
    # pool_width] (a token's ``[c | k_pe]``, no head axis) where an
    # "attention" layer keeps a K and a V pool. ``rope_yarn`` is the
    # configuration's ``rope_scaling`` block as (key, value) pairs.
    kv_lora_rank: int = 0  # required where a layer is "latent", as the three below
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[tuple] = None
    # The other LATENT_TYPES' own sizes: ``((type, ((field, value), ...)),
    # ...)``, LatentAttention's fields for the layers of that type. What a
    # variant names replaces the model's (``n_heads``, ``rope_theta``, the
    # four sizes above); its ``window``, ``q_lora_rank``, ``gate``,
    # ``lora_rescale`` and indexer (``index_heads``, ``index_dim``,
    # ``index_top_k``) are the layer's own: a window and a rotary base are a
    # layer's, not the model's.
    latent_variants: Optional[tuple] = None

    @property
    def recurrent_layers(self) -> int:
        """How many layers keep a per-slot state in decode mode."""
        return sum(t in RECURRENT_TYPES for t in self.layer_types or ())

    @property
    def latent_layers(self) -> int:
        """How many layers keep latent pages (pools with no head axis)."""
        return sum(t in LATENT_TYPES for t in self.layer_types or ())

    def latent_sizes(self, layer_type: str) -> dict:
        """LatentAttention's sizes for the layers of ``layer_type`` (one of
        LATENT_TYPES): the model's, under the variant's own."""
        sizes = dict(
            n_heads=self.n_heads, rope_theta=self.rope_theta,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, yarn=self.rope_yarn,
        )
        if layer_type != "latent":
            variants = dict(self.latent_variants or ())
            if layer_type not in variants:
                raise ValueError(
                    f"a model with {layer_type!r} layers needs that type's "
                    f"sizes in latent_variants"
                )
            sizes.update(dict(variants[layer_type]))
        return sizes

    def attention_sizes(self) -> dict:
        """An "attention_window" layer's own ``window``, ``rope`` and
        ``rope_theta``: the model's, under the variant's."""
        variants = dict(self.attention_variants or ())
        own = dict(variants.get("attention_window", ()))
        if own.get("window", 0) < 1:
            raise ValueError(
                "a model with 'attention_window' layers needs that type's "
                "window in attention_variants"
            )
        return {"rope": self.rope, "rope_theta": self.rope_theta, **own}

    @property
    def kv_window(self) -> int:
        """The window of the "attention_window" layers, whose pages stand in
        a block-table group of their own; 0: the model has no such group."""
        if "attention_window" not in (self.layer_types or ()):
            return 0
        return int(self.attention_sizes()["window"])

    @property
    def routed_layers(self) -> int:
        """How many layers sow a routing count (``"routing"`` collection)."""
        return sum(t == "routed" for t in self.ffn_types or ())

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        targets: Optional[jnp.ndarray] = None,
        *,
        block_tables: Optional[jnp.ndarray] = None,
        seq_lens: Optional[jnp.ndarray] = None,
        state_slots: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
        row_groups=None,
        window_tables: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        types = self.layer_types
        if types is not None and len(types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(types)} layers, the model has "
                f"{self.n_layers}"
            )
        if "mamba" in (types or ()) and self.mamba_dt_rank < 1:
            raise ValueError("a model with mamba layers needs mamba_dt_rank")
        if "mamba2" in (types or ()) and min(
            self.mamba_n_heads, self.mamba_d_head
        ) < 1:
            raise ValueError(
                "a model with mamba2 layers needs mamba_n_heads and "
                "mamba_d_head"
            )
        if "gated_delta" in (types or ()) and min(
            self.linear_n_heads, self.linear_d_k, self.linear_d_v
        ) < 1:
            raise ValueError(
                "a model with gated_delta layers needs linear_n_heads, "
                "linear_d_k and linear_d_v"
            )
        if "latent" in (types or ()) and min(
            self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim,
            self.v_head_dim,
        ) < 1:
            raise ValueError(
                "a model with latent layers needs kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        ffns = self.ffn_types
        if ffns is not None and len(ffns) != self.n_layers:
            raise ValueError(
                f"ffn_types names {len(ffns)} layers, the model has "
                f"{self.n_layers}"
            )
        if self.routed_layers and min(
            self.routed_experts, self.routed_top_k
        ) < 1:
            raise ValueError(
                "a model with routed layers needs routed_experts and "
                "routed_top_k"
            )
        embed = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="embed"
        )
        x = embed(tokens)
        if self.embedding_multiplier != 1.0:
            x = x * jnp.asarray(self.embedding_multiplier, x.dtype)
        if self.dropout_rate > 0.0:
            x = nn.Dropout(self.dropout_rate)(
                x, deterministic=not self.has_rng("dropout")
            )
        block = TransformerBlock
        remat_mlp = False
        if self.remat:
            if self.remat_policy == "full":
                block = nn.remat(TransformerBlock)
            elif self.remat_policy == "mlp":
                remat_mlp = True
            else:
                raise ValueError(f"unknown remat_policy {self.remat_policy!r}")
        # See TransformerBlock: the paged-decode arrays are forwarded only
        # when present so the train/remat call signature is unchanged.
        paged_kw = (
            {} if block_tables is None
            else {"block_tables": block_tables, "seq_lens": seq_lens}
        )
        if state_slots is not None:
            paged_kw["state_slots"] = state_slots
        if valid_lens is not None:
            paged_kw["valid_lens"] = valid_lens
        if row_groups is not None:  # latent layers' (models/mla.py)
            paged_kw["row_groups"] = row_groups
        block_kw = dict(
            norm=self.norm, norm_eps=self.norm_eps, mlp=self.mlp,
            use_bias=self.use_bias, rope=self.rope,
            score_scale=self.attention_multiplier,
            residual_multiplier=self.residual_multiplier,
            norm_placement=self.norm_placement, qk_norm=self.qk_norm,
        )
        if self.residual_scales:
            block_kw["residual_scales"] = True
        if self.head_dim:
            block_kw["head_dim"] = self.head_dim
        mixer_kw = {
            "mamba": (
                ("d_state", self.mamba_d_state), ("d_conv", self.mamba_d_conv),
                ("expand", self.mamba_expand), ("dt_rank", self.mamba_dt_rank),
            ),
            "mamba2": (
                ("n_heads", self.mamba_n_heads), ("d_head", self.mamba_d_head),
                ("d_state", self.mamba_d_state), ("d_conv", self.mamba_d_conv),
                ("n_groups", self.mamba_n_groups),
            ),
            "gated_delta": (
                ("n_heads", self.linear_n_heads), ("d_k", self.linear_d_k),
                ("d_v", self.linear_d_v), ("d_conv", self.linear_d_conv),
                ("neg_eigval", self.linear_neg_eigval),
            ),
            "cca": tuple(self.cca_options),
        }
        for latent_type in LATENT_TYPES:
            if latent_type in (types or ()):
                mixer_kw[latent_type] = tuple(
                    sorted(self.latent_sizes(latent_type).items())
                )
        if "attention_window" in (types or ()):
            mixer_kw["attention_window"] = tuple(
                sorted(self.attention_sizes().items())
            )
            if block_tables is not None and window_tables is None:
                raise ValueError(
                    "a model with attention_window layers needs its window "
                    "group's window_tables beside block_tables"
                )
        routed = (
            ("n_experts", self.routed_experts),
            ("top_k", self.routed_top_k), ("held", self.experts_held),
        )
        if self.routed_gating != "softmax_of_top_k":
            routed += (("gating", self.routed_gating),)
        if self.routed_scale != 1.0:
            routed += (("scale", self.routed_scale),)
        if self.routed_router != "linear":
            routed += (
                ("router", self.routed_router),
                ("router_hidden", self.routed_router_hidden),
                ("norm_eps", self.norm_eps),
            )
        routed_kw = dict(
            ffn="routed", shared_d_ff=self.shared_d_ff, routed=routed
        )
        router_carry = None  # of the last routed layer whose router has one
        for i in range(self.n_layers):
            # GShard-style interleaving: every `moe_every`-th block is MoE.
            moe = self.n_experts if (i + 1) % self.moe_every == 0 else 0
            layer_kw = block_kw
            layer_paged, num_pages = paged_kw, self.num_pages
            if types is not None and types[i] != "attention":
                sizes = "latent" if types[i] in LATENT_TYPES else "mamba"
                if types[i] == "cca":
                    sizes = "cca"
                if types[i] == "attention_window":
                    # The window group's tables and pool size.
                    sizes = "attention"
                    num_pages = self.window_num_pages or self.num_pages
                    if window_tables is not None:
                        layer_paged = dict(
                            paged_kw, block_tables=window_tables
                        )
                layer_kw = dict(
                    block_kw, mixer=types[i],
                    **{sizes: mixer_kw.get(types[i], ())},
                )
            if ffns is not None and ffns[i] != "dense":
                if ffns[i] != "routed":
                    raise ValueError(
                        f"unknown feed-forward {ffns[i]!r} "
                        f"(expected one of {FFN_TYPES})"
                    )
                layer_kw = dict(layer_kw, **routed_kw)
                d_ff = self.d_ff
            else:
                d_ff = self.dense_d_ff or self.d_ff
            layer = block(
                self.n_heads, self.d_model, d_ff, self.dtype,
                True, self.mesh, self.sequence_axis,
                sequence_mode=self.sequence_mode,
                n_kv_heads=self.n_kv_heads, window=self.attention_window,
                rope_scale=self.rope_scale, rope_theta=self.rope_theta,
                dropout_rate=self.dropout_rate,
                n_experts=moe, moe_top_k=self.moe_top_k,
                decode=self.decode, remat_mlp=remat_mlp,
                quantized_cache=self.quantized_cache,
                page_size=self.page_size, num_pages=num_pages,
                paged_kernel=self.paged_kernel, kv_quant=self.kv_quant,
                name=f"block_{i}", **layer_kw,
            )
            if layer.carries_router:
                x, router_carry = layer(
                    x, router_carry=router_carry, **layer_paged
                )
            else:
                x = layer(x, **layer_paged)
        x = make_norm(self.norm, self.norm_eps, "ln_final")(x)
        if self.fused_head_chunk and self.vocab_size % self.fused_head_chunk:
            # Fail loudly here: a silent dense fallback would surface later as
            # a baffling "gradient only defined for scalar-output functions"
            # from the train step (which expects the fused scalar loss).
            raise ValueError(
                f"vocab_size {self.vocab_size} not divisible by "
                f"fused_head_chunk {self.fused_head_chunk}"
            )
        out = LMHead(
            self.vocab_size, self.fused_head_chunk, name="lm_head"
        )(
            x,
            targets,
            tied_kernel=embed.embedding.T if self.tie_embeddings else None,
        )
        if self.logits_scaling != 1.0:
            if self.fused_head_chunk and targets is not None:
                raise ValueError(
                    "logits_scaling does not compose with the fused loss "
                    "head (it never has the logits to divide)"
                )
            out = out / self.logits_scaling
        return out

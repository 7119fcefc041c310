"""Multi-head latent attention (MLA), the ``deepseek_v2`` family's: a cache of
ONE latent vector a token where multi-head attention keeps a key and a value a
head.

With ``x`` a token's input (after the block's norm), ``H`` heads, a non-rotary
query/key width ``dn``, a rotary width ``dr`` that ALL heads share, a value
width ``dv`` and a latent rank ``r``::

    q               = x W_q                  H heads of [q_nope (dn) | q_pe (dr)]
    [c_raw | kpe_raw] = x W_kva              r + dr numbers, no head axis
    c               = RMSNorm(c_raw)         k_pe = R(kpe_raw, pos)   q_pe = R(q_pe, pos)
    [k_nope_h | v_h] = c W_kvb[:, h]         dn + dv a head

    expanded:  s_h(t, u) = a (q_nope_h(t) . k_nope_h(u) + q_pe_h(t) . k_pe(u))
               o_h       = sum_u softmax_u(s_h) v_h(u)
    absorbed:  q~_h      = q_nope_h W_uk_h^T                       (r numbers)
               s_h(t, u) = a (q~_h(t) . c(u) + q_pe_h(t) . k_pe(u))
               o_h       = (sum_u softmax_u(s_h) c(u)) W_uv_h

``W_kvb[:, h] = [W_uk_h | W_uv_h]``. The two forms are the same numbers; the
absorbed one attends over the latent itself, so a cached token is never
expanded. **What is cached a token and layer is** ``[c | k_pe]``: ``r + dr``
numbers (576 at the published sizes, 1,152 B in bf16).

``R`` is rotary over the ``dr`` dimensions, paired by halves as
:func:`models.transformer.apply_rope` pairs them (the published checkpoint
pairs them interleaved and permutes: with seeded weights a fixed permutation
of ``dr`` columns of ``W_q`` and ``W_kva``), with YaRN's frequencies where the
configuration has a ``rope_scaling`` block (:func:`yarn_frequencies`); the
score scale ``a`` is ``(dn + dr) ** -0.5`` times YaRN's ``mscale`` squared
(:func:`score_scale`).

**The page.** In decode mode the module declares ONE pool in the ``cache``
collection, ``cached_latent [num_pages, page_size, pool_width]``, beside which
the multi-head module declares a K and a V pool: the same first two axes, so
the allocator, the block tables, the prefix trie, copy-on-write and the
engine's page-copy program govern it unchanged. ``pool_width`` is ``r + dr``
rounded up to whole lanes of 128 (640 for 576): the TPU stores a ``[.., 576]``
bf16 array in tiles of 128 lanes, so the array takes 640 a token in HBM
whatever its logical shape says, and Mosaic refuses to copy a page out of a
pool whose last size is no whole number of tiles (``tests/test_chip_compile.py``
pins both). The lanes past ``r + dr`` are written as zeros and read by nobody.

**Which form a call runs** is decided from its shapes (:func:`absorb`), never
by an option: by cached token, the absorbed form costs every query ``2 H (2 r
+ dr)`` FLOP, the expanded form costs ``2 r H (dn + dv)`` once for the
expansion and every query ``2 H (dn + dr + dv)``. At the published sizes a
piece of up to 170 tokens is cheaper absorbed (a decode row: 35 kFLOP a cached
token against 4.2 MFLOP), a wider one expanded.

**What a call reads.** A row's pages are walked in blocks of
``ATTEND_BLOCK_TOKENS`` with an online softmax, as many blocks as the longest
row of the call holds (a trip count read from ``seq_lens``): a 64-token piece
over 8,800 cached tokens gathers 9,216 of them, not the 16,384 the table could
hold. The single-token decode step of a model built with ``paged_kernel``
goes to ``ops/paged_attention.py``'s latent kernel instead
(``attention._latent_decode_step``), which copies the pages that several rows
hold in common (a document the prefix trie handed to each asker) once for all
of them.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.models.mamba import token_mask
from distributed_pytorch_tpu.ops.attention import NEG_INF

F32 = jnp.float32
#: Cached tokens a step of the gather loop reads, a row.
ATTEND_BLOCK_TOKENS = 512
LANES = 128


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term ``0.1 m ln s + 1`` (1 at ``s <= 1``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(
    dim: int, theta: float, original_max: int, beta_fast: float,
    beta_slow: float,
) -> tuple:
    """``(low, high)``: the rotary pairs between which YaRN blends
    extrapolation into interpolation. ``corr(n) = dim ln(original_max / (2 pi
    n)) / (2 ln theta)`` is the pair that turns ``n`` times over the trained
    length."""

    def corr(n):
        return dim * math.log(original_max / (n * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    return low, high


def yarn_frequencies(dim: int, theta: float, yarn: Optional[dict]):
    """The ``dim / 2`` rotary frequencies: ``theta ** (-i / (dim / 2))``, and
    under a YaRN block ``f_i m_i + (f_i / factor)(1 - m_i)`` with ``m_i = 1 -
    clip((i - low) / (high - low), 0, 1)``: the fast pairs keep their
    frequency, the slow ones are interpolated by ``factor``."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    if not yarn:
        return freqs
    low, high = yarn_correction_range(
        dim, theta, yarn["original_max_position_embeddings"],
        yarn["beta_fast"], yarn["beta_slow"],
    )
    ramp = (jnp.arange(half, dtype=F32) - low) / max(high - low, 1e-3)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return freqs * keep + (freqs / yarn["factor"]) * (1.0 - keep)


def rope_multiplier(yarn: Optional[dict]) -> float:
    """What cos and sin are multiplied by: ``g(s, mscale) / g(s,
    mscale_all_dim)`` (1.0 where the two are equal, as published)."""
    if not yarn:
        return 1.0
    return yarn_mscale(yarn["factor"], yarn.get("mscale", 1.0)) / yarn_mscale(
        yarn["factor"], yarn.get("mscale_all_dim", 0.0) or 0.0
    )


def score_scale(qk_head_dim: int, yarn: Optional[dict]) -> float:
    """``qk_head_dim ** -0.5``, times ``g(s, mscale_all_dim) ** 2`` under a
    YaRN block that has one (0.114722 at 192, 40 and 0.707)."""
    scale = qk_head_dim**-0.5
    if yarn and yarn.get("mscale_all_dim"):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def rotate(x, positions, freqs, multiplier: float = 1.0):
    """Rotary embedding of ``x [B, T, ..., D]`` at ``positions [B, T]`` with
    the given ``D / 2`` frequencies, paired by halves."""
    half = x.shape[-1] // 2
    angles = positions.astype(F32)[..., None] * freqs  # [B, T, D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angles) * multiplier, jnp.sin(angles) * multiplier
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def absorb(t_step: int, n_heads: int, rank: int, dn: int, dr: int,
           dv: int) -> bool:
    """Whether a call of ``t_step`` queries a row runs the absorbed form: its
    FLOP by cached token, ``2 t H (2 r + dr)``, against the expanded form's,
    ``2 r H (dn + dv)`` to rebuild the token's keys and values and ``2 t H (dn
    + dr + dv)`` to attend (module docstring)."""
    absorbed = 2 * t_step * n_heads * (2 * rank + dr)
    expanded = 2 * rank * n_heads * (dn + dv) + 2 * t_step * n_heads * (
        dn + dr + dv
    )
    return absorbed <= expanded


def latent_norm(eps: float) -> nn.Module:
    """The latent's own RMSNorm (``kv_a_layernorm``): statistics in float32."""
    return nn.RMSNorm(epsilon=eps, dtype=F32, name="kv_norm")


def value_of(latent, rank: int):
    """A cached row's value: its own first ``rank`` numbers, ``c``."""
    return latent[..., :rank]


def _lanes(x, width: int):
    """``x`` with its last axis padded with zeros to ``width``."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def latent_row(c, k_pe, width: int):
    """What a token's row of the pool holds: ``[c | k_pe | zeros]``."""
    return _lanes(jnp.concatenate([c, k_pe], axis=-1), width)


class LatentAttention(nn.Module):
    """The module docstring's layer. ``[B, T, d_model] -> [B, T, d_model]``.

    Parameters: ``query/kernel [d, H, dn + dr]``, ``kv_a/kernel [d, r + dr]``,
    ``kv_norm/scale [r]``, ``kv_b [r, H, dn + dv]``, ``out/kernel [H, dv, d]``;
    no biases. Without ``decode`` (and on the cache-init pass) a call is the
    plain causal forward over its own tokens, expanded. With ``decode`` and a
    ``page_size`` it is a step against the paged latent pool and must be told
    ``block_tables [S, pages_per_seq]`` and ``seq_lens [S]`` (and, for a padded
    prefill piece, ``valid_lens [S]``), as :class:`models.transformer.Attention`
    is. ``row_groups`` is for the decode kernel alone: which rows share their
    tables' first pages (``ops/paged_attention.py`` ``shared_prefix_groups``),
    where the program has worked that out once for all its layers; a call
    that says nothing lets the kernel's wrapper work it out. A contiguous
    decode cache is not built: latent layers are served through pages."""

    n_heads: int
    d_model: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dtype: Any = F32
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn: Optional[tuple] = None  # the rope_scaling block as (key, value) pairs
    decode: bool = False
    page_size: int = 0
    num_pages: int = 0
    paged_kernel: str = ""  # see models.transformer.Attention

    @property
    def latent_width(self) -> int:
        """What a token caches: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """What a token's row of the pool holds: whole lanes."""
        return -(-self.latent_width // LANES) * LANES

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        block_tables: Optional[jnp.ndarray] = None,
        seq_lens: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
        row_groups=None,
    ) -> jnp.ndarray:
        h, r = self.n_heads, self.kv_lora_rank
        dn, dr, dv = (
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        )
        if min(h, r, dn, dr, dv) < 1 or dr % 2:
            raise ValueError(
                f"latent attention needs heads, a rank, both key widths "
                f"(the rotary one even) and a value width, got H={h} r={r} "
                f"dn={dn} dr={dr} dv={dv}"
            )
        if self.decode and not self.page_size:
            raise ValueError(
                "latent attention keeps no contiguous decode cache: it is "
                "served through pages (decode=True needs page_size > 0)"
            )
        if self.page_size and not self.decode:
            raise ValueError("page_size > 0 requires decode=True")
        if self.page_size and self.num_pages < 2:
            raise ValueError(
                "paged decode needs num_pages >= 2 (page 0 is the reserved "
                f"null page), got {self.num_pages}"
            )
        if self.paged_kernel:
            if not self.page_size:
                raise ValueError(
                    "paged_kernel requires the paged cache (page_size > 0)"
                )
            from distributed_pytorch_tpu.ops.paged_attention import (
                resolve_kernel,
            )

            resolve_kernel(self.paged_kernel)
        yarn = dict(self.yarn) if self.yarn else None
        freqs = yarn_frequencies(dr, self.rope_theta, yarn)
        multiplier = rope_multiplier(yarn)
        scale = score_scale(dn + dr, yarn)

        query = nn.DenseGeneral(
            (h, dn + dr), dtype=self.dtype, use_bias=False, name="query"
        )
        kv_a = nn.Dense(
            r + dr, dtype=self.dtype, use_bias=False, name="kv_a"
        )
        kv_norm = latent_norm(self.norm_eps)
        w_kvb = self.param(
            "kv_b", nn.initializers.lecun_normal(), (r, h, dn + dv)
        ).astype(self.dtype)
        out_proj = nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=self.dtype, use_bias=False,
            name="out",
        )

        paged = self.decode and self.has_variable("cache", "cached_latent")
        batch, t_step = x.shape[:2]
        if paged:
            if block_tables is None or seq_lens is None:
                raise ValueError(
                    "paged decode requires block_tables and seq_lens every "
                    "step (the serving engine passes them)"
                )
            seq_lens = seq_lens.astype(jnp.int32)
            positions = seq_lens[:, None] + jnp.arange(t_step, dtype=jnp.int32)
        else:
            positions = jnp.broadcast_to(
                jnp.arange(t_step, dtype=jnp.int32), (batch, t_step)
            )
            if self.decode:
                # Cache init pass: size the pool, then the plain forward.
                self.variable(
                    "cache", "cached_latent", jnp.zeros,
                    (self.num_pages, self.page_size, self.pool_width),
                    self.dtype,
                )

        with jax.named_scope("mla.project"):
            q = query(x)  # [B, T, H, dn + dr]
            q_nope = q[..., :dn]
            q_pe = rotate(q[..., dn:], positions, freqs, multiplier)
            latent = kv_a(x)  # [B, T, r + dr]
            c = kv_norm(latent[..., :r]).astype(self.dtype)
            k_pe = rotate(latent[..., r:], positions, freqs, multiplier)

        if not paged:
            with jax.named_scope("mla.expand"):
                kv = jnp.einsum("bkr,rhn->bkhn", c, w_kvb)
            logits = (
                jnp.einsum(
                    "bthn,bkhn->bhtk", q_nope, kv[..., :dn],
                    preferred_element_type=F32,
                )
                + jnp.einsum(
                    "bthd,bkd->bhtk", q_pe, k_pe, preferred_element_type=F32
                )
            ) * scale
            causal = positions[:, None, :, None] >= positions[:, None, None, :]
            weights = jax.nn.softmax(
                jnp.where(causal, logits, NEG_INF), axis=-1
            ).astype(self.dtype)
            out = jnp.einsum("bhtk,bkhv->bthv", weights, kv[..., dn:])
            return out_proj(out)

        pool = self.variable("cache", "cached_latent", lambda: None)
        page = self.page_size
        pages_per_seq = block_tables.shape[1]
        with jax.named_scope("mla.write"):
            # As Attention._paged_decode_step writes K and V: a position at or
            # past the row's table, and the padding of a prefill piece, go to
            # the reserved null page.
            flat_pos = positions.reshape(-1)
            logical = jnp.clip(flat_pos // page, 0, pages_per_seq - 1)
            rows = jnp.repeat(jnp.arange(batch, dtype=jnp.int32), t_step)
            phys = block_tables[rows, logical]
            kept = flat_pos < pages_per_seq * page
            if valid_lens is not None:
                kept &= token_mask(valid_lens, t_step).reshape(-1)
            phys = jnp.where(kept, phys, 0)
            row = latent_row(c, k_pe, self.pool_width).reshape(
                batch * t_step, self.pool_width
            )
            pool.value = pool.value.at[phys, flat_pos % page].set(
                row.astype(pool.value.dtype)
            )

        # The decode kernel attends over the latent itself: absorbed, as the
        # arithmetic says of any single-token call.
        use_kernel = bool(self.paged_kernel) and t_step == 1
        absorbed = use_kernel or absorb(t_step, h, r, dn, dr, dv)
        if absorbed:
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_kvb[..., :dn])
        if use_kernel:
            from distributed_pytorch_tpu.ops.paged_attention import (
                paged_latent_attention,
            )

            q_row = _lanes(
                jnp.concatenate([q_lat, q_pe], axis=-1), self.pool_width
            )
            mixed = paged_latent_attention(
                q_row, pool.value, block_tables, seq_lens, v_width=r,
                kernel=self.paged_kernel, sm_scale=scale,
                row_groups=row_groups,
            )
        else:
            held = seq_lens + (t_step if valid_lens is None else valid_lens)
            mixed = _attend_blocks(
                q_lat if absorbed else q_nope, q_pe, pool.value, block_tables,
                positions, jnp.max(held), scale=scale, rank=r, dr=dr,
                w_kvb=None if absorbed else w_kvb, dn=dn,
            )
        if absorbed:
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum(
                    "bthr,rhv->bthv", mixed.astype(self.dtype),
                    w_kvb[..., dn:],
                )
        else:
            out = mixed.astype(self.dtype)
        return out_proj(out)


def _attend_blocks(
    q_main, q_pe, pool, block_tables, positions, n_keys, *, scale, rank, dr,
    w_kvb, dn,
):
    """Attention of ``[B, T, H, .]`` queries over the rows' paged latents, a
    block of pages at a time with an online softmax, over the first
    ``n_keys`` (traced) key positions only. ``w_kvb=None`` is the absorbed
    form: ``q_main`` is ``q~ [B, T, H, r]`` and the result the weighted sum of
    ``c`` ``[B, T, H, r]`` (float32). Else the expanded form: ``q_main`` is
    ``q_nope``, every block's keys and values are rebuilt from its ``c``, and
    the result is ``[B, T, H, dv]``."""
    batch, t_step, h = q_main.shape[:3]
    page = pool.shape[1]
    pages_per_seq = block_tables.shape[1]
    bp = max(1, min(ATTEND_BLOCK_TOKENS // page, pages_per_seq))
    bkv = bp * page
    n_blocks_max = -(-pages_per_seq // bp)
    tables = jnp.pad(
        block_tables, ((0, 0), (0, n_blocks_max * bp - pages_per_seq))
    )
    width = rank if w_kvb is None else w_kvb.shape[-1] - dn
    n_blocks = jnp.clip((n_keys + bkv - 1) // bkv, 1, n_blocks_max)

    def block(j, carry):
        m_prev, l_prev, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        blk = pool[ids].reshape(batch, bkv, pool.shape[-1])
        c, k_pe = value_of(blk, rank), blk[..., rank : rank + dr]
        if w_kvb is None:
            keys = values = c
            main = jnp.einsum(
                "bthr,bkr->bhtk", q_main, keys, preferred_element_type=F32
            )
        else:
            with jax.named_scope("mla.expand"):
                kv = jnp.einsum("bkr,rhn->bkhn", c, w_kvb)
            keys, values = kv[..., :dn], kv[..., dn:]
            main = jnp.einsum(
                "bthn,bkhn->bhtk", q_main, keys, preferred_element_type=F32
            )
        s = (
            main
            + jnp.einsum(
                "bthd,bkd->bhtk", q_pe, k_pe, preferred_element_type=F32
            )
        ) * scale
        k_abs = j * bkv + jnp.arange(bkv, dtype=jnp.int32)
        visible = k_abs[None, None, :] <= positions[:, :, None]  # [B, T, K]
        s = jnp.where(visible[:, None], s, NEG_INF)
        # Block 0 holds key 0, which every query sees: the running max is
        # finite from the first block on.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=-1)
        p = p.astype(values.dtype)
        if w_kvb is None:
            pv = jnp.einsum(
                "bhtk,bkr->bhtr", p, values, preferred_element_type=F32
            )
        else:
            pv = jnp.einsum(
                "bhtk,bkhv->bhtv", p, values, preferred_element_type=F32
            )
        return m_new, l_new, acc * correction[..., None] + pv

    init = (
        jnp.full((batch, h, t_step), NEG_INF, F32),
        jnp.zeros((batch, h, t_step), F32),
        jnp.zeros((batch, h, t_step, width), F32),
    )
    _, l_fin, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    return (acc / l_fin[..., None]).transpose(0, 2, 1, 3)  # [B, T, H, .]

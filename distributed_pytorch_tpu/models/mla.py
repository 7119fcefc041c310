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

**What a layer may add**, each by a field that defaults to none of it:

* ``q_lora_rank``: the query through a latent of its own, ``c_q =
  RMSNorm(x W_qa)``, ``q = c_q W_qb`` (``q_a``, ``q_norm``, ``query``).
* ``lora_rescale``: ``c_q`` and ``c`` times ``sqrt(d_model / rank)`` after
  their norms (the cached ``c`` is the rescaled one).
* ``gate``: ``g = sigmoid(x W_g)``, one number a head, on the head's output
  before ``W_o``.
* ``window``: a query at ``t`` sees the keys ``(t - window, t]``. A decode
  step walks only the pages that meet the window (the kernel is handed the
  row's table from the window's first live page on), a prefill piece only
  the blocks that do. The pages behind the window stay where they are.
* an INDEXER (``index_heads``, ``index_dim``, ``index_top_k``; needs
  ``q_lora_rank``): ``q^I_h = c_q W_Iq`` (``index_heads`` of ``index_dim``),
  ``k^I = LayerNorm(x W_Ik)`` (one ``index_dim``-wide key a token), rotary on
  the first ``dr`` of each, ``w = x W_Iw``; ``I(t, s) = sum_h w_{t,h}
  index_heads ** -0.5 relu(q^I_{t,h} . k^I_s) index_dim ** -0.5`` in float32;
  a query attends over the ``index_top_k`` positions ``s <= t`` of largest
  ``I(t, s)`` alone (all of them while ``t < index_top_k``; exact:
  ``ops/paged_attention.py`` ``top_k_mask``). Such a layer keeps a SECOND
  pool, ``cached_index [num_pages, page_size, index_dim in whole lanes]``,
  under the same tables. A decode step scores the row's index-key pages
  (``attention._index_scores``), selects, gathers the selected tokens' latents
  and attends over them (``attention._sparse_latent_decode_step``); a prefill
  piece masks the blocked walk by each query's selection.
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


def whole_lanes(width: int) -> int:
    """``width`` numbers as a pool's row holds them: whole lanes."""
    return -(-width // LANES) * LANES


def _lanes(x, width: int):
    """``x`` with its last axis padded with zeros to ``width``."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def latent_row(c, k_pe, width: int):
    """What a token's row of the pool holds: ``[c | k_pe | zeros]``."""
    return _lanes(jnp.concatenate([c, k_pe], axis=-1), width)


def index_row(k_idx, width: int):
    """What a token's row of the index-key pool holds: ``[k^I | zeros]``."""
    return _lanes(k_idx, width)


class LatentAttention(nn.Module):
    """The module docstring's layer. ``[B, T, d_model] -> [B, T, d_model]``.

    Parameters: ``query/kernel [d, H, dn + dr]``, ``kv_a/kernel [d, r + dr]``,
    ``kv_norm/scale [r]``, ``kv_b [r, H, dn + dv]``, ``out/kernel [H, dv, d]``;
    no biases. With ``q_lora_rank`` ``rq``: ``q_a/kernel [d, rq]``,
    ``q_norm/scale [rq]`` and ``query/kernel [rq, H, dn + dr]``; with ``gate``
    ``gate/kernel [d, H]``; with an indexer ``index_q/kernel [rq, Hi, Di]``,
    ``index_k/kernel [d, Di]``, ``index_k_norm/scale, bias [Di]`` and
    ``index_w/kernel [d, Hi]``. Without ``decode`` (and on the cache-init pass) a call is the
    plain causal forward over its own tokens, expanded. With ``decode`` and a
    ``page_size`` it is a step against the paged latent pool and must be told
    ``block_tables [S, pages_per_seq]`` and ``seq_lens [S]`` (and, for a padded
    prefill piece, ``valid_lens [S]``), as :class:`models.transformer.Attention`
    is. ``row_groups`` is for the decode kernels alone (the latent kernel and
    an indexed layer's index kernel): which rows share their tables' first
    pages (``ops/paged_attention.py`` ``shared_prefix_groups``), where the
    program has worked that out once for all its layers; a call that says
    nothing lets the kernel's wrapper work it out. A contiguous
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
    # What a layer may add (module docstring); the defaults add nothing.
    q_lora_rank: int = 0
    lora_rescale: bool = False
    gate: bool = False
    window: int = 0
    index_heads: int = 0
    index_dim: int = 0
    index_top_k: int = 0

    @property
    def latent_width(self) -> int:
        """What a token caches: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """What a token's row of the pool holds: whole lanes."""
        return whole_lanes(self.latent_width)

    @property
    def index_pool_width(self) -> int:
        """What a token's row of the index-key pool holds: whole lanes."""
        return whole_lanes(self.index_dim)

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
        indexed = self.index_top_k > 0
        if indexed and (
            min(self.index_heads, self.index_dim) < 1
            or self.index_dim < dr or not self.q_lora_rank or self.window
        ):
            raise ValueError(
                "an indexer needs index_heads, an index_dim of at least the "
                "rotary width, the query's latent (q_lora_rank) and no "
                f"window, got heads={self.index_heads} dim={self.index_dim} "
                f"q_lora_rank={self.q_lora_rank} window={self.window}"
            )
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")
        yarn = dict(self.yarn) if self.yarn else None
        freqs = yarn_frequencies(dr, self.rope_theta, yarn)
        multiplier = rope_multiplier(yarn)
        scale = score_scale(dn + dr, yarn)

        query = nn.DenseGeneral(
            (h, dn + dr), dtype=self.dtype, use_bias=False, name="query"
        )
        rq = self.q_lora_rank
        if rq:
            q_a = nn.Dense(rq, dtype=self.dtype, use_bias=False, name="q_a")
            q_norm = nn.RMSNorm(epsilon=self.norm_eps, dtype=F32, name="q_norm")
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

        if self.gate:
            gate = nn.Dense(h, dtype=self.dtype, use_bias=False, name="gate")
        if indexed:
            index_q = nn.DenseGeneral(
                (self.index_heads, self.index_dim), dtype=self.dtype,
                use_bias=False, name="index_q",
            )
            index_k = nn.Dense(
                self.index_dim, dtype=self.dtype, use_bias=False,
                name="index_k",
            )
            index_k_norm = nn.LayerNorm(
                epsilon=self.norm_eps, dtype=F32, name="index_k_norm"
            )
            index_w = nn.Dense(
                self.index_heads, dtype=self.dtype, use_bias=False,
                name="index_w",
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
                if indexed:
                    self.variable(
                        "cache", "cached_index", jnp.zeros,
                        (self.num_pages, self.page_size,
                         self.index_pool_width),
                        self.dtype,
                    )

        def rescaled(y, rank):
            if not self.lora_rescale:
                return y
            return y * jnp.asarray(math.sqrt(self.d_model / rank), y.dtype)

        with jax.named_scope("mla.project"):
            c_q = x
            if rq:
                c_q = rescaled(q_norm(q_a(x)), rq).astype(self.dtype)
            q = query(c_q)  # [B, T, H, dn + dr]
            q_nope = q[..., :dn]
            q_pe = rotate(q[..., dn:], positions, freqs, multiplier)
            latent = kv_a(x)  # [B, T, r + dr]
            c = rescaled(kv_norm(latent[..., :r]), r).astype(self.dtype)
            k_pe = rotate(latent[..., r:], positions, freqs, multiplier)
        if indexed:
            with jax.named_scope("dsa.project"):
                def first_rotated(y):
                    return jnp.concatenate([
                        rotate(y[..., :dr], positions, freqs, multiplier),
                        y[..., dr:],
                    ], axis=-1)

                q_idx = first_rotated(index_q(c_q))  # [B, T, Hi, Di]
                k_idx = first_rotated(
                    index_k_norm(index_k(x)).astype(self.dtype)
                )  # [B, T, Di]
                # The scores' two scales ride on the head weights.
                w_idx = index_w(x).astype(F32) * (
                    self.index_heads**-0.5 * self.index_dim**-0.5
                )  # [B, T, Hi]

        def gated(out):
            """``out [B, T, H, dv]`` under the layer's head-wise gate."""
            if not self.gate:
                return out
            g = jax.nn.sigmoid(gate(x).astype(F32)).astype(out.dtype)
            return out * g[..., None]

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
            causal = positions[:, :, None] >= positions[:, None, :]  # [B, T, K]
            if self.window:
                causal &= (
                    positions[:, :, None] - positions[:, None, :] < self.window
                )
            if indexed:
                from distributed_pytorch_tpu.ops.paged_attention import (
                    top_k_mask,
                )

                scores = jnp.einsum(
                    "bthk,bth->btk",
                    jax.nn.relu(jnp.einsum(
                        "bthd,bkd->bthk", q_idx, k_idx,
                        preferred_element_type=F32,
                    )),
                    w_idx,
                )
                causal &= top_k_mask(
                    jnp.where(causal, scores, -jnp.inf), self.index_top_k
                )
            weights = jax.nn.softmax(
                jnp.where(causal[:, None], logits, NEG_INF), axis=-1
            ).astype(self.dtype)
            out = jnp.einsum("bhtk,bkhv->bthv", weights, kv[..., dn:])
            return out_proj(gated(out))

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
            if indexed:
                index_pool = self.variable("cache", "cached_index", lambda: None)
                index_pool.value = index_pool.value.at[
                    phys, flat_pos % page
                ].set(
                    index_row(k_idx, self.index_pool_width).reshape(
                        batch * t_step, self.index_pool_width
                    ).astype(index_pool.value.dtype)
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
            if indexed:
                from distributed_pytorch_tpu.ops.paged_attention import (
                    paged_index_scores,
                    selected_positions,
                    sparse_latent_attention,
                    top_k_mask,
                )

                with jax.named_scope("dsa.select"):
                    scores = paged_index_scores(
                        _lanes(q_idx[:, 0], self.index_pool_width),
                        w_idx[:, 0], index_pool.value, block_tables, seq_lens,
                        kernel=self.paged_kernel, row_groups=row_groups,
                    )  # [B, table tokens] float32
                    chosen, real = selected_positions(
                        top_k_mask(scores, self.index_top_k),
                        min(self.index_top_k, scores.shape[-1]),
                        kernel=self.paged_kernel,
                    )
                    # For who asks what was selected (the serving engine's
                    # ``selected_positions``): -1 past a row's own.
                    self.sow(
                        "selection", "positions", jnp.where(real, chosen, -1)
                    )
                mixed = sparse_latent_attention(
                    q_row, pool.value, block_tables, chosen, real, v_width=r,
                    kernel=self.paged_kernel, sm_scale=scale,
                )
            else:
                mixed = paged_latent_attention(
                    q_row, pool.value, block_tables, seq_lens, v_width=r,
                    kernel=self.paged_kernel, sm_scale=scale,
                    row_groups=row_groups,
                    **({"window": self.window} if self.window else {}),
                )
        else:
            held = seq_lens + (t_step if valid_lens is None else valid_lens)
            more = {}
            if self.window:
                more["window"] = self.window
            if indexed:
                from distributed_pytorch_tpu.ops.paged_attention import (
                    top_k_mask,
                )

                with jax.named_scope("dsa.select"):
                    more["selected"] = top_k_mask(
                        _index_scores_blocks(
                            q_idx, w_idx, index_pool.value, block_tables,
                            positions, jnp.max(held),
                        ),
                        self.index_top_k,
                    )
            mixed = _attend_blocks(
                q_lat if absorbed else q_nope, q_pe, pool.value, block_tables,
                positions, jnp.max(held), scale=scale, rank=r, dr=dr,
                w_kvb=None if absorbed else w_kvb, dn=dn, **more,
            )
        if absorbed:
            with jax.named_scope("mla.absorb"):
                out = jnp.einsum(
                    "bthr,rhv->bthv", mixed.astype(self.dtype),
                    w_kvb[..., dn:],
                )
        else:
            out = mixed.astype(self.dtype)
        return out_proj(gated(out))


def _block_geometry(pool, block_tables):
    """``(pages a block, tokens a block, blocks a table, the tables padded to
    whole blocks)`` of the gather loops over a row's pages."""
    page = pool.shape[1]
    pages_per_seq = block_tables.shape[1]
    bp = max(1, min(ATTEND_BLOCK_TOKENS // page, pages_per_seq))
    n_blocks_max = -(-pages_per_seq // bp)
    tables = jnp.pad(
        block_tables, ((0, 0), (0, n_blocks_max * bp - pages_per_seq))
    )
    return bp, bp * page, n_blocks_max, tables


def _index_scores_blocks(q_idx, w_idx, pool, block_tables, positions, n_keys):
    """The indexer's scores of ``[B, T, Hi, Di]`` index queries (head weights
    ``w_idx [B, T, Hi]``, the scales folded in) against the rows' paged index
    keys, a block of pages at a time over the first ``n_keys`` (traced) key
    positions: float32 ``[B, T, table tokens in whole blocks]``, ``-inf`` at
    every key a query does not see and at every block not walked."""
    batch, t_step = q_idx.shape[:2]
    bp, bkv, n_blocks_max, tables = _block_geometry(pool, block_tables)
    n_blocks = jnp.clip((n_keys + bkv - 1) // bkv, 1, n_blocks_max)
    width = q_idx.shape[-1]

    def block(j, scores):
        ids = jax.lax.dynamic_slice_in_dim(tables, j * bp, bp, axis=1)
        keys = pool[ids].reshape(batch, bkv, pool.shape[-1])[..., :width]
        s = jnp.einsum(
            "bthk,bth->btk",
            jax.nn.relu(jnp.einsum(
                "bthd,bkd->bthk", q_idx, keys, preferred_element_type=F32
            )),
            w_idx,
        )
        k_abs = j * bkv + jnp.arange(bkv, dtype=jnp.int32)
        s = jnp.where(k_abs[None, None, :] <= positions[:, :, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(scores, s, j * bkv, axis=2)

    return jax.lax.fori_loop(
        0, n_blocks, block,
        jnp.full((batch, t_step, n_blocks_max * bkv), -jnp.inf, F32),
    )


def _attend_blocks(
    q_main, q_pe, pool, block_tables, positions, n_keys, *, scale, rank, dr,
    w_kvb, dn, window: int = 0, selected=None,
):
    """Attention of ``[B, T, H, .]`` queries over the rows' paged latents, a
    block of pages at a time with an online softmax, over the first
    ``n_keys`` (traced) key positions only. ``w_kvb=None`` is the absorbed
    form: ``q_main`` is ``q~ [B, T, H, r]`` and the result the weighted sum of
    ``c`` ``[B, T, H, r]`` (float32). Else the expanded form: ``q_main`` is
    ``q_nope``, every block's keys and values are rebuilt from its ``c``, and
    the result is ``[B, T, H, dv]``.

    ``window`` keeps a query at ``t`` to the keys ``(t - window, t]`` and
    starts the walk at the first block that any query's window meets;
    ``selected [B, T, table tokens in whole blocks]`` (bool) keeps a query to
    the keys it marks. Under either a query may see nothing in a block: its
    weights there are zeroed, not left to the running max."""
    batch, t_step, h = q_main.shape[:3]
    bp, bkv, n_blocks_max, tables = _block_geometry(pool, block_tables)
    width = rank if w_kvb is None else w_kvb.shape[-1] - dn
    n_blocks = jnp.clip((n_keys + bkv - 1) // bkv, 1, n_blocks_max)
    narrowed = bool(window) or selected is not None
    first_block = 0
    if window:
        first_block = jnp.clip(
            (jnp.min(positions) - (window - 1)) // bkv, 0, n_blocks - 1
        )

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
        if window:
            visible &= k_abs[None, None, :] > positions[:, :, None] - window
        if selected is not None:
            visible &= jax.lax.dynamic_slice_in_dim(
                selected, j * bkv, bkv, axis=2
            )
        s = jnp.where(visible[:, None], s, NEG_INF)
        # Block 0 holds key 0, which every query sees: the running max is
        # finite from the first block on.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if narrowed:
            p = jnp.where(visible[:, None], p, 0.0)
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
    _, l_fin, acc = jax.lax.fori_loop(first_block, n_blocks, block, init)
    if narrowed:
        # A row that carries no request (a dead decode row, a piece's
        # padding) may see no key at all: its output is nobody's.
        l_fin = jnp.maximum(l_fin, jnp.finfo(F32).tiny)
    return (acc / l_fin[..., None]).transpose(0, 2, 1, 3)  # [B, T, H, .]

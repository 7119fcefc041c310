"""Compressed convolutional attention (CCA, arXiv:2510.04476, as the ``zaya``
family runs it): grouped-query attention whose queries and keys are mixed
along the SEQUENCE by two short causal convolutions before the scores, and
whose value is half this token's and half the previous one's.

Over a block's normed input ``n [B, T, d]`` (``H`` query heads on ``G`` KV
heads of ``D``, ``r = H / G``; ``C = (H + G) D`` channels, ``H + G`` heads of
``D``; ``K0 = time0``, ``K1 = time1`` taps)::

    u_t   = [W_q n_t ; W_k n_t]                          d -> C
    a_t   = b0 + sum_{i<K0} w0[i] * u_{t-K0+1+i}         depthwise, causal
    c_t^h = b1^h + sum_{i<K1} W1[i]^h a^h_{t-K1+1+i}     a [D, D] matrix a tap
                                                         and head, heads apart
    m_q   = (q~_h + k~_g) / 2,  m_k = (mean_{h in g} q~_h + k~_g) / 2
                                                         of u_t's own halves
    q, k  = c[q] + m_q,  c[k] + m_k
    q, k  = sqrt(D) q / |q|_2,  sqrt(D) exp(tau_g) k / |k|_2     a head
    q, k  = R(q, t), R(k, t)         rotary over the FIRST ``rotary_dim`` of D
    v_t   = [W_v1 n_t ; W_v2 n_{t-1}]                    split into G heads
    o     = softmax(q k^T / sqrt(D)) v   causal, r query heads a KV head
    out   = W_o o                                        H D -> d

``u`` and ``a`` before the sequence's first token are zeros, as is ``W_v2
n_{-1}``. Projections run in the module's ``dtype``; the depthwise taps, the
means, the L2 norms, ``exp(tau)`` and the rotation are float32, the second
convolution's products in ``dtype`` accumulated in float32. The L2 norm is
``models/gated_delta.py``'s (``L2_EPS`` inside the root).

**Decode mode** keeps BOTH kinds of cache a layer can have: K and V page pools
``[num_pages, page_size, G, D]`` (``k`` after the rotation and ``v`` after the
shift: what ``ops/paged_attention.py`` reads, through the writes every K/V
layer uses), and ``models/mamba.py``'s two ``STATE_KEYS`` leaves, a row per
engine slot, float32: ``conv_state [slots, (K0 - 1) + (K1 - 1), C]`` (the last
``u`` then the last ``a``) and ``scan_state [slots, G D / 2]`` (``W_v2 n`` of
the last token), under that file's rules: ``state_slots``, a row at
``seq_lens`` 0 starts from zeros, a batch as long as the slot table is updated
in place under the mask, ``valid_lens`` marks a padded piece's own tokens (the
tails kept are those that END at the valid length). ONE body serves a prefill
piece ``[1, width]`` and the batched decode step ``[slots, 1]``.

The options below the sizes are what the published ``config.json`` has no key
for (a benchmark configuration lists them under ``assumed``); each changes one
line here.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.models import mamba
from distributed_pytorch_tpu.models.gated_delta import l2_normalised

F32 = jnp.float32


def causal_taps(tail, x, taps: int):
    """``[tail ; x]`` along the sequence, and its ``taps`` causal views
    ``[.., T, ..]`` oldest first: view ``i`` holds ``x_{t - taps + 1 + i}``."""
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    return padded, [padded[:, i : i + t] for i in range(taps)]


class CCAttention(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    time0: int = 2
    time1: int = 2
    rotary_dim: int = 0  # of a head's dimensions, the first; 0 = all
    rope_theta: float = 10000.0
    dtype: Any = F32
    decode: bool = False
    page_size: int = 0
    num_pages: int = 0
    paged_kernel: str = ""  # see models/transformer.py Attention
    conv_bias: bool = True  # b0 and b1
    qk_mean: bool = True  # m_q and m_k
    key_temperature: bool = True  # exp(tau_g) on k
    value_shift: bool = True  # False: v_t = [W_v1 n_t ; W_v2 n_t]

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        block_tables: Optional[jnp.ndarray] = None,
        seq_lens: Optional[jnp.ndarray] = None,
        state_slots: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        from distributed_pytorch_tpu.models.transformer import apply_rope

        batch, t, _ = x.shape
        h, g, d = self.n_heads, self.n_kv_heads, self.head_dim
        if h % g or (g * d) % 2 or min(self.time0, self.time1) < 1:
            raise ValueError(
                f"{h} query heads on {g} KV heads of {d}, taps "
                f"{self.time0} and {self.time1}"
            )
        heads, r, half = h + g, h // g, g * d // 2
        chans = heads * d
        k0, k1 = self.time0 - 1, self.time1 - 1  # tokens each tail keeps
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype, name=name
        )

        cached = self.decode and self.has_variable("cache", "scan_state")
        if self.decode and not cached:
            # Cache init pass: the layer's page pools, and one state row per
            # row of this call (the engine inits with a [max_slots, 1] batch).
            if self.page_size < 1 or self.num_pages < 2:
                raise ValueError(
                    "a decode-mode CCA layer keeps its K and V in pages "
                    "(page_size > 0, num_pages >= 2: page 0 is the null page)"
                )
            pool = (self.num_pages, self.page_size, g, d)
            self.variable("cache", "cached_key", jnp.zeros, pool, self.dtype)
            self.variable("cache", "cached_value", jnp.zeros, pool, self.dtype)
            self.variable(
                "cache", "conv_state", jnp.zeros,
                (batch, k0 + k1, chans), mamba.STATE_DTYPE,
            )
            self.variable(
                "cache", "scan_state", jnp.zeros,
                (batch, half), mamba.STATE_DTYPE,
            )
        if cached:
            if state_slots is None or seq_lens is None or block_tables is None:
                raise ValueError(
                    "a decode-mode CCA layer requires block_tables, seq_lens "
                    "and state_slots every step (the serving engine passes "
                    "them)"
                )
            conv_var = self.variable("cache", "conv_state", lambda: None)
            shift_var = self.variable("cache", "scan_state", lambda: None)
            tails = mamba.load_rows(conv_var.value, state_slots, seq_lens)
            tail_v = mamba.load_rows(shift_var.value, state_slots, seq_lens)
            positions = seq_lens.astype(jnp.int32)[:, None] + jnp.arange(
                t, dtype=jnp.int32
            )
        else:
            tails = jnp.zeros((batch, k0 + k1, chans), mamba.STATE_DTYPE)
            tail_v = jnp.zeros((batch, half), mamba.STATE_DTYPE)
            positions = None

        u = jnp.concatenate(
            [dense(h * d, "q_proj")(x), dense(g * d, "k_proj")(x)], axis=-1
        ).astype(F32)
        vv = dense(g * d, "v_proj")(x)
        w0 = self.param(
            "conv0_kernel", nn.initializers.lecun_normal(),
            (self.time0, chans), F32,
        )
        w1 = self.param(
            "conv1_kernel", nn.initializers.lecun_normal(),
            (self.time1, heads, d, d), F32,
        )
        with jax.named_scope("cca.conv"):
            pad_u, views = causal_taps(tails[:, :k0], u, self.time0)
            a = sum(w0[i].astype(F32) * views[i] for i in range(self.time0))
            if self.conv_bias:
                a = a + self.param(
                    "conv0_bias", nn.initializers.zeros_init(), (chans,), F32
                ).astype(F32)
            pad_a, views = causal_taps(tails[:, k0:], a, self.time1)
            c = sum(
                jnp.einsum(
                    "bthk,hkj->bthj",
                    views[i].reshape(batch, t, heads, d).astype(self.dtype),
                    w1[i].astype(self.dtype), preferred_element_type=F32,
                )
                for i in range(self.time1)
            )
            if self.conv_bias:
                c = c + self.param(
                    "conv1_bias", nn.initializers.zeros_init(), (chans,), F32
                ).astype(F32).reshape(heads, d)
        with jax.named_scope("cca.qk"):
            q, k = c[:, :, :h], c[:, :, h:]
            if self.qk_mean:
                q_raw = u[..., : h * d].reshape(batch, t, g, r, d)
                k_raw = u[..., h * d :].reshape(batch, t, g, 1, d)
                q = q + ((q_raw + k_raw) * 0.5).reshape(batch, t, h, d)
                k = k + (
                    (jnp.mean(q_raw, axis=3, keepdims=True) + k_raw) * 0.5
                ).reshape(batch, t, g, d)
            q = l2_normalised(q) * d**0.5
            k = l2_normalised(k) * d**0.5
            if self.key_temperature:
                tau = self.param(
                    "temperature", nn.initializers.zeros_init(), (g,), F32
                )
                k = k * jnp.exp(tau.astype(F32))[:, None]
            rope = dict(
                theta=self.rope_theta, positions=positions,
                rotary_dim=self.rotary_dim or None,
            )
            q = apply_rope(q, **rope).astype(self.dtype)
            k = apply_rope(k, **rope).astype(self.dtype)
        with jax.named_scope("cca.shift"):
            now, late = vv[..., :half], vv[..., half:].astype(F32)
            pad_v = jnp.concatenate([tail_v[:, None], late], axis=1)
            if self.value_shift:
                late = pad_v[:, :t]
            v = jnp.concatenate([now, late.astype(self.dtype)], axis=-1)
            v = v.reshape(batch, t, g, d)

        if cached:
            with jax.named_scope("cca.state"):
                conv_var.value = mamba.store_rows(
                    conv_var.value,
                    jnp.concatenate(
                        [mamba.conv_tail(pad_u, k0, valid_lens),
                         mamba.conv_tail(pad_a, k1, valid_lens)], axis=1,
                    ),
                    state_slots,
                )
                shift_var.value = mamba.store_rows(
                    shift_var.value,
                    mamba.conv_tail(pad_v, 1, valid_lens)[:, 0], state_slots,
                )
            out = self._paged(q, k, v, block_tables, positions, valid_lens)
        else:
            from distributed_pytorch_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(
                q, jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2),
                causal=True,
            )
        return nn.DenseGeneral(
            self.d_model, axis=(-2, -1), dtype=self.dtype, use_bias=False,
            name="o_proj",
        )(out)

    def _paged(self, q, k, v, block_tables, positions, valid_lens):
        """Write-then-attend against the layer's page pools, as
        ``Attention._paged_decode_step`` does it: the same scatter
        (``page_slots``), the same one read (``paged_attention``)."""
        from distributed_pytorch_tpu.models.transformer import page_slots
        from distributed_pytorch_tpu.ops.paged_attention import (
            paged_attention,
        )

        keys = self.variable("cache", "cached_key", lambda: None)
        values = self.variable("cache", "cached_value", lambda: None)
        g, d = k.shape[2:]
        phys, offset = page_slots(
            block_tables, positions, self.page_size, valid_lens
        )
        keys.value = keys.value.at[phys, offset].set(
            k.astype(keys.value.dtype).reshape(-1, g, d)
        )
        values.value = values.value.at[phys, offset].set(
            v.astype(values.value.dtype).reshape(-1, g, d)
        )
        return paged_attention(
            q, keys.value, values.value, block_tables, positions[:, 0],
            valid_lens=valid_lens, kernel=self.paged_kernel or "xla",
        )

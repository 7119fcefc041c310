"""The gated delta-rule mixer (Gated DeltaNet, arXiv:2412.06464, as the
``olmo_hybrid`` family runs it): linear attention with a MATRIX state a head,
``S [d_k, d_v]`` float32, that is read before it is written.

Over a sequence ``x [B, T, d]`` (``H`` heads; ``K = d_conv``)::

    q~, k~, v~ = W_q x, W_k x, W_v x              d -> H d_k, H d_k, H d_v
    [q', k', v'] = silu(conv_K([q~, k~, v~]))     depthwise causal, no bias
    q = q' / |q'|_2 * d_k^-1/2,  k = k' / |k'|_2  a head
    beta  = 2 sigmoid(W_b x)                      a head, in (0, 2)
    alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))      a head, in (0, 1)
    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = W_o [rmsnorm_{d_v}(o_t) * silu(W_g x)]_heads      H d_v -> d

(``neg_eigval=False`` drops ``beta``'s factor 2: ``I - beta k k^T`` then has
no negative eigenvalue.) Projections run in the module's ``dtype`` and the
conv reads its taps (and keeps its tail) in it; the L2 norms, ``alpha``,
``beta``, the state, every sum of the recurrence (the blocks' triangular solve
among them) and the gated RMSNorm are float32 (``STATE_DTYPE`` is
``models/mamba.py``'s: what is carried from token to token).

The recurrence's evaluations are ``ops/linear_attention.py``'s: a stretch of
tokens in blocks of 64 (``gated_delta_blocks``), and the engine's batched
decode step, whose batch IS the slot table: one token a row, the update
itself, by the named kernel ``linear_attention._gated_delta_step`` on the
states in place (``kernel``: the block's ``paged_kernel``).

**Decode mode** keeps the same two ``cache`` variables as ``models/mamba.py``
(``STATE_KEYS``), a row per engine slot, under the same rules (``state_slots``,
a row at ``seq_lens`` 0 starts from zeros, a batch as long as the slot table
is updated in place under the mask, ``valid_lens`` marks a padded piece's
own tokens): ``conv_state [slots, K-1, 2 H d_k + H d_v]`` and ``scan_state
[slots, H / p, d_k, p d_v]``: ``p`` heads side by side on the lanes
(``ops/linear_attention.lane_pack``; 2 at 30 heads of 96 x 192, where an
array ``[.., 96, 192]`` would be stored a third larger than it is).
:func:`head_states` gives ``[.., H, d_k, d_v]`` back. A padded token has
``alpha = 1`` and ``beta = 0``: it changes nothing.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.models import mamba
from distributed_pytorch_tpu.ops import linear_attention as la

F32 = jnp.float32
#: Inside the square root of the L2 norms of ``q`` and ``k``.
L2_EPS = 1e-6


def head_states(scan_state, heads: int):
    """``[.., H, d_k, d_v]`` of a ``scan_state`` leaf as the cache keeps it."""
    return la.unpack_state(scan_state, heads // scan_state.shape[-3])


def l2_normalised(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class GatedDeltaMixer(nn.Module):
    d_model: int
    n_heads: int
    d_k: int
    d_v: int
    d_conv: int = 4
    neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: Any = F32
    decode: bool = False
    kernel: str = ""  # the block's paged_kernel; "" = XLA operations

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        seq_lens: Optional[jnp.ndarray] = None,
        state_slots: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        batch, t, _ = x.shape
        heads, dk, dv, taps = self.n_heads, self.d_k, self.d_v, self.d_conv - 1
        conv_dim = heads * (2 * dk + dv)
        pack = la.lane_pack(heads, dv)
        packed = (heads // pack, dk, pack * dv)
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype, name=name
        )

        cached = self.decode and self.has_variable("cache", "scan_state")
        if self.decode and not cached:
            # Cache init pass: one state row per row of this call (the
            # engine inits with a [max_slots, 1] batch).
            self.variable(
                "cache", "conv_state", jnp.zeros,
                (batch, taps, conv_dim), self.dtype,
            )
            self.variable(
                "cache", "scan_state", jnp.zeros,
                (batch,) + packed, mamba.STATE_DTYPE,
            )
        in_place = False
        if cached:
            if state_slots is None or seq_lens is None:
                raise ValueError(
                    "a decode-mode gated-delta layer requires state_slots "
                    "and seq_lens every step (the serving engine passes them)"
                )
            conv_var = self.variable("cache", "conv_state", lambda: None)
            scan_var = self.variable("cache", "scan_state", lambda: None)
            tail = mamba.load_rows(conv_var.value, state_slots, seq_lens)
            # The batched decode step: the kernel takes the slot table's
            # states as they lie and applies the mask itself.
            in_place = t == 1 and batch == scan_var.value.shape[0]
            if not in_place:
                s0 = la.unpack_state(
                    mamba.load_rows(scan_var.value, state_slots, seq_lens),
                    pack,
                )
        else:
            tail = jnp.zeros((batch, taps, conv_dim), self.dtype)
            s0 = jnp.zeros((batch, heads, dk, dv), mamba.STATE_DTYPE)

        qkv = jnp.concatenate(
            [
                dense(heads * dk, "q_proj")(x), dense(heads * dk, "k_proj")(x),
                dense(heads * dv, "v_proj")(x),
            ],
            axis=-1,
        )
        conv_w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (self.d_conv, conv_dim), F32,
        )
        with jax.named_scope("gdn.conv"):
            # Taps in ``dtype`` (what the projections left, and the tail);
            # the K products and their sum in float32.
            padded = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
            new_tail = mamba.conv_tail(padded, taps, valid_lens)
            padded = padded.astype(F32)
            qkv32 = nn.silu(sum(
                conv_w[i] * padded[:, i : i + t] for i in range(self.d_conv)
            ))
        q, k, v = jnp.split(qkv32, [heads * dk, 2 * heads * dk], axis=-1)
        q = l2_normalised(q.reshape(batch, t, heads, dk)) * dk**-0.5
        k = l2_normalised(k.reshape(batch, t, heads, dk))
        v = v.reshape(batch, t, heads, dv)
        dt_bias = self.param(
            "dt_bias", nn.initializers.zeros_init(), (heads,), F32
        )
        a_log = self.param(
            "A_log", nn.initializers.zeros_init(), (heads,), F32
        )
        beta = jax.nn.sigmoid(dense(heads, "b_proj")(x).astype(F32))
        if self.neg_eigval:
            beta = beta * 2.0
        log_alpha = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
            dense(heads, "a_proj")(x).astype(F32) + dt_bias.astype(F32)
        )
        if valid_lens is not None:
            # The padding of a prefill piece: tokens that change nothing.
            own = mamba.token_mask(valid_lens, t)[..., None]
            beta = jnp.where(own, beta, 0.0)
            log_alpha = jnp.where(own, log_alpha, 0.0)
        with jax.named_scope("gdn.state"):
            if in_place:
                o, s_new = la.gated_delta_step(
                    q[:, 0], k[:, 0], v[:, 0], jnp.exp(log_alpha[:, 0]),
                    beta[:, 0], scan_var.value,
                    la.row_codes(state_slots, seq_lens), pack=pack,
                    kernel=self.kernel,
                )
                o = o[:, None]
                scan_var.value = s_new
            else:
                o, s = la.gated_delta_blocks(q, k, v, log_alpha, beta, s0)
            if cached:
                conv_var.value = mamba.store_rows(
                    conv_var.value, new_tail, state_slots
                )
                if not in_place:
                    scan_var.value = mamba.store_rows(
                        scan_var.value, la.pack_state(s, pack), state_slots
                    )
        normed = nn.RMSNorm(epsilon=self.norm_eps, dtype=F32, name="norm")(o)
        gate = dense(heads * dv, "g_proj")(x).astype(F32)
        gated = normed.reshape(batch, t, heads * dv) * nn.silu(gate)
        return dense(self.d_model, "o_proj")(gated.astype(self.dtype))

"""The Mamba-2 (SSD, state-space duality) mixer: a state ``[H, P, N]`` a
sequence with ONE decay a head, evaluated a block at a time.

Over a sequence ``u [B, T, d]`` (``H`` heads of ``P`` channels, ``d_inner =
H P``; ``N = d_state``; ``G`` groups sharing ``B`` and ``C``; ``K = d_conv``)::

    [z, xBC, dt] = W_in u                        d -> d_inner + (d_inner + 2GN) + H
    xBC_t  = silu(b_c + sum_{k<K} w_c[k] xBC_{t-K+1+k})   depthwise causal conv
    [x, B, C] = xBC                              d_inner, GN, GN
    dt_t   = softplus(dt_t + dt_bias)            a head
    A      = -exp(A_log)                         ONE SCALAR a head
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t     h [H, P, N]
    y_t    = h_t C_t + D x_t                     D a head
    out    = W_out rmsnorm_w(y_t * silu(z_t))    over all d_inner; d_inner -> d

Projections run in the module's ``dtype`` and the conv reads its taps (and
keeps its tail) in it; ``dt``, ``A``, ``D``, the decay, the state, every sum
of the evaluation below and the gated RMSNorm are float32 (``STATE_DTYPE`` is
``models/mamba.py``'s: what is carried from token to token).

**The evaluation is blocked** (``ssd_blocked``). Carrying ``h`` token by
token moves ``H P N`` floats a token (4 MB at 128 x 64 x 128: 47 ms for a
512-token chunk of nine layers on a v5e), so a stretch of ``T`` tokens is cut
into blocks of ``L``; with ``a_t = dt_t A`` and ``s_i = a_1 + .. + a_i``
inside a block::

    y_i  = sum_{j<=i} exp(s_i - s_j) (C_i . B_j) dt_j x_j      the quadratic form
           + exp(s_i) C_i . h_in                               the carried state
    h_out = exp(s_L) h_in + sum_j exp(s_L - s_j) dt_j x_j (outer) B_j

The first sum is ``(Lmat o (C B^T)) X``, three matrix products a block; the
state is read and written once a BLOCK. It is the recurrence regrouped, equal
to it to float32 rounding for every ``L``; a ``T`` that ``L`` does not divide
is padded with ``dt = 0`` tokens, which change neither ``h`` nor any ``y``
that is kept. One token (the decode step) is the recurrence itself.

**Decode mode** keeps the same two ``cache`` variables as ``models/mamba.py``
(``STATE_KEYS``), a row per engine slot: ``conv_state [slots, K-1, d_inner +
2GN]`` and ``scan_state [slots, H, P, N]`` (``N`` on the lanes), with the
same rules: ``state_slots [B]`` says whose state a row carries (-1: none),
a row whose ``seq_lens`` is 0 starts from zeros, a batch as long as the slot
table IS the slot table and is updated in place under the mask, any other
batch is gathered and scattered; under ``valid_lens [B]`` (a prefill piece
padded to its program's width) the padding is ``dt = 0`` tokens and the conv's
tail ends at the valid length (``models/mamba.py``, "A padded piece").
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_pytorch_tpu.models import mamba

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: Tokens a block of the mixer's evaluation (no value depends on it). The
#: only length run on the chip; others are unmeasured there (PERF.md 7).
BLOCK = 64


def ssd_recurrence(x, dt, a, b, c, h0):
    """The definition, a token at a time (``lax.scan`` over ``T``): what
    ``ssd_blocked`` is tested against, and the decode step.

    ``x [B, T, H, P]``, ``dt [B, T, H]``, ``a [H]``, ``b, c [B, T, G, N]``
    (all float32), ``h0 [B, H, P, N]`` in whose type the state is carried.
    Returns ``(y [B, T, H, P]`` without the ``D x`` term, ``h_T)``."""
    batch, _, heads, _ = x.shape
    groups = b.shape[2]

    def per_head(v):  # [B, G, N] -> [B, H, N]
        return jnp.repeat(v, heads // groups, axis=1)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        decay = jnp.exp(dt_t * a)[:, :, None, None]
        fed = (dt_t[:, :, None] * x_t)[..., None] * per_head(b_t)[:, :, None, :]
        h = decay * h.astype(F32) + fed
        y = jnp.sum(h * per_head(c_t)[:, :, None, :], axis=-1)
        return h.astype(h0.dtype), y

    if x.shape[1] == 1:  # the decode step: no loop
        h, y = step(h0, (x[:, 0], dt[:, 0], b[:, 0], c[:, 0]))
        return y[:, None], h
    time_major = tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c))
    h, ys = jax.lax.scan(step, h0, time_major)
    return jnp.swapaxes(ys, 0, 1), h


def ssd_blocked(x, dt, a, b, c, h0, block: int):
    """``ssd_recurrence``'s results by blocks of ``block`` tokens (module
    docstring). The state crosses a block border in ``h0``'s type."""
    batch, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    length = min(block, t)
    pad = -t % length
    if pad:
        widen = lambda v: jnp.pad(  # noqa: E731
            v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nb = (t + pad) // length
    rep = heads // groups
    # [B, nb, L, ...], heads split into (group, head of the group).
    x = x.reshape(batch, nb, length, groups, rep, p)
    dt = dt.reshape(batch, nb, length, groups, rep)
    b = b.reshape(batch, nb, length, groups, n)
    c = c.reshape(batch, nb, length, groups, n)
    s = jnp.cumsum(dt * a.reshape(groups, rep), axis=2)  # [B, nb, L, G, R]
    xdt = x * dt[..., None]

    # Inside a block: (Lmat o (C B^T)) X.
    i = jnp.arange(length)
    gap = s[:, :, :, None] - s[:, :, None, :]  # s_i - s_j: [B, nb, Li, Lj, G, R]
    causal = (i[:, None] >= i[None, :])[None, None, :, :, None, None]
    lmat = jnp.exp(jnp.where(causal, gap, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", c, b, precision=HIGHEST)
    y = jnp.einsum(
        "bcijgr,bcjgrp->bcigrp", lmat * cb[..., None], xdt, precision=HIGHEST)

    # What a block adds to the state, and what it leaves of the one it met.
    to_end = jnp.exp(s[:, :, -1:] - s)  # [B, nb, L, G, R]
    added = jnp.einsum(
        "bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, xdt, b, precision=HIGHEST)
    kept = jnp.exp(s[:, :, -1])  # [B, nb, G, R]

    def border(h, xs):
        kept_c, added_c = xs
        h_next = kept_c[..., None, None] * h.astype(F32) + added_c
        return h_next.astype(h0.dtype), h

    h_in = h0.reshape(batch, groups, rep, p, n)
    h_out, met = jax.lax.scan(
        border, h_in, (jnp.swapaxes(kept, 0, 1), jnp.swapaxes(added, 0, 1)))
    met = jnp.swapaxes(met, 0, 1).astype(F32)  # [B, nb, G, R, P, N]
    y = y + jnp.einsum(
        "bcign,bcgrpn,bcigr->bcigrp", c, met, jnp.exp(s), precision=HIGHEST)
    y = y.reshape(batch, nb * length, heads, p)[:, :t]
    return y, h_out.reshape(batch, heads, p, n)


class Mamba2Mixer(nn.Module):
    d_model: int
    n_heads: int
    d_head: int
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    norm_eps: float = 1e-5
    dtype: Any = F32
    decode: bool = False

    @nn.compact
    def __call__(
        self,
        u: jnp.ndarray,
        *,
        seq_lens: Optional[jnp.ndarray] = None,
        state_slots: Optional[jnp.ndarray] = None,
        valid_lens: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        batch, t, _ = u.shape
        heads, p, n, g, k = (
            self.n_heads, self.d_head, self.d_state, self.n_groups, self.d_conv)
        d_inner = heads * p
        conv_dim = d_inner + 2 * g * n
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype, name=name
        )

        cached = self.decode and self.has_variable("cache", "scan_state")
        if self.decode and not cached:
            # Cache init pass: one state row per row of this call (the
            # engine inits with a [max_slots, 1] batch).
            self.variable(
                "cache", "conv_state", jnp.zeros,
                (batch, k - 1, conv_dim), self.dtype,
            )
            self.variable(
                "cache", "scan_state", jnp.zeros,
                (batch, heads, p, n), mamba.STATE_DTYPE,
            )
        if cached:
            if state_slots is None or seq_lens is None:
                raise ValueError(
                    "a decode-mode Mamba-2 layer requires state_slots and "
                    "seq_lens every step (the serving engine passes them)"
                )
            conv_var = self.variable("cache", "conv_state", lambda: None)
            scan_var = self.variable("cache", "scan_state", lambda: None)
            tail = mamba.load_rows(conv_var.value, state_slots, seq_lens)
            h0 = mamba.load_rows(scan_var.value, state_slots, seq_lens)
        else:
            tail = jnp.zeros((batch, k - 1, conv_dim), self.dtype)
            h0 = jnp.zeros((batch, heads, p, n), mamba.STATE_DTYPE)

        z, xbc, dt = jnp.split(
            dense(d_inner + conv_dim + heads, "in_proj")(u),
            [d_inner, d_inner + conv_dim], axis=-1,
        )
        conv_w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (k, conv_dim), F32
        )
        conv_b = self.param(
            "conv_bias", nn.initializers.zeros_init(), (conv_dim,), F32
        )
        with jax.named_scope("ssd.conv"):
            # Taps in ``dtype`` (what the projection left, and the tail);
            # the K products and their sum in float32.
            padded = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
            new_tail = mamba.conv_tail(padded, k - 1, valid_lens)
            padded = padded.astype(F32)
            xbc32 = nn.silu(conv_b + sum(
                conv_w[i] * padded[:, i : i + t] for i in range(k)
            ))
        x, b, c = jnp.split(xbc32, [d_inner, d_inner + g * n], axis=-1)
        x = x.reshape(batch, t, heads, p)
        b, c = b.reshape(batch, t, g, n), c.reshape(batch, t, g, n)
        dt_bias = self.param(
            "dt_bias", nn.initializers.zeros_init(), (heads,), F32
        )
        a_log = self.param(
            "A_log",
            lambda _k, shape: jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32)),
            (heads,),
        )
        d_skip = self.param("D", nn.initializers.ones_init(), (heads,), F32)
        delta = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
        if valid_lens is not None:
            # The padding of a prefill piece: ``dt = 0`` tokens, like the
            # ones ``ssd_blocked`` pads its last block with.
            delta = jnp.where(
                mamba.token_mask(valid_lens, t)[..., None], delta, 0.0)
        a = -jnp.exp(a_log.astype(F32))
        with jax.named_scope("ssd.block"):
            if t == 1:
                y, h = ssd_recurrence(x, delta, a, b, c, h0)
            else:
                y, h = ssd_blocked(x, delta, a, b, c, h0, BLOCK)
            y = y + d_skip.astype(F32)[:, None] * x
            if cached:
                conv_var.value = mamba.store_rows(
                    conv_var.value, new_tail, state_slots
                )
                scan_var.value = mamba.store_rows(scan_var.value, h, state_slots)
        gated = y.reshape(batch, t, d_inner) * nn.silu(z.astype(F32))
        normed = nn.RMSNorm(
            epsilon=self.norm_eps, dtype=F32, name="norm"
        )(gated)
        return dense(self.d_model, "out_proj")(normed.astype(self.dtype))

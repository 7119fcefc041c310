"""The Mamba (S6, selective state-space) mixer, in the variant the ``jamba``
family runs: RMSNorm on ``dt``, ``B`` and ``C`` between the two projections.

Over a sequence ``x [B, T, d]`` (``d_inner = expand * d``, ``N = d_state``,
``R = dt_rank``, ``K = d_conv``)::

    [u, z]     = W_in x                                   d -> 2 d_inner
    u_t        = silu(b_c + sum_{k<K} w_c[k] u_{t-K+1+k}) depthwise causal conv
    [dt, B, C] = W_x u_t                                  d_inner -> R + 2N
    dt, B, C   = rmsnorm(dt), rmsnorm(B), rmsnorm(C)
    delta_t    = softplus(W_dt dt + b_dt)                 R -> d_inner
    A          = -exp(A_log)                              [d_inner, N]
    h_t        = exp(delta_t A) * h_{t-1} + (delta_t B_t) u_t
    y_t        = h_t C_t + D u_t
    out        = W_out (y_t * silu(z_t))                  d_inner -> d

Projections run in the module's ``dtype``, and the conv reads its taps (and
keeps its tail) in it; ``delta``, ``A``, ``D``, the conv's sums, the
recurrence and the state are float32 (``STATE_DTYPE``: what is carried from
token to token; anything below float32 there is a different model).

**Decode mode** (``decode=True``) keeps two ``cache`` variables, one row per
engine slot: ``conv_state [slots, K-1, d_inner]`` (the conv's tail, in
``dtype``) and ``scan_state [slots, N, d_inner]`` (``h`` transposed so that
``d_inner`` lies on the lanes). A call is told, per batch row, whose state it
carries: ``state_slots [B]`` (-1: none; the row computes on zeros and nothing
is written) and ``seq_lens [B]``, the row's token count before the call:
a row at position 0 starts from zeros whatever its slot held. When the batch
IS the slot table (``B == slots``: the engine's batched decode step, row ``r``
is slot ``r``) the states are updated in place under ``state_slots >= 0``,
with no gather; a row outside the mask keeps both states bit for bit.
Otherwise (a ``[1, width]`` prefill piece) the rows are gathered and scattered.

**A padded piece.** ``valid_lens [B]`` (optional; the engine's prefill
programs pass it) says how many of a row's ``T`` tokens are its own; the rest
is padding up to the program's width. ``delta`` is zeroed there, so ``exp(0 *
A) = 1`` and ``0 * u * B = 0``: ``h`` comes out as the last real token left it.
The conv's tail kept is the ``K - 1`` inputs that END at the valid length, not
at ``T``. A padded token's output is garbage nobody reads.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

F32 = jnp.float32
#: The type the scan state is kept and carried in. Not an option: the tests
#: and the benchmark's control that show what catches a lower one patch it.
STATE_DTYPE = F32

#: Names of the per-slot ``cache`` variables (everything else in a decode
#: model's ``cache`` collection is a KV page pool).
STATE_KEYS = ("conv_state", "scan_state")


#: Tokens per iteration of the prefill scan's loop. On the v5e at d_inner
#: 5120, N 16 and 512 tokens the loop takes 1.76 us a token unrolled by 1 and
#: 0.82-0.87 by 2 to 32 (PERF.md §6, PR 26); the results are bit-identical.
SCAN_UNROLL = 8


def selective_scan(u, delta, a_t, b, c, h0):
    """The recurrence, one token at a time (``lax.scan`` over ``T``).

    ``u, delta [B, T, d_inner]``, ``b, c [B, T, N]`` (all float32),
    ``a_t [N, d_inner]`` (``A`` transposed), ``h0 [B, N, d_inner]``, in
    whose type the state is carried. Returns ``(y [B, T, d_inner]`` without
    the ``D u`` term, ``h_T)``."""

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        decay = jnp.exp(d_t[:, None, :] * a_t[None])
        h = decay * h.astype(F32) + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(h * c_t[:, :, None], axis=1)
        return h.astype(h0.dtype), y

    if u.shape[1] == 1:  # the decode step: no loop
        h, y = step(h0, (u[:, 0], delta[:, 0], b[:, 0], c[:, 0]))
        return y[:, None], h
    time_major = tuple(jnp.swapaxes(v, 0, 1) for v in (u, delta, b, c))
    h, ys = jax.lax.scan(step, h0, time_major, unroll=SCAN_UNROLL)
    return jnp.swapaxes(ys, 0, 1), h


class _DtProjection(nn.Module):
    """``R -> d_inner`` with a bias: the matmul in ``dtype``, accumulated
    and biased in float32 (``delta`` is a float32 quantity)."""

    features: int
    dtype: Any = F32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), F32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), F32
        )
        y = jnp.dot(
            x.astype(self.dtype), kernel.astype(self.dtype),
            preferred_element_type=F32,
        )
        return y + bias.astype(F32)


class MambaMixer(nn.Module):
    d_model: int
    dt_rank: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    norm_eps: float = 1e-6
    dtype: Any = F32
    decode: bool = False

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
        d_inner = self.expand * self.d_model
        n, k = self.d_state, self.d_conv
        rank = self.dt_rank
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype, name=name
        )
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=F32, name=name
        )

        cached = self.decode and self.has_variable("cache", "scan_state")
        if self.decode and not cached:
            # Cache init pass: one state row per row of this call (the
            # engine inits with a [max_slots, 1] batch), then the plain
            # forward from zeros.
            self.variable(
                "cache", "conv_state", jnp.zeros,
                (batch, k - 1, d_inner), self.dtype,
            )
            self.variable(
                "cache", "scan_state", jnp.zeros,
                (batch, n, d_inner), STATE_DTYPE,
            )
        if cached:
            if state_slots is None or seq_lens is None:
                raise ValueError(
                    "a decode-mode Mamba layer requires state_slots and "
                    "seq_lens every step (the serving engine passes them)"
                )
            conv_var = self.variable("cache", "conv_state", lambda: None)
            scan_var = self.variable("cache", "scan_state", lambda: None)
            tail = load_rows(conv_var.value, state_slots, seq_lens)
            h0 = load_rows(scan_var.value, state_slots, seq_lens)
        else:
            tail = jnp.zeros((batch, k - 1, d_inner), self.dtype)
            h0 = jnp.zeros((batch, n, d_inner), STATE_DTYPE)

        u, z = jnp.split(dense(2 * d_inner, "in_proj")(x), 2, axis=-1)
        conv_w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (k, d_inner), F32
        )
        conv_b = self.param(
            "conv_bias", nn.initializers.zeros_init(), (d_inner,), F32
        )
        with jax.named_scope("ssm.conv"):
            # The taps are what the projection left in ``dtype``; the four
            # products and their sum are float32 (the VPU's own width).
            padded = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            new_tail = conv_tail(padded, k - 1, valid_lens)
            padded = padded.astype(F32)
            u32 = nn.silu(conv_b + sum(
                conv_w[i] * padded[:, i : i + t] for i in range(k)
            ))

        dt, b, c = jnp.split(
            dense(rank + 2 * n, "x_proj")(u32.astype(self.dtype)),
            [rank, rank + n], axis=-1,
        )
        dt, b, c = norm("dt_norm")(dt), norm("b_norm")(b), norm("c_norm")(c)
        delta = jax.nn.softplus(
            _DtProjection(d_inner, self.dtype, name="dt_proj")(dt)
        )
        if valid_lens is not None:
            delta = jnp.where(token_mask(valid_lens, t)[..., None], delta, 0.0)
        a_log = self.param(
            "A_log",
            lambda _k, shape: jnp.log(
                jnp.broadcast_to(jnp.arange(1, n + 1, dtype=F32), shape)
            ),
            (d_inner, n),
        )
        d_skip = self.param("D", nn.initializers.ones_init(), (d_inner,), F32)
        with jax.named_scope("ssm.scan"):
            y, h = selective_scan(
                u32, delta, -jnp.exp(a_log.astype(F32)).T, b.astype(F32),
                c.astype(F32), h0,
            )
            y = y + d_skip.astype(F32) * u32
            if cached:
                conv_var.value = store_rows(
                    conv_var.value, new_tail, state_slots
                )
                scan_var.value = store_rows(scan_var.value, h, state_slots)
        gated = (y * nn.silu(z.astype(F32))).astype(self.dtype)
        return dense(self.d_model, "out_proj")(gated)


def token_mask(valid_lens, t: int):
    """``[B, t]`` bool: which of a row's ``t`` tokens are its own, the first
    ``valid_lens [B]`` of them (the rest pads a prefill piece to its
    program's width)."""
    return jnp.arange(t, dtype=jnp.int32)[None, :] < valid_lens[:, None]


def conv_tail(padded, taps: int, valid_lens=None):
    """The ``taps`` conv inputs a row's next call starts from, out of
    ``padded [B, taps + T, C]`` (the old tail, then this call's inputs): the
    last ``taps``, or under ``valid_lens [B]`` the ``taps`` that end at the
    row's valid length."""
    if valid_lens is None:
        return padded[:, padded.shape[1] - taps :]
    at = valid_lens[:, None] + jnp.arange(taps, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(padded, at[:, :, None], axis=1)


def _a_row(mask, like):
    """``mask [rows]`` against a state of any rank (``models/mamba2.py``'s has
    one axis more)."""
    return mask[(slice(None),) + (None,) * (like.ndim - 1)]


def load_rows(state, state_slots, seq_lens):
    """Each batch row's state: its slot's, or zeros at position 0 and for a
    row that carries none. A batch as long as the slot table IS the slot
    table (module docstring)."""
    slots = state.shape[0]
    rows = state if state_slots.shape[0] == slots else state[
        jnp.clip(state_slots, 0, slots - 1)
    ]
    keep = (seq_lens > 0) & (state_slots >= 0)
    return jnp.where(_a_row(keep, rows), rows, jnp.zeros_like(rows))


def store_rows(state, new, state_slots):
    """Write the rows that carry a slot; every other row of ``state`` is
    returned as it was."""
    slots = state.shape[0]
    new = new.astype(state.dtype)
    live = state_slots >= 0
    if state_slots.shape[0] == slots:
        return jnp.where(_a_row(live, state), new, state)
    return state.at[jnp.where(live, state_slots, slots)].set(new, mode="drop")

"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464): a linear-attention layer whose state is a MATRIX a head,
``S [d_k, d_v]`` float32, read before it is written::

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(``alpha`` in (0, 1) a head and token, ``beta`` in (0, 2), ``|k| = 1``.) With
``r = S_{t-1}^T k_t`` and ``u = beta (v - alpha r)`` that is ``S_t = alpha
S_{t-1} + k u^T`` and ``o_t = alpha S_{t-1}^T q + (k . q) u``: ONE read of the
state gives ``r``, ``S^T q`` and the new state. Two evaluations, which agree
with the recurrence as written to float32 rounding
(``tests/test_gated_delta.py``):

* :func:`gated_delta_blocks`: a stretch of ``T`` tokens in blocks of ``L``
  (the WY / UT-transform form of arXiv:2406.06484 with the decay folded in).
  With ``g_i = log alpha_1 + .. + log alpha_i`` inside a block and ``u_t`` as
  above, ``(I + A) U = beta o (V - exp(g) o (K S_0))`` where ``A_tj = beta_t
  exp(g_t - g_j) (k_t . k_j)`` for ``j < t``: one unit-lower-triangular system
  ``L x L`` a block and head, with ``d_v + d_k`` right-hand sides (``beta V``
  and ``beta exp(g) K``), worked out for all blocks at once (its inverse by
  halves, :func:`unit_lower_inverse`, then one product); then a scan over
  the blocks, each three products with the state it met: ``U = W_v - W_k
  S_0``, ``O = (exp(g) Q) S_0 + (M o Q K^T) U``, ``S_L = exp(g_L) S_0 +
  (exp(g_L - g) K)^T U``. The state is read and written once a BLOCK. Plain
  XLA operations (float32, ``HIGHEST``): the benchmark's reader knows them by
  their shapes (``benchmarks/harness/linear.py``).
* :func:`gated_delta_step`: ONE token for every row of the engine's slot
  table, the state updated in place. On a TPU a Pallas kernel NAMED
  ``linear_attention._gated_delta_step`` (a device trace shows it under that
  name): grid over the slots, a slot's whole state (``heads x d_k x d_v``
  float32, 2.2 MB at 30 x 96 x 192) one pipelined block in VMEM, read once and
  written once where XLA's fusions walk it three times (``S^T k``, the
  update, ``S^T q``). A row out of the dispatch group keeps its state bit for
  bit, a row at position 0 starts from zeros: both ride as one scalar-prefetch
  code a row. Elsewhere (and ``kernel="xla"``) the same arithmetic as XLA
  operations; ``"interpret"`` runs the kernel through the interpreter.

**The state's layout.** A float32 array whose last size is 192 is stored in
tiles of 128 lanes: ``[slots, 30, 96, 192]`` takes a third more HBM (and
traffic) than its numbers, 283 MB where 212 MB are held (compiled for a
described v5e). So the state is kept with :func:`lane_pack` heads side by
side on the lanes, ``[slots, heads / p, d_k, p d_v]`` (``p = 2``: 384 lanes,
dense), which :func:`pack_state` / :func:`unpack_state` convert. The kernel
computes on that layout as it lies: a head's ``k`` is a column broadcast over
its half of the lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128

STEP_KERNEL = "linear_attention._gated_delta_step"
#: Tokens a block of the blocked form (no value depends on it; the engine's
#: prefill pieces are whole multiples of it).
BLOCK = 64


def lane_pack(heads: int, d_v: int) -> int:
    """How many heads lie side by side on the lanes of the stored state: the
    fewest that fill whole tiles of 128 lanes, 1 where none does."""
    for p in (1, 2, 4, 8):
        if heads % p == 0 and (p * d_v) % LANES == 0:
            return p
    return 1


def pack_state(s, pack: int):
    """``[.., H, d_k, d_v] -> [.., H / pack, d_k, pack d_v]``."""
    if pack == 1:
        return s
    *lead, h, dk, dv = s.shape
    s = s.reshape(*lead, h // pack, pack, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, dk, pack * dv)


def unpack_state(s, pack: int):
    """:func:`pack_state`'s inverse."""
    if pack == 1:
        return s
    *lead, hp, dk, w = s.shape
    s = s.reshape(*lead, hp, dk, pack, w // pack)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, hp * pack, dk, w // pack)


def state_bytes_moved(rows: int, heads: int, d_k: int, d_v: int,
                      itemsize: int = 4) -> int:
    """Bytes of state the one-token update moves for ``rows`` (row, layer)
    pairs: each state once in and once out."""
    return 2 * rows * heads * d_k * d_v * itemsize


def _one_token(s, q, k, v, alpha, beta):
    """The update of one token on ``s [.., d_k, d_v]`` (float32): returns
    ``(o [.., d_v], the new state)``. One read of ``s``."""
    r = jnp.sum(s * k[..., :, None], axis=-2)
    p = jnp.sum(s * q[..., :, None], axis=-2)
    a = alpha[..., None]
    u = beta[..., None] * (v - a * r)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)
    o = a * p + kq * u
    return o, a[..., None] * s + k[..., :, None] * u[..., None, :]


def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a [.., n, n]``, by
    halves: the inverse of ``[[L11, 0], [L21, L22]]`` is ``[[L11^-1, 0],
    [-L22^-1 L21 L11^-1, L22^-1]]``, from the 1 x 1 diagonal blocks (ones) up,
    every level two small matrix products for all blocks, heads and diagonal
    blocks at once. (XLA's triangular solve takes 1.05 ms for a 448-token
    piece's 7 x 30 systems of 64 x 64 on the v5e, a row at a time: 12.6 ms a
    prefill program over 12 layers; PERF.md section 6, PR 42.) A size that
    is no power of two is padded with zeros, which invert to themselves."""
    n = a.shape[-1]
    size = 1 << max(n - 1, 0).bit_length()
    if size != n:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, size - n)] * 2)
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (size, 1, 1), F32)  # the diagonal blocks' inverses
    s = 1
    while s < size:
        g = size // (2 * s)
        # The lower-left block of every diagonal block of 2s: [.., g, s, s].
        l21 = jnp.einsum(
            "...iaib->...iab",
            a.reshape(lead + (g, 2, s, g, 2, s))[..., :, 1, :, :, 0, :],
        )
        pairs = inv.reshape(lead + (g, 2, s, s))
        inv11, inv22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        new21 = -jnp.einsum(
            "...ab,...bc,...cd->...ad", inv22, l21, inv11, precision=HIGHEST)
        top = jnp.concatenate([inv11, jnp.zeros_like(inv11)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([new21, inv22], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :n, :n]


def gated_delta_blocks(q, k, v, log_alpha, beta, s0, block: int = BLOCK):
    """The recurrence over ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``,
    ``log_alpha, beta [B, T, H]`` (float32; ``log_alpha <= 0``) from ``s0 [B,
    H, d_k, d_v]``, by blocks of ``block`` tokens (module docstring): returns
    ``(o [B, T, H, d_v], s_T)``. A token with ``log_alpha = 0`` and ``beta = 0`` changes nothing
    (padding). The state crosses a block border in ``s0``'s type."""
    batch, t, heads, dk = q.shape
    dv = v.shape[-1]
    length = min(block, t)
    pad = -t % length
    if pad:
        widen = lambda x: jnp.pad(  # noqa: E731
            x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        q, k, v, log_alpha, beta = (
            widen(x) for x in (q, k, v, log_alpha, beta))
    nb = (t + pad) // length

    def by_block(x):  # [B, T, H, ..] -> [nb, B, H, L, ..]
        x = x.reshape(batch, nb, length, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = by_block(q), by_block(k), by_block(v)
    g = jnp.cumsum(by_block(log_alpha), axis=-1)  # [nb, B, H, L]
    beta = by_block(beta)
    i = jnp.arange(length)
    lower = i[:, None] >= i[None, :]
    # exp(g_t - g_j) for j <= t; 0 above the diagonal (never exp of a
    # positive number: g falls).
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))
    kk = jnp.einsum("nbhtd,nbhjd->nbhtj", k, k, precision=HIGHEST)
    a = jnp.where(i[:, None] > i[None, :], beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
    solved = jnp.einsum(
        "nbhtj,nbhjr->nbhtr", unit_lower_inverse(a), rhs, precision=HIGHEST)
    w_v, w_k = solved[..., :dv], solved[..., dv:]
    attn = decay * jnp.einsum("nbhtd,nbhjd->nbhtj", q, k, precision=HIGHEST)
    q_in = jnp.exp(g)[..., None] * q
    k_out = jnp.exp(g[..., -1:] - g)[..., None] * k
    kept = jnp.exp(g[..., -1])  # [nb, B, H]

    def one_block(s, xs):
        w_v_c, w_k_c, attn_c, q_c, k_c, kept_c = xs
        s32 = s.astype(F32)
        u = w_v_c - jnp.einsum("bhtd,bhdv->bhtv", w_k_c, s32, precision=HIGHEST)
        o = jnp.einsum("bhtd,bhdv->bhtv", q_c, s32, precision=HIGHEST) + (
            jnp.einsum("bhtj,bhjv->bhtv", attn_c, u, precision=HIGHEST))
        s_new = kept_c[..., None, None] * s32 + jnp.einsum(
            "bhtd,bhtv->bhdv", k_c, u, precision=HIGHEST)
        return s_new.astype(s0.dtype), o

    s, o = jax.lax.scan(one_block, s0, (w_v, w_k, attn, q_in, k_out, kept))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, nb, L, H, d_v]
    return o.reshape(batch, nb * length, heads, dv)[:, :t], s


# ------------------------------------------------------------ the decode step
#
# Row codes (scalar prefetch): what the kernel does with a row's state.
ROW_ABSENT, ROW_FRESH, ROW_CARRIES = -1, 0, 1


def row_codes(state_slots, seq_lens):
    """``[B] int32``: a row out of the dispatch group (``state_slots < 0``),
    one that starts from zeros (position 0), one that carries its state."""
    return jnp.where(
        state_slots < 0, ROW_ABSENT,
        jnp.where(seq_lens > 0, ROW_CARRIES, ROW_FRESH)).astype(jnp.int32)


def _step_kernel(code_ref, qc_ref, kc_ref, v_ref, a_ref, b_ref, kq_ref, s_ref,
                 o_ref, s_out_ref, *, pack, d_v):
    """One slot (grid step): every head's state read once, written once.
    ``qc_ref, kc_ref [1, d_k, H..]`` hold a head's ``q`` and ``k`` as COLUMNS
    (d_k on the sublanes); ``v, a, b, kq [1, H / pack, pack d_v]`` are rows in
    the state's own lane order (``alpha``, ``beta`` and ``k . q`` repeated
    over their head's lanes)."""
    code = code_ref[pl.program_id(0)]
    groups = s_ref.shape[1]
    width = s_ref.shape[3]

    @pl.when(code == ROW_ABSENT)
    def _absent():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(code != ROW_ABSENT)
    def _row():
        carries = code == ROW_CARRIES
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        qc, kc = qc_ref[0], kc_ref[0]  # [d_k, lanes >= H]
        for grp in range(groups):
            def columns(c):
                col = c[:, grp * pack : grp * pack + 1]
                for j in range(1, pack):
                    h = grp * pack + j
                    col = jnp.where(lane < j * d_v, col, c[:, h : h + 1])
                return col

            k = columns(kc)  # [d_k, 1] or [d_k, width]
            q = columns(qc)
            s = s_ref[0, grp].astype(F32)
            s = jnp.where(carries, s, jnp.zeros_like(s))
            r = jnp.sum(s * k, axis=0, keepdims=True)  # [1, width]
            p = jnp.sum(s * q, axis=0, keepdims=True)
            a = a_ref[0, grp : grp + 1]
            u = b_ref[0, grp : grp + 1] * (v_ref[0, grp : grp + 1] - a * r)
            o_ref[0, grp : grp + 1] = a * p + kq_ref[0, grp : grp + 1] * u
            s_out_ref[0, grp] = (a * s + k * u).astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def _step_call(codes, q, k, v, alpha, beta, state, *, pack, interpret):
    """Build and invoke the kernel for ``q, k [B, H, d_k]``, ``v [B, H,
    d_v]``, ``alpha, beta [B, H]`` (float32) and the packed ``state [B, H /
    pack, d_k, pack d_v]``, which is donated to the result. Jitted so that a
    model's layers share one trace."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    groups, width = h // pack, pack * dv

    def rows(x):  # [B, H] -> [B, groups, width]: a head's lanes hold its x
        return jnp.repeat(x, dv, axis=-1).reshape(b, groups, width)

    cols = lambda x: jnp.pad(  # noqa: E731  [B, d_k, H up to whole lanes]
        jnp.swapaxes(x, 1, 2), ((0, 0), (0, 0), (0, -h % LANES)))
    kq = jnp.sum(q * k, axis=-1)
    operands = (
        cols(q), cols(k), v.reshape(b, groups, width), rows(alpha),
        rows(beta), rows(kq), state,
    )

    def spec(shape):
        return pl.BlockSpec(
            (1,) + shape[1:], lambda i, codes: (i,) + (0,) * (len(shape) - 1))

    out_shapes = (
        jax.ShapeDtypeStruct((b, groups, width), F32),
        jax.ShapeDtypeStruct(state.shape, state.dtype),
    )
    block_bytes = state[0].size * (4 + state.dtype.itemsize)
    o, s_new = pl.pallas_call(
        functools.partial(_step_kernel, pack=pack, d_v=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[spec(x.shape) for x in operands],
            out_specs=[spec(s.shape) for s in out_shapes],
        ),
        out_shape=out_shapes,
        input_output_aliases={len(operands): 1},  # after the prefetch operand
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # A slot's state in and out, each double-buffered, and the
            # float32 temporaries of a head pair.
            vmem_limit_bytes=max(32 << 20, 4 * block_bytes + (8 << 20)),
        ),
        interpret=interpret,
        name=STEP_KERNEL,
    )(codes, *operands)
    return o.reshape(b, h, dv), s_new


def gated_delta_step(q, k, v, alpha, beta, state, codes, *, pack: int,
                     kernel="auto"):
    """One token a row on the slot table's packed ``state [B, H / pack, d_k,
    pack d_v]`` under ``codes`` (:func:`row_codes`): returns ``(o [B, H,
    d_v]`` float32, the new state``)``. ``kernel`` is
    ``ops/paged_attention.resolve_kernel``'s."""
    from distributed_pytorch_tpu.ops.paged_attention import resolve_kernel

    mode = resolve_kernel(kernel) if kernel else "xla"
    if mode != "xla":
        return _step_call(
            codes, q, k, v, alpha, beta, state, pack=pack,
            interpret=(mode == "interpret"))
    held = unpack_state(state, pack)
    s = jnp.where(
        (codes == ROW_CARRIES)[:, None, None, None], held.astype(F32), 0.0)
    o, s_new = _one_token(s, q, k, v, alpha, beta)
    live = (codes != ROW_ABSENT)[:, None, None, None]
    s_new = jnp.where(live, s_new.astype(state.dtype), held)
    return o, pack_state(s_new, pack)

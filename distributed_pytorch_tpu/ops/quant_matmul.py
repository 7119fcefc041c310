"""Pallas TPU kernel: activation @ int8-weight matmul with in-VMEM dequant.

The guaranteed-fused counterpart to ``dequantize() + @``: the weight tile is
read from HBM as **int8**, converted and scaled in VMEM registers, and fed
straight to the MXU — the bf16/f32 weight tensor never exists in HBM. This
is the fallback for the case where XLA chooses to materialize the dequant
instead of fusing it into the dot (observed on the CPU backend; on the TPU
neither has been measured: ROADMAP D14).
Decode-shaped: small-batch x [B, K] against q [K, N].

Grid: ``(N/block_n, K/block_k)`` — K is TILED, not held whole in VMEM.
TPU grid execution is sequential with the last dimension fastest, so each
output block accumulates over its K tiles in place and applies the
per-channel scales once on the final tile. Per-program VMEM residency is
``block_k * block_n`` int8 (+ its f32 convert) plus the small x/out tiles,
so arbitrary K fits; shapes whose dims no supported tile divides fall back
to the XLA dequant + matmul path instead of failing in the Mosaic compiler
(the n % block_n fallback generalized, per ADVICE round 3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributed_pytorch_tpu.ops.quant import QuantTensor, dequantize
from distributed_pytorch_tpu.utils.platform import on_tpu

# Candidate K-tile sizes, largest first: bigger tiles amortize grid overhead;
# 128 is the MXU contraction width and the f32 lane tile, so every candidate
# keeps the x tile lane-aligned. 2048 int8 x 512 lanes = 1 MB int8 + 4 MB f32
# convert per tile — comfortable in ~16 MB VMEM.
_BLOCK_K_CANDIDATES = (2048, 1024, 512, 256, 128)


def _kernel(x_ref, q_ref, s_ref, o_ref):
    kid = pl.program_id(1)

    @pl.when(kid == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    x = x_ref[:]  # [B, block_k] float32
    w = q_ref[:].astype(jnp.float32)  # [block_k, bn] int8 -> f32, in VMEM
    o_ref[:] += jax.lax.dot_general(
        x,
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kid == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = o_ref[:] * s_ref[:]  # s: [1, bn] per-output-channel


def _pad_rows(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    rows = x.shape[0]
    padded = -(-rows // multiple) * multiple
    if padded == rows:
        return x
    return jnp.pad(x, ((0, padded - rows), (0, 0)))


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def _quant_matmul_tpu(
    x: jnp.ndarray,
    q: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    block_n: int,
    block_k: int,
    interpret: bool,
) -> jnp.ndarray:
    batch, k = x.shape
    n = q.shape[1]
    x32 = _pad_rows(x.astype(jnp.float32), 8)  # f32 sublane multiple
    grid = (n // block_n, k // block_k)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((x32.shape[0], block_k), lambda j, kk: (0, kk)),
            pl.BlockSpec((block_k, block_n), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda j, kk: (0, j)),
        ],
        # Independent of the K grid index: the same output block is revisited
        # across K tiles (sequential on TPU), accumulating in place.
        out_specs=pl.BlockSpec((x32.shape[0], block_n), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((x32.shape[0], n), jnp.float32),
        interpret=interpret,
    )(x32, q, scale)
    return out[:batch].astype(x.dtype)


def quant_matmul(
    x: jnp.ndarray,
    qt: QuantTensor,
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x [B, K] @ dequant(qt) [K, N] -> [B, N]`` reading int8 weights.

    ``qt`` must be a 2-D :class:`~.quant.QuantTensor` quantized over its
    contraction dim (``quantize_int8(w, (0,))`` — scale shape ``[1, N]``).
    Runs the Pallas kernel on TPU (or under ``interpret=True``); elsewhere —
    or when no supported tile divides N and K evenly — falls back to the
    XLA dequant + matmul path, so every shape computes correctly and only
    aligned ones take the kernel.
    """
    if qt.q.ndim != 2 or qt.scale.shape != (1, qt.q.shape[1]):
        raise ValueError(
            f"need a 2-D weight quantized over dim 0; got q {qt.q.shape}, "
            f"scale {qt.scale.shape}"
        )
    k, n = qt.q.shape
    block_k = next((c for c in _BLOCK_K_CANDIDATES if k % c == 0), None)
    use_kernel = interpret or on_tpu()
    if not use_kernel or n % block_n != 0 or block_k is None:
        return (x @ dequantize(qt, x.dtype)).astype(x.dtype)
    return _quant_matmul_tpu(
        x, qt.q, qt.scale, block_n=block_n, block_k=block_k, interpret=interpret
    )

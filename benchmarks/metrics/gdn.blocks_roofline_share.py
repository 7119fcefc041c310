"""The blocked prefill's share of its roofline: the least time the chip could
take for the gated-delta layers' blocked evaluation of the traced steps'
prefill pieces, over the device time of its operations (``harness/linear.py``
``blocks``: recognised by shape).

The floor, a piece and layer: the larger of the evaluation's matrix-product
FLOPs at the chip's bf16 peak (``gdn_blocks_flops`` of the reference module:
the Gram products, the unit-triangular solve, the four products with the
state, for the piece's whole blocks: the engine's ``state_blocks`` of the
``prefill.chunk`` slice, padding included, which is what the program
evaluates) and its least bytes at the memory bandwidth
(``gdn_blocks_min_bytes``: the state once in and once out a piece, a token's
``q, k, v, o, alpha, beta``). The program evaluates in float32 at the highest
matmul precision (six bf16 passes a product), so against the bf16 peak this
share reads low by that factor before anything else."""

from harness import hybrid, linear, peaks


def read(ctx):
    seconds = linear.device_seconds(ctx, ("blocks",))
    pieces = linear.traced_pieces(ctx)
    if not seconds or not pieces:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    layers = ref.layer_types(cfg).count("linear_attention")
    least = 0.0
    for blocks in pieces:
        tokens = blocks * linear.BLOCK
        least += layers * peaks.roofline_seconds(
            ref.gdn_blocks_flops(cfg, tokens, linear.BLOCK),
            ref.gdn_blocks_min_bytes(cfg, tokens, 1), ctx["device_kind"])[0]
    return 100.0 * least / seconds

"""The Mamba-2 mixers' share of their roofline: the least time to move what
the traced steps' mixers had to move, at the chip's memory bandwidth, over
the device time of their operations (``ssd.device_ms_per_step``'s).

What they had to move: every decoded row's state and every prefill chunk's
state, read once and written once (``state_bytes_per_slot``: a chunk is
evaluated in blocks, and the floor carries the state once a CHUNK, so more
blocks lower this share), and per token and Mamba-2 layer the recurrence's
inputs and output (``x`` and ``y`` over ``H P``, ``dt`` over ``H``, ``B`` and
``C`` over ``G N``, float32). The decode step is bound by memory (per state
element a token costs 6 vector operations against 8 bytes); a chunk's block
evaluation adds matrix products that the floor does not count."""

from harness import hybrid, moe_hybrid, peaks


def read(ctx):
    seconds = moe_hybrid.device_seconds(ctx, "ssd")
    steps = moe_hybrid.traced_steps(ctx)
    if not seconds or steps is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    states = sum(p["decode_rows"] + p["prefill_chunks"] for p in steps)
    tokens = sum(p["decode_rows"] + p["prefill_tokens"] for p in steps)
    nbytes = (2.0 * ref.state_bytes_per_slot(cfg) * states
              + ref.scan_io_bytes_per_token(cfg) * tokens)
    least = nbytes / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds

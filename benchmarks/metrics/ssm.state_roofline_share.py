"""The conv's and the scan's share of their roofline: the least time to move
what the traced steps' mixers had to move, at the chip's memory bandwidth,
over the device time of their operations (``ssm.device_ms_per_step``'s).

What they had to move: every decoded row's state and every prefill chunk's
state, read once and written once (``state_bytes_per_slot``), and per token
and Mamba layer the scan's inputs and output (``u``, ``delta`` and ``y`` over
``d_inner``, ``B`` and ``C`` over ``N``, float32). Both kinds of operation are
bound by memory: per state element a token costs 7 vector operations against
8 bytes."""

from harness import hybrid, peaks


def read(ctx):
    seconds = hybrid.ssm_device_seconds(ctx)
    steps = hybrid.traced_steps(ctx)
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

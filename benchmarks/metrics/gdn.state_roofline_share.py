"""The gated-delta decode kernel's share of its roofline: the least time to
read and write the states the traced steps' decode programs touched, at the
chip's memory bandwidth, over the device time of the kernel's calls
(``linear_attention._gated_delta_step``, by name).

What it had to move is the engine's own count, ``state_bytes_moved`` of the
traced ``step`` slices: live rows x gated-delta layers x one float32 state
``[H, d_k, d_v]`` once in and once out. Not in the floor: a token's ``q, k,
v, alpha, beta`` and ``o`` (a hundredth of the state), and the state of a row
outside the dispatch group, which the kernel copies through. The update is
bound by memory (per state element 7 vector operations against 8 bytes), so
the share cannot pass 100%."""

from harness import linear, peaks


def read(ctx):
    seconds = linear.device_seconds(ctx, ("step",))
    moved = linear.state_bytes_moved(ctx)
    if not seconds or not moved:
        return None
    least = moved / peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds

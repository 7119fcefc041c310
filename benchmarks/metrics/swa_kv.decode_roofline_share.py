"""The windowed K/V decode kernel's share of its roofline: the least time the
traced steps' window-layer decode attention could take over the device time
of the kernel's calls (``attention._window_paged_decode_step``).

Least time is the larger of bytes / bandwidth and FLOPs / peak
(``harness/peaks.py``), with the reference module's counts over ALL the window
layers: bytes are ``k`` and ``v`` of every key INSIDE a row's window at the
published 4,096 B a token and layer (the engine's
``decode_window_tokens_visible``, ``min(pos + 1, window)`` a row; the kernel
copies the short table's whole block, ``decode_window_tokens_read``, which
lowers the share); FLOPs are the same pairs against 64 heads' 128-wide scores
and sums. Summed over the steps that started in the traced window."""

from harness import hybrid, peaks, window


def read(ctx):
    seconds = window.device_seconds(ctx, "decode")
    visible = window.traced_window_tokens(ctx)
    if not seconds or not visible:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        ref.window_decode_min_bytes(cfg, visible) / peak["hbm_bytes_per_s"],
        ref.window_decode_flops(cfg, visible) / peak["bf16_flops"])
    return 100.0 * least / seconds

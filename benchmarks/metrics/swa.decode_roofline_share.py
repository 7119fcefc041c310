"""The windowed decode kernel's share of its roofline: the least time the
traced steps' sliding-layer decode attention could take over the device time
of the kernel's calls (``attention._window_latent_decode_step``).

Least time a step and sliding layer is the larger of bytes / bandwidth and
FLOPs / peak, with the reference module's counts: bytes are the latent of
every key INSIDE a row's window at the published ``r + dr`` numbers (the
engine's ``decode_window_tokens_visible``, ``min(pos + 1, window)`` a row; the
kernel copies whole pages, ``decode_window_tokens_read``, and a pool padded to
whole lanes: both lower the share); FLOPs are the same pairs against ``H``
heads. Summed over the steps that started in the traced window, times the
sliding layers."""

from harness import dsa, hybrid, peaks


def read(ctx):
    seconds = dsa.device_seconds(ctx, "window")
    counted = dsa.step_counters(ctx)
    if not seconds or counted is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        ref.window_decode_min_bytes(cfg, counted["window_visible"])
        / peak["hbm_bytes_per_s"],
        ref.window_decode_flops(cfg, counted["window_visible"])
        / peak["bf16_flops"])
    return 100.0 * dsa.layer_counts(cfg)[1] * least / seconds

"""The sparse decode's share of its roofline: the least time the traced
steps' attention over the selected tokens could take over the device time of
the gather of their latents and of the kernel's calls
(``attention._sparse_latent_decode_step``; the gather is the kernel's read of
the pool, done by XLA before it: ``harness/dsa.py``'s ``gather``).

Least time a step and full layer is the larger of bytes / bandwidth and FLOPs
/ peak, with the reference module's counts: bytes are the selected tokens'
latents at the published ``r + dr`` numbers (``decode_kv_tokens_selected``:
rows select apart, nothing is shared); FLOPs are the same pairs against ``H``
heads' ``r + dr`` wide scores and ``r`` wide sums. Summed over the steps that
started in the traced window, times the full layers."""

from harness import dsa, hybrid, peaks


def read(ctx):
    seconds = dsa.device_seconds(ctx, "sparse", "gather")
    counted = dsa.step_counters(ctx)
    if not seconds or counted is None or not dsa.device_seconds(ctx, "sparse"):
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        ref.sparse_decode_min_bytes(cfg, counted["selected"])
        / peak["hbm_bytes_per_s"],
        ref.sparse_decode_flops(cfg, counted["selected"]) / peak["bf16_flops"])
    return 100.0 * dsa.layer_counts(cfg)[0] * least / seconds

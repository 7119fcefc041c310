"""The latent decode kernel's share of its roofline: the least time the
traced steps' decode attention could take over the device time of the
kernel's calls (``attention._latent_decode_step``).

Least time a step and layer is the larger of bytes / bandwidth and FLOPs /
peak (``harness/peaks.py``), with the counts of the configuration's reference
module: bytes are the latent of every DISTINCT cached token among the rows'
pages, once, at the published ``r + dr`` numbers a token (the engine's
``decode_kv_tokens_distinct``: rows that share a document count it once, so
the share stays under 100% whatever a later kernel does with them, and a pool
padded to whole lanes lowers it); FLOPs are every row's ``pos + 1`` visible
keys against ``H`` heads' ``r + dr`` wide scores and ``r`` wide sums
(``decode_kv_tokens_visible``). Summed over the steps that started in the
traced window, times the layers."""

from harness import hybrid, latent, peaks


def read(ctx):
    seconds = latent.device_seconds(ctx, "decode")
    counted = latent.traced_decode_counters(ctx)
    if not seconds or counted is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        ref.latent_decode_min_bytes(cfg, counted["distinct"])
        / peak["hbm_bytes_per_s"],
        ref.latent_decode_flops(cfg, counted["visible"])
        / peak["bf16_flops"])
    return 100.0 * cfg["num_hidden_layers"] * least / seconds

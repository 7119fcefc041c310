"""The index-score kernel's share of its roofline: the least time the traced
steps' index scoring could take over the device time of the kernel's calls
(``attention._index_scores``).

Least time a step and full layer is the larger of bytes / bandwidth and FLOPs
/ peak (``harness/peaks.py``), with the counts of the configuration's
reference module: bytes are the index key of every DISTINCT cached token
among the rows' pages, once, at the published ``index_head_dim`` numbers a
token (the engine's ``decode_index_tokens_scored_distinct``: askers of one
document score the same keys, so a kernel that reads them once for all stays
under 100%); FLOPs are every row's ``pos + 1`` visible keys against
``index_n_heads`` heads' ``index_head_dim`` wide products
(``decode_index_tokens_scored``). Summed over the steps that started in the
traced window, times the full layers."""

from harness import dsa, hybrid, peaks


def read(ctx):
    seconds = dsa.device_seconds(ctx, "index")
    counted = dsa.step_counters(ctx)
    if not seconds or counted is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    peak = peaks.peaks_for(ctx["device_kind"])
    least = max(
        ref.index_scores_min_bytes(cfg, counted["scored_distinct"])
        / peak["hbm_bytes_per_s"],
        ref.index_scores_flops(cfg, counted["scored"]) / peak["bf16_flops"])
    return 100.0 * dsa.layer_counts(cfg)[0] * least / seconds

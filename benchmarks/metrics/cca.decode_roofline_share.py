"""The K/V decode kernel's share of its roofline in a cell whose every layer is
CCA: the least time the traced steps' decode attention could take over the
device time of the kernel's calls (``attention._paged_decode_step``).

Least time is the larger of bytes / bandwidth and FLOPs / peak
(``harness/peaks.py``), with the reference module's counts over ALL the
layers: bytes are ``k`` and ``v`` of every key a decoded row sees at the
published 1,024 B a token and layer (the engine's ``decode_kv_tokens_visible``,
``pos + 1`` a row, as ``serving/decode_reads.py`` counts the traced
dispatches; the kernel copies whole blocks, ``decode_kv_tokens_fetched``,
which lowers the share); FLOPs are the same pairs against 8 heads' 128-wide
scores and sums. Summed over the steps that started in the traced window."""

from harness import cca, hybrid, peaks


def read(ctx):
    seconds = cca.device_seconds(ctx, "kernel")
    visible = cca.traced_visible_tokens(ctx)
    if not seconds or not visible:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    least, _ = peaks.roofline_seconds(
        ref.decode_kv_flops(cfg, visible), ref.decode_kv_min_bytes(cfg, visible),
        ctx["device_kind"])
    return 100.0 * least / seconds

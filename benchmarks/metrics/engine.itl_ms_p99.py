"""99th percentile of every gap between consecutive tokens of a deck request
in the window: the steadier or noisier twin of ``tpot_ms_p50``, kept as a
per-layer reading in cells where it is not an end-to-end metric."""

from harness import stats


def read(ctx):
    gaps = ctx.get("gaps_ms") or []
    if len(gaps) < 200:
        return None
    return stats.percentile(gaps, 99.0)

"""Model FLOP/s utilization: the FLOPs the forward and backward passes need
for the samples trained in the window (counted from the configuration's
shapes by its reference module) over the window's wall time, over chips x
peak bf16 FLOP/s."""

from harness import peaks


def read(ctx):
    if not ctx.get("samples_per_s"):
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"]
    flops = ctx["train_flops_per_sample"] * ctx["samples_per_s"]
    return 100.0 * flops / (ctx["chips"] * peak)

"""Least time the chip could take for one training step, over the time the
device was busy per step in the traced steps. The least time is the larger
of the step's FLOPs / peak and its unavoidable bytes / bandwidth, both
counted from the configuration's shapes by its reference module; for
ResNet-50 at 256 images a chip it is the FLOPs that bound it."""

from harness import peaks


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("traced_steps") or not trace.busy_s:
        return None
    samples = ctx["global_batch"] // ctx["chips"]
    seconds, bound = peaks.roofline_seconds(
        ctx["train_flops_per_sample"] * samples,
        ctx["train_min_bytes_per_step"], ctx["device_kind"])
    ctx["roofline_bound"] = bound
    return 100.0 * seconds * ctx["traced_steps"] / trace.busy_s

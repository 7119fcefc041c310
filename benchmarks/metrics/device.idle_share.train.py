"""Share of a training step in which no operation ran on the device: 1 -
(device-busy time per step, from the trace) / (wall time per step of the
window's median epoch, untraced). The traced steps' own wall time is not
used: the profiler stretches a training epoch's host time about threefold
here (PERF.md), so 1 - busy / traced window overstates; that figure is what
``device.busy_s`` and ``device.window_s`` of the result line give."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not ctx.get("traced_steps") or not ctx.get("step_s_p50"):
        return None
    busy_per_step = trace.busy_s / ctx["traced_steps"]
    return 100.0 * (1.0 - busy_per_step / ctx["step_s_p50"])

"""Share of the traced window in which no operation ran on the device: 1 -
(union of device op intervals) / window, mean over the chips used."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)

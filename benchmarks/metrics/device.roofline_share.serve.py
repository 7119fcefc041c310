"""Least time the chip could take for the traced steps' work, over the time
the device was busy in the traced window.

The work is counted from shapes, step by step, by ``harness/peaks.py``: every
step reads the weights once and the KV entries of the contexts its rows attend
to (the pages the rows own, not the pool), writes the new KV entries, and
multiplies every new position against every matrix. The least time of a step
is the larger of FLOPs / peak and bytes / bandwidth; the steps' least times
are summed. ``roofline_bound`` of the context says which bound set most of
it."""

from harness import peaks


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.busy_s:
        return None
    cfg = ctx["cfg"]
    t0, t1 = ctx["traced"]
    least = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for plan, (s0, s1) in zip(ctx["counters"]["plans"], ctx["step_rows"]):
        if s0 < t0 or s1 > t1:
            continue
        new = plan["decode_rows"] + plan["prefill_tokens"]
        if not new:
            continue
        context = plan["decode_context"] + plan["prefill_context"]
        flops = peaks.lm_forward_flops(cfg, new, context, plan["decode_rows"])
        kv = peaks.kv_bytes_per_token(cfg)
        nbytes = (peaks.lm_weight_bytes(cfg)
                  + kv * (plan["decode_context"] + plan["prefill_keys"] + new))
        seconds, bound = peaks.roofline_seconds(flops, nbytes, ctx["device_kind"])
        least += seconds
        by_bound[bound] += seconds
    ctx["roofline_bound"] = max(by_bound, key=by_bound.get)
    return 100.0 * least / trace.busy_s

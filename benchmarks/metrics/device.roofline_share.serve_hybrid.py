"""Least time the chip could take for the traced steps' work, over the time
the device was busy in the traced window: ``device.roofline_share.serve`` for
a configuration whose counts come from its reference module
(``serve_flops``, ``serve_min_bytes``) and not from ``harness/peaks.py``'s
transformer.

The work is counted step by step, as that reader counts it: a step that runs
anything reads every weight once, reads the KV entries its rows attend to and
writes the new ones, reads and writes the recurrent state of every decoded
row and of every prefill chunk, and multiplies every new position against
every matrix. How many programs the engine makes of a step (one for the
decode rows and one for every prefill chunk, each reading the weights again)
is the engine's choice and not in the floor: fewer programs for the same
steps raise this share. The least time of a step is the larger of FLOPs /
peak and bytes / bandwidth; the steps' least times are summed.
``roofline_bound`` of the context says which bound set most of it."""

from harness import hybrid, peaks


def read(ctx):
    trace = ctx.get("trace")
    steps = hybrid.traced_steps(ctx)
    if trace is None or not trace.busy_s or steps is None:
        return None
    cfg = ctx["cfg"]
    ref = hybrid.reference_for(cfg)
    by_bound = {"compute": 0.0, "memory": 0.0}
    for plan in steps:
        new = plan["decode_rows"] + plan["prefill_tokens"]
        if not new:
            continue
        flops = ref.serve_flops(
            cfg, new, plan["decode_context"] + plan["prefill_context"],
            plan["decode_rows"])
        nbytes = ref.serve_min_bytes(
            cfg, plan["decode_rows"], plan["prefill_tokens"],
            plan["decode_context"] + plan["prefill_keys"],
            plan["prefill_chunks"])
        seconds, bound = peaks.roofline_seconds(flops, nbytes, ctx["device_kind"])
        by_bound[bound] += seconds
    if not any(by_bound.values()):
        return None
    ctx["roofline_bound"] = max(by_bound, key=by_bound.get)
    return 100.0 * sum(by_bound.values()) / trace.busy_s

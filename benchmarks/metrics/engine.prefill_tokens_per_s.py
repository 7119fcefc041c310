"""Prompt tokens prefilled in the window over the summed wall time of the
engine steps that carried a prefill chunk."""


def read(ctx):
    plans, rows = ctx["counters"]["plans"], ctx["step_rows"]
    tokens = seconds = 0.0
    for plan, (t0, t1) in zip(plans, rows):
        if plan["prefill_tokens"]:
            tokens += plan["prefill_tokens"]
            seconds += t1 - t0
    if not seconds:
        return None
    return tokens / seconds

"""Prefill programs the engine ran over the window's engine steps: its
``prefill.chunk`` slices (each exactly one program and a stretch of one
request) over its ``step`` slices. Every such program reads the weights its
tokens reach, however few they are, so fewer programs a step is device time
given back to the decode program."""


def read(ctx):
    events = [e for e in ctx.get("engine_events") or () if e.get("ph") == "X"]
    steps = sum(e["name"] == "step" for e in events)
    if not steps:
        return None
    return sum(e["name"] == "prefill.chunk" for e in events) / steps

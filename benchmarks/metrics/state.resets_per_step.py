"""Recurrent states started from zeros (admissions, and re-prefills after a
preemption) over the window's engine steps: the engine's ``state.reset``
instants over its ``step`` slices. Nothing to read from a program without
them."""


def read(ctx):
    events = ctx.get("engine_events") or ()
    steps = sum(e.get("ph") == "X" and e["name"] == "step" for e in events)
    resets = sum(e["name"] == "state.reset" for e in events)
    if not steps or not resets:
        return None
    return resets / steps

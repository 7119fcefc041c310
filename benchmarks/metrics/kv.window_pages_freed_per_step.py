"""Pages the window group's tables gave back to its allocator an engine step:
the ``pages`` of the engine's ``window.free`` instants (one a step that frees:
after every prefill piece and decode dispatch a sequence's pages wholly behind
its window go) over its ``step`` slices, the window's. Nothing to read from a
program without a window group."""


def read(ctx):
    events = ctx.get("engine_events") or ()
    steps = sum(e.get("ph") == "X" and e["name"] == "step" for e in events)
    freed = [e["args"]["pages"] for e in events if e["name"] == "window.free"]
    if not steps or not freed:
        return None
    return sum(freed) / steps

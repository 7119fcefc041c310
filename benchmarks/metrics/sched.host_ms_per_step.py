"""Mean host time of the engine's ``schedule`` phase per step, from the
engine tracer's phase spans (host clock, microseconds)."""


def read(ctx):
    spans = [e["dur"] for e in ctx["engine_events"]
             if e.get("ph") == "X" and e.get("name") == "schedule"]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e3

"""The share of the prefill programs' token positions that was padding, in
percent: 1 - the ``prefill.chunk`` slices' summed ``tokens`` over their
summed ``width``. Nothing to read from a program whose slices carry no
``width`` (every program then was as long as its piece)."""


def read(ctx):
    pieces = [e["args"] for e in ctx.get("engine_events") or ()
              if e.get("ph") == "X" and e["name"] == "prefill.chunk"]
    if not pieces or any("width" not in p for p in pieces):
        return None
    return 100.0 * (1.0 - sum(p["tokens"] for p in pieces)
                    / sum(p["width"] for p in pieces))

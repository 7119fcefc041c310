"""Key positions the paged-decode kernel read over the ones its rows could
see: the engine's ``decode_kv_tokens_fetched`` over its
``decode_kv_tokens_visible``, both summed over the window's ``step`` slices
(whole blocks walked against ``pos + 1`` a row; a program on the gather path
counts every slot's whole table). Nothing to read from a program without the
counters."""


def read(ctx):
    steps = [e.get("args") or {} for e in ctx.get("engine_events") or ()
             if e.get("ph") == "X" and e["name"] == "step"]
    fetched = sum(a.get("decode_kv_tokens_fetched", 0) for a in steps)
    visible = sum(a.get("decode_kv_tokens_visible", 0) for a in steps)
    if not visible:
        return None
    return fetched / visible

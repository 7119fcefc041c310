"""Of the cached tokens the window's decode rows could see, the share a full
layer's attention read: the engine's ``decode_kv_tokens_selected`` over its
``decode_kv_tokens_visible``, both summed over the window's ``step`` slices,
in percent (``min(pos + 1, index_topk)`` over ``pos + 1`` a row: 100 until a
context passes ``index_topk``). Nothing to read from a program without the
counters."""

from harness import dsa


def read(ctx):
    counted = dsa.step_counters(ctx, traced_only=False)
    if counted is None or not counted["visible"]:
        return None
    return 100.0 * counted["selected"] / counted["visible"]

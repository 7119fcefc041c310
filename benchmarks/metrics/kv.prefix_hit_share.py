"""Of the prompt tokens admitted in the window, the share the prefix trie
served: the engine's ``admit`` events' ``cached_tokens`` over their
``prompt_tokens`` (first admissions only: a readmission after a preemption
finds its own pages). Nothing to read from a program whose ``admit`` events
lack ``prompt_tokens``, or from a window without admissions."""


def read(ctx):
    hit = asked = 0
    for e in ctx.get("engine_events") or ():
        args = e.get("args") or {}
        if (e["name"] != "admit" or "prompt_tokens" not in args
                or args.get("readmission")):
            continue
        hit += min(args["cached_tokens"], args["prompt_tokens"])
        asked += args["prompt_tokens"]
    if not asked:
        return None
    return 100.0 * hit / asked

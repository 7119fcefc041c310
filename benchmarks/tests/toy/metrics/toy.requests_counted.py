"""A per-layer metric added by files alone: the deck requests the run saw."""


def read(ctx):
    return len(ctx["requests"])

"""Mean host time the loader took to hand over one batch (indexing the
dataset and stacking the rows), from the harness's ``loader.next`` span."""


def read(ctx):
    spans = ctx["spans"].durations("loader.next")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)

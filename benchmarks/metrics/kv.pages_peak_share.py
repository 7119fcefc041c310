"""Peak share of the KV pool's pages that requests held (referenced pages,
not idle cached ones), sampled after every step of the window."""


def read(ctx):
    pages = ctx["counters"]["pages"]
    if not pages:
        return None
    return 100.0 * max(pages) / ctx["num_pages"]

"""The latent and index kernels' page copies counted the slow way, a walk, a
block, a turn and a page at a time as the kernels' copy loops go
(``ops/paged_attention.py`` ``_latent_decode_kernel`` ``start_next``,
``_index_scores_kernel`` ``start``): the yardstick of the vectorised
``latent_copies_started`` / ``index_copies_started``. Nothing but tests runs
it."""

import math

from distributed_pytorch_tpu.ops.paged_attention import block_widths


def _neighbours(entries):
    """One turn's table entries name neighbouring pages of the pool."""
    entries = [int(e) for e in entries]
    return len(entries) > 1 and all(
        e == entries[0] + i for i, e in enumerate(entries))


def latent_copies_by_loop(tables, positions, leader, shared, page, npb):
    """A block's LEADING turns of neighbours go as one copy each; from its
    first turn that is none on, a copy a page."""
    widths = block_widths(npb)
    chunk = math.gcd(*widths)
    copies = in_runs = 0
    for r, table in enumerate(tables):
        last = min(int(positions[r]) // page, len(table) - 1)
        walks = [(int(shared[r]), last + 1 - int(shared[r]))]
        if leader[r] == r and shared[r] > 0:
            walks.insert(0, (0, int(shared[r])))
        for first, pages in walks:
            for p0 in range(first, first + pages, npb):
                live = min(first + pages - p0, npb)
                width = next(w for w in widths if w >= live)
                leading = True
                for n0 in range(0, width, chunk):
                    leading = leading and _neighbours(
                        table[p0 + min(n0 + i, live - 1)]
                        for i in range(chunk))
                    copies += 1 if leading else chunk
                    in_runs += chunk if leading else 0
    return copies, in_runs


def index_copies_by_loop(tables, positions, leader, shared, page, npb):
    """The LEADING blocks of a row's table that are neighbours go as one
    copy each, where the row walks them; every other block a copy a page."""
    copies = in_runs = 0
    for r, table in enumerate(tables):
        last = min(int(positions[r]) // page, len(table) - 1)
        end = min(int(positions[r]) // (npb * page) + 1,
                  -(-len(table) // npb))
        known = 0
        while known < end and _neighbours(
                table[min(known * npb + n, last)] for n in range(npb)):
            known += 1
        ahead = 0 if leader[r] == r else int(shared[r]) // npb
        for block in range(min(ahead, end - 1), end):
            copies += 1 if block < known else npb
            in_runs += npb if block < known else 0
    return copies, in_runs

#!/usr/bin/env python3
"""Time, on the chip, the pieces of a sparse layer's decode step at the
``dots3-note-prev`` cell's shapes (32 rows at ~33,000 cached tokens, a table of
3,104 pages, 2,048 selected), each alone, and a pass of the plain reference
layer by layer. The index kernel on three dispatches: four askers a document
(the cell's), 32 rows of the same lengths of which NOBODY shares a page with
another, and one row alone; each a second time over PERMUTED physical pages
(the same tables and contents, no two neighbouring pages left: the kernels
copy a run of neighbours as one DMA, and there is none). Host clock around
``block_until_ready``; the traced cell's readers have the device time. Beside
them the latent decode kernel at ``deepseek-v2-lite-docqa``'s dispatch (32
rows, the traffic file's 16 documents, two askers each, 640 lanes, 16 heads)
by DEVICE time, in three dispatches: documents in runs, the same over
permuted pages, nobody sharing. Every dispatch prints the copies its kernel
starts and the share of its pages that go in runs, where the tree counts
them. Writes ``chiprun_out/dsa_pieces.json``, or ``--out``: a copy of this
file in another commit's tree times that commit's kernels. ``*_device_ms`` is
the device's time, from a trace (``bench_grouped_matmul.device_time``).

    chiprun --timeout 1500 -- python3 tools/bench_dsa_pieces.py [--reference]
"""

import argparse
import functools
import inspect
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def timed(fn, *args, calls=10, **kw):
    import jax

    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def no_neighbours(num_pages: int):
    """A permutation of the pool's physical pages (the null page stays) under
    which no two neighbours stay neighbours: ``perm[p]`` is where page ``p``
    goes."""
    import numpy as np

    step = next(
        m for m in range(7, num_pages) if np.gcd(m, num_pages - 1) == 1)
    perm = 1 + (np.arange(num_pages) - 1) * step % (num_pages - 1)
    perm[0] = 0
    return perm


def moved(pool, perm):
    """``pool``'s pages where ``perm`` sends them."""
    import jax.numpy as jnp
    import numpy as np

    return pool[jnp.asarray(np.argsort(perm))]


def copies(count, tables, positions, page, npb, turn):
    """What ``count`` (the tree's ``latent_copies_started`` or
    ``index_copies_started``; ``None`` in a tree that has none) says of a
    dispatch whose kernel copies ``turn`` pages a turn of its copy loop: the
    descriptors started and the share of the copied pages that go in runs (a
    descriptor is a run's ``turn`` pages or one page)."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    if count is None:
        return {}
    live = tables[:, 0] != 0
    tables, positions = tables[live], positions[live]
    started, in_runs = count(
        tables, positions,
        *pa.shared_prefix_groups(tables, positions, page, npb), page, npb)
    pages = started - in_runs // turn + in_runs
    return {"copies_started": started, "pages_in_runs": in_runs,
            "share_in_runs": in_runs / max(1, pages)}


def latent_pieces() -> dict:
    """The latent decode kernel at ``deepseek-v2-lite-docqa``'s dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_grouped_matmul import device_time
    from distributed_pytorch_tpu.ops import paged_attention as pa

    with open(os.path.join(
            ROOT, "benchmarks", "traffic", "docqa_closed_c32.json")) as f:
        documents = np.asarray(json.load(f)["documents"])
    rng = np.random.default_rng(0)
    slots, pages_per_seq, num_pages, page = 32, 1024, 10241, 16
    positions = (
        np.repeat(documents, 2) + rng.integers(32, 384, size=slots)
    ).astype(np.int32)
    # A document's whole pages stand side by side in the pool, as set-up
    # prefills them; an asker's own pages follow one another too.
    tables = np.zeros((slots, pages_per_seq), np.int32)
    apart = np.zeros_like(tables)
    base = 1
    for d, n in enumerate(documents // page):
        tables[2 * d : 2 * d + 2, :n] = base + np.arange(n)
        base += n
    for r, n in enumerate(positions // page + 1):
        whole = documents[r // 2] // page
        tables[r, whole:n] = base + np.arange(n - whole)
        base += n - whole
    assert base <= num_pages
    # 32 rows of these lengths, no page held twice: half the pool's worth.
    lengths = positions // 2
    start = 1
    for r, n in enumerate(lengths // page + 1):
        apart[r, :n] = start + np.arange(n)
        start += n
    perm = no_neighbours(num_pages)
    key = jax.random.PRNGKey(1)
    pool = jax.random.normal(key, (num_pages, page, 640), jnp.bfloat16)
    q = jax.random.normal(key, (slots, 1, 16, 640), jnp.bfloat16)
    npb = pa.block_pages(pages_per_seq, page, 640, jnp.bfloat16)
    attend = jax.jit(functools.partial(
        pa.paged_latent_attention, v_width=512, kernel="pallas",
        sm_scale=0.1147))
    count = getattr(pa, "latent_copies_started", None)
    out = {"latent_block_pages": int(npb)}
    results = {}
    for name, held_by, at, held in (
        ("latent_decode_runs", tables, positions, pool),
        ("latent_decode_permuted", perm[tables], positions,
         moved(pool, perm)),
        ("latent_decode_no_sharing", apart, lengths, pool),
        ("latent_decode_no_sharing_permuted", perm[apart], lengths,
         moved(pool, perm)),
    ):
        # The grouping, and the turns that are runs where the tree has
        # them, beforehand: a decode program works them out once for its
        # layers.
        groups = pa.shared_prefix_groups(held_by, at, page, npb)
        if hasattr(pa, "latent_runs"):
            groups += (pa.latent_runs(held_by, at, *groups, page, npb),)
        args = (q, held, jnp.asarray(held_by), jnp.asarray(at))
        told = {"row_groups": tuple(map(jnp.asarray, groups))}
        seconds, ops = device_time(lambda *a: attend(*a, **told), args, 20)
        out[name + "_device_ms"] = seconds * 1e3
        out[name + "_kernel_ms"] = 1e3 * sum(
            t for op, t in ops.items() if "latent_decode" in op)
        out[name] = copies(
            count, held_by, at, page, npb, math.gcd(*pa.block_widths(npb)))
        results[name] = np.asarray(attend(*args, **told))
    out["latent_permuted_pages_give_the_same_bits"] = bool(
        np.array_equal(
            results["latent_decode_runs"], results["latent_decode_permuted"])
        and np.array_equal(
            results["latent_decode_no_sharing"],
            results["latent_decode_no_sharing_permuted"]))
    return out


def pieces() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(0)
    slots, pages_per_seq, num_pages, page, k = 32, 3104, 18433, 16, 2048
    # The cell's dispatch: 8 documents of 16,430-49,152 tokens, four askers
    # each, an asker a question and an answer so far (32-384 tokens) past
    # its document; the document's whole pages are the same physical pages in
    # its askers' tables, what follows them is each asker's own.
    documents = rng.integers(16430, 49152, size=slots // 4)
    positions = (
        np.repeat(documents, 4) + rng.integers(32, 384, size=slots)
    ).astype(np.int32)
    tables = np.zeros((slots, pages_per_seq), np.int32)
    apart = np.zeros_like(tables)
    for r, n in enumerate(positions // page + 1):
        whole = documents[r // 4] // page
        tables[r, :whole] = 1 + ((r // 4) * 2300 + np.arange(whole)) % 17000
        tables[r, whole:n] = 17001 + r * 32 + np.arange(n - whole)
        # no two rows hold the same page at any index of their tables
        apart[r, :n] = 1 + (r * 571 + np.arange(n)) % (num_pages - 1)
    lone = np.where(np.arange(slots)[:, None] == 0, apart, 0)
    key = jax.random.PRNGKey(0)
    index_pool = jax.random.normal(key, (num_pages, page, 128), jnp.bfloat16)
    pool = jax.random.normal(key, (num_pages, page, 640), jnp.bfloat16)
    wide = jax.random.normal(key, (num_pages, page, 1152), jnp.bfloat16)
    q_i = jax.random.normal(key, (slots, 64, 128), jnp.bfloat16)
    w_i = jax.random.normal(key, (slots, 64), jnp.float32)
    q = jax.random.normal(key, (slots, 1, 128, 640), jnp.bfloat16)
    q_w = jax.random.normal(key, (slots, 1, 64, 1152), jnp.bfloat16)
    out = {}
    index_scores = jax.jit(
        pa.paged_index_scores, static_argnames=("kernel",))
    grouping = "row_groups" in inspect.signature(
        pa.paged_index_scores).parameters

    def told(held_by):
        """The rows' grouping worked out beforehand, as a decode program
        works it out once for its layers (a kernel that takes one)."""
        if not grouping:
            return {}
        return {"row_groups": tuple(map(jnp.asarray, pa.shared_prefix_groups(
            held_by, positions, page, pa.index_block_pages(pages_per_seq))))}

    dispatches = {
        "index_scores_kernel": tables,
        "index_scores_kernel_no_sharing": apart,
        "index_scores_kernel_one_row": lone,
    }
    perm = no_neighbours(num_pages)
    index_pool_moved = moved(index_pool, perm)
    count = getattr(pa, "index_copies_started", None)
    same_bits = True
    for name, held_by in dispatches.items():
        scored = {}
        for twin, held, keys in (
            ("", held_by, index_pool),
            ("_permuted", perm[held_by], index_pool_moved),
        ):
            args = (q_i, w_i, keys, jnp.asarray(held), jnp.asarray(positions))
            out[name + twin + "_ms"] = timed(
                index_scores, *args, kernel="pallas", calls=50, **told(held))
            scored[twin] = np.asarray(
                index_scores(*args, kernel="pallas", **told(held)))
            block = pa.index_block_pages(pages_per_seq)
            out[name + twin] = copies(
                count, held, positions, page, block, block)
        same_bits &= np.array_equal(scored[""], scored["_permuted"])
    out["index_permuted_pages_score_the_same_bits"] = bool(same_bits)
    groups = told(tables)
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)
    scores_of = functools.partial(
        index_scores, q_i, w_i, index_pool, tables, positions)
    out["index_scores_xla_ms"] = timed(scores_of, kernel="xla", calls=3)
    scores = scores_of(kernel="pallas", **groups)
    by_gather = scores_of(kernel="xla")
    out["index_scores_kernel_off_xla_max"] = float(jnp.max(jnp.abs(jnp.where(
        jnp.isfinite(by_gather), scores - by_gather, 0.0))))
    if grouping:
        rows = jnp.arange(slots, dtype=jnp.int32)
        alone = scores_of(
            kernel="pallas", row_groups=(rows, jnp.zeros_like(rows)))
        out["index_scores_grouped_are_the_bits_of_rows_alone"] = bool(
            jnp.all(scores == alone))
        out["index_rows_grouped"] = pa.index_rows_grouped(
            np.asarray(groups["row_groups"][1]),
            pa.index_block_pages(pages_per_seq))
    mask_of = jax.jit(lambda s: pa.top_k_mask(s, k))
    out["top_k_mask_ms"] = timed(mask_of, scores)
    mask = mask_of(scores)
    positions_of = jax.jit(lambda m: pa.selected_positions(m, k))
    out["selected_positions_ms"] = timed(positions_of, mask)
    # This host dispatches a call in ~0.4 ms: under that only the device's
    # own time (a trace of 20 calls, the operations' durations) says anything.
    from bench_grouped_matmul import device_time

    out["selected_positions_device_ms"] = device_time(
        positions_of, (mask,), 20)[0] * 1e3
    out["lax_top_k_ms"] = timed(jax.jit(lambda s: jax.lax.top_k(s, k)), scores)
    where, real = pa.selected_positions(mask, k)
    out["selected_positions_are_flatnonzero"] = bool(
        np.asarray(real).all() and all(
            (np.flatnonzero(row) == found).all()
            for row, found in zip(np.asarray(mask), np.asarray(where))))
    _, by_sort = jax.lax.top_k(scores, k)
    same = jnp.all(jnp.sort(by_sort, axis=-1) == where)
    out["selection_agrees_with_lax_top_k"] = bool(same)
    out["sparse_decode_ms"] = timed(jax.jit(lambda: pa.sparse_latent_attention(
        q, pool, tables, where, real, v_width=512, kernel="pallas",
        sm_scale=0.07)))
    out["gather_alone_ms"] = timed(jax.jit(lambda: pool[
        jnp.take_along_axis(tables, where // page, axis=1), where % page]))
    out["window_decode_ms"] = timed(jax.jit(lambda: pa.paged_latent_attention(
        q_w, wide, tables, positions, v_width=1024, kernel="pallas",
        sm_scale=0.0625, window=513)))
    return out


def reference_pass() -> dict:
    import jax
    import run as bench

    cfg = bench.load_json(
        os.path.join(ROOT, "benchmarks", "configs", "dots3-note-prev.json"))
    ref = bench.load_module(
        os.path.join(ROOT, "benchmarks", "reference", "dots3_note.py"))
    driver = bench.load_module(os.path.join(
        ROOT, "benchmarks", "drivers", "serve_sparse_latent_moe.py"))
    weights = ref.make_weights(cfg, 7)
    jax.block_until_ready(weights)
    driver.experts_to_host(weights)
    tokens = list(range(1, 1 + 49536))
    rows = list(range(49536 - 257, 49536 - 1))
    out = {}
    layers = {}
    programs = ref._programs

    def timing_programs(*args):
        embed, by_kind, head = programs(*args)

        def timed_layer(kind):
            def run(x, w, q0):
                t0 = time.perf_counter()
                result = by_kind[kind](x, w, q0)
                jax.block_until_ready(result)
                layers.setdefault(str(kind), []).append(
                    round(time.perf_counter() - t0, 2))
                return result
            return run

        return embed, {kind: timed_layer(kind) for kind in by_kind}, head

    ref._programs = timing_programs
    for name in ("first_pass_s", "second_pass_s"):
        t0 = time.perf_counter()
        jax.block_until_ready(ref.logits_at(
            cfg, weights, tokens, rows, pad_tokens_to=49664, pad_rows_to=256))
        out[name] = time.perf_counter() - t0
    out["layer_seconds"] = layers
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--reference", action="store_true")
    parser.add_argument(
        "--out", default=os.path.join(ROOT, "chiprun_out", "dsa_pieces.json"))
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform

    init_platform()
    out = {"pieces": pieces()}
    print(json.dumps(out), flush=True)
    out["latent"] = latent_pieces()
    print(json.dumps(out["latent"]), flush=True)
    if args.reference:
        out["reference"] = reference_pass()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

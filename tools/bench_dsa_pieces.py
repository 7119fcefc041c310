#!/usr/bin/env python3
"""Time, on the chip, the pieces of a sparse layer's decode step at the
``dots3-note-prev`` cell's shapes (32 rows at ~33,000 cached tokens, a table of
3,104 pages, 2,048 selected), each alone, and a pass of the plain reference
layer by layer. Host clock around ``block_until_ready``; the traced cell's
readers have the device time. Writes ``chiprun_out/dsa_pieces.json``.

    chiprun --timeout 1500 -- python3 tools/bench_dsa_pieces.py [--reference]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))


def timed(fn, *args, calls=10):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def pieces() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(0)
    slots, pages_per_seq, num_pages, page, k = 32, 3104, 18433, 16, 2048
    positions = rng.integers(16384, 49152, size=slots).astype(np.int32)
    tables = np.zeros((slots, pages_per_seq), np.int32)
    for r, pos in enumerate(positions):  # four askers a document
        n = pos // page + 1
        tables[r, :n] = 1 + (r // 4) * 2300 + np.arange(n) % 2300
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)
    key = jax.random.PRNGKey(0)
    index_pool = jax.random.normal(key, (num_pages, page, 128), jnp.bfloat16)
    pool = jax.random.normal(key, (num_pages, page, 640), jnp.bfloat16)
    wide = jax.random.normal(key, (num_pages, page, 1152), jnp.bfloat16)
    q_i = jax.random.normal(key, (slots, 64, 128), jnp.bfloat16)
    w_i = jax.random.normal(key, (slots, 64), jnp.float32)
    q = jax.random.normal(key, (slots, 1, 128, 640), jnp.bfloat16)
    q_w = jax.random.normal(key, (slots, 1, 64, 1152), jnp.bfloat16)
    out = {}
    scores_of = jax.jit(lambda: pa.paged_index_scores(
        q_i, w_i, index_pool, tables, positions, kernel="pallas"))
    out["index_scores_kernel_ms"] = timed(scores_of)
    out["index_scores_xla_ms"] = timed(jax.jit(lambda: pa.paged_index_scores(
        q_i, w_i, index_pool, tables, positions, kernel="xla")), calls=3)
    scores = scores_of()
    mask_of = jax.jit(lambda s: pa.top_k_mask(s, k))
    out["top_k_mask_ms"] = timed(mask_of, scores)
    mask = mask_of(scores)
    out["selected_positions_ms"] = timed(
        jax.jit(lambda m: pa.selected_positions(m, k)), mask)
    out["lax_top_k_ms"] = timed(jax.jit(lambda s: jax.lax.top_k(s, k)), scores)
    where, real = pa.selected_positions(mask, k)
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
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform

    init_platform()
    out = {"pieces": pieces()}
    print(json.dumps(out), flush=True)
    if args.reference:
        out["reference"] = reference_pass()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dsa_pieces.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

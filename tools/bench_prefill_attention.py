#!/usr/bin/env python3
"""Time, on the chip, a K/V layer's read of a prefill piece both ways: the
DENSE read (``paged_attention_reference``: the row's whole table gathered,
every query scored against every key of it, a one-shot softmax over the
table's width) and the WALK that took its place (``paged_attention`` at
``t_step > 1``: the blocks the row holds, a block at a time with an online
softmax). One layer call each, by DEVICE time from a trace (:func:`device_busy`:
the union of the operations' intervals, so that a ``while`` and the body
inside it count once; ``bench_grouped_matmul.device_time`` sums every event
and reads a loop twice), at the four geometries the cells serve K/V attention
at:

    StarCoder2   24 query heads on 2,   table of  4,096 keys
    K-EXAONE     64 query heads on 8,   table of 12,800 keys
    Olmo hybrid  32 on 32 (30 held as 32, heads of 128), 2,048 keys
    Jamba        20 query heads on 1,   table of  2,048 keys

at pieces 64, 256 and 512 wide that start at 0, a quarter, a half and the END
of the table (``table - width``: the walk then reads every block, the dense
read's own work and the loop on top), with the walk's block at 512, 1,024 and
2,048 tokens. The dense read costs the same wherever the piece starts, so it
is timed once a width.

This is where ``ops/paged_attention.py`` ``WALK_BLOCK_TOKENS`` is chosen (ONE
number for all four geometries: ``by_block`` sums every walk's time a block
size) and where what a piece at the table's END pays against the dense read
it replaced is read off (``end_of_table_ratio_max``, at widths of 256 and up;
the worst narrower piece is named beside it: 0.4-1.3 at blocks of 512 on the
v5e, Jamba's one KV head alone over 1.25). A layer call ALONE
is not the call inside a cell's program: there the compiler lays out and
fuses the dense read otherwise (422 us a 512-wide piece in
``sc2-3b-completion``'s trace where this tool reads 236; PERF.md section 6,
PR 47), so a cell's traced run decides what the walk saves, and this tool
which block. Writes ``chiprun_out/prefill_attention.json``, or ``--out``.

    chiprun --timeout 1500 -- python3 tools/bench_prefill_attention.py
    JAX_PLATFORMS=cpu python tools/bench_prefill_attention.py --rehearse   # toy shapes, host clock: control flow only
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

PAGE = 16
#: name -> (query heads, KV heads as the pool holds them, head size, table
#: tokens): what ``Attention._paged_decode_step`` hands the op in each cell.
GEOMETRIES = {
    "starcoder2_24on2_4096": (24, 2, 128, 4096),
    "k_exaone_64on8_12800": (64, 8, 128, 12800),
    "olmo_32on32_2048": (32, 32, 128, 2048),
    "jamba_20on1_2048": (20, 1, 128, 2048),
}
TOY = {"toy_4on2_256": (4, 2, 16, 256)}
WIDTHS = (64, 256, 512)
BLOCKS = (512, 1024, 2048)


def host_time(fn, args, calls):
    """:func:`device_busy`'s answer by the host's clock: the rehearsal's, off
    the chip."""
    from bench_dsa_pieces import timed

    return timed(fn, *args, calls=calls) / 1e3, {}


def device_busy(fn, args, calls):
    """Seconds the first device is busy a call of ``fn(*args)``, from a trace
    of ``calls`` calls: the union of the intervals on its ``XLA Ops`` line (a
    ``while``'s event spans its body's). Beside it the four longest
    operations by name, a call."""
    import jax
    from harness.trace import _union
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    logdir = tempfile.mkdtemp(prefix="pa_trace_")
    try:
        jax.profiler.start_trace(logdir)
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = max(
            glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        # The first device plane that has the line (a chip shows planes of
        # other kinds beside it).
        events = next((
            [(ev.name.split(" = ", 1)[0].strip("%"), int(ev.start_ns),
              int(ev.duration_ns)) for ev in line.events]
            for plane in sorted(
                (p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:")), key=lambda p: p.name)
            for line in plane.lines if line.name.lower() == "xla ops"), [])
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    if not events:
        raise RuntimeError(f"no device operation in the trace under {logdir}")
    busy = sum(e - b for b, e in _union([(b, b + d) for _, b, d in events]))
    by_name = {}
    for name, _, dur in events:
        by_name[name] = by_name.get(name, 0) + dur
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:4]
    return busy / 1e9 / calls, {k: v / 1e9 / calls for k, v in top}


def problem(heads, kv_heads, d, table, width, seed):
    """A row that owns its whole table (a long prompt: its later pieces' pages
    are its own already), pages dealt in ascending order as the allocator's
    free list deals them, in a pool four tables large."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    pages = table // PAGE
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (4 * pages + 1, PAGE, kv_heads, d)
    q = jax.random.normal(kq, (1, width, heads, d), jnp.bfloat16)
    kp = jax.random.normal(kk, pool, jnp.bfloat16)
    vp = jax.random.normal(kv, pool, jnp.bfloat16)
    bt = jnp.asarray(pages + 1 + np.arange(pages, dtype=np.int32)[None])
    return q, kp, vp, bt


def bench(geometries, widths, blocks, calls, measure, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.ops import paged_attention as pa

    rows = []
    for name, (heads, kv_heads, d, table) in geometries.items():
        for width in widths:
            q, kp, vp, bt = problem(heads, kv_heads, d, table, width, seed)
            starts = sorted({0, table // 4, table // 2, table - width})
            lens = lambda start: jnp.asarray([start], jnp.int32)  # noqa: E731
            dense = jax.jit(pa.paged_attention_reference)
            dense_s, top = measure(dense, (q, kp, vp, bt, lens(0)), calls)
            ref = np.asarray(
                dense(q, kp, vp, bt, lens(starts[-1])), np.float32)
            row = {
                "geometry": name, "width": width, "table": table,
                "dense_ms": dense_s * 1e3,
                "dense_top_ms": {k: v * 1e3 for k, v in top.items()},
                "walk_ms": {},
            }
            for block in blocks:
                pa.WALK_BLOCK_TOKENS = block  # read when the walk is traced
                walk = jax.jit(lambda *a: pa.paged_attention(*a, kernel="xla"))
                out = np.asarray(
                    walk(q, kp, vp, bt, lens(starts[-1])), np.float32)
                row.setdefault("gap_to_dense_max", {})[str(block)] = float(
                    np.abs(out - ref).max())
                row["walk_ms"][str(block)] = {
                    str(start): measure(
                        walk, (q, kp, vp, bt, lens(start)), calls)[0] * 1e3
                    for start in starts
                }
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def summary(rows, blocks):
    """A block size's sum of every walk's time (beside the dense reads' sum
    over the same calls), and the worst piece at its table's end against the
    dense read it replaced."""
    by_block, worst = {}, {}
    for block in map(str, blocks):
        walked = sum(sum(r["walk_ms"][block].values()) for r in rows)
        dense = sum(r["dense_ms"] * len(r["walk_ms"][block]) for r in rows)
        by_block[block] = {"walk_ms": walked, "dense_ms": dense}
        for wide in (True, False):
            ratios = [
                (r["walk_ms"][block][str(r["table"] - r["width"])]
                 / r["dense_ms"], r["geometry"], r["width"])
                for r in rows if (r["width"] >= 256) == wide
            ]
            if ratios:
                worst.setdefault(block, {})[
                    "widths_256_up" if wide else "narrower"] = max(ratios)
    return {"by_block": by_block, "end_of_table_ratio_max": worst}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse", action="store_true",
                        help="toy shapes by the host's clock, on any backend")
    parser.add_argument("--calls", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "prefill_attention.json"))
    args = parser.parse_args()

    import jax

    from distributed_pytorch_tpu.ops import paged_attention as pa
    from distributed_pytorch_tpu.utils.platform import (
        enable_compile_cache,
        on_tpu,
    )

    enable_compile_cache()
    if not (on_tpu() or args.rehearse):
        sys.exit("a device time comes from the chip; --rehearse runs the "
                 "control flow at toy shapes")
    shipped = pa.WALK_BLOCK_TOKENS
    if args.rehearse:
        geometries, widths, blocks, measure = TOY, (16, 64), (32, 64), host_time
    else:
        geometries, widths, blocks, measure = (
            GEOMETRIES, WIDTHS, BLOCKS, device_busy)
    rows = bench(geometries, widths, blocks, args.calls, measure, args.seed)
    pa.WALK_BLOCK_TOKENS = shipped
    device = jax.devices()[0]
    out = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "clock": "host" if args.rehearse else "device_trace",
        "shipped_block_tokens": shipped,
        **summary(rows, blocks),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}, indent=1))


if __name__ == "__main__":
    main()

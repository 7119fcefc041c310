"""``jax.lax.ragged_dot`` against ``ops/grouped_matmul.py``'s kernel on the
chip, at the shapes and routing of the two cells that run ``RoutedExperts``
(``granite-4.0-h-small-chat``: 36 held of 72 experts, top 10, 4096 x 1536 and
768 x 4096; ``deepseek-v2-lite-docqa``: 8 held of 64, top 6, 2048 x 2816 and
1408 x 2048), for a decode program's tokens and the prefill pieces' widths.

    chiprun -- python3 tools/bench_grouped_matmul.py            # the table
    python3 tools/bench_grouped_matmul.py --rehearse            # CPU, toy, interpreted

Every time is DEVICE time from a profiler trace of ``--calls`` calls (the sum
of the operations' durations on the first device's ``XLA Ops`` line over the
calls: the product, and what the compiler or the wrapper puts round it), so
the host's dispatch is in none of them. Beside it the share of the HBM
bandwidth: the weights of every reached expert once, over the time. Writes
``chiprun_out/grouped_matmul.json`` and prints a table.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.ops import grouped_matmul as gm

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmarks/harness/peaks.py)

#: name: (routed experts, held, top_k, d_model, d_ff, decode tokens, prefill widths)
MODELS = {
    "granite": (72, 36, 10, 4096, 768, 64, (128, 192, 256, 512)),
    "deepseek": (64, 8, 6, 2048, 1408, 32, (64, 128, 192, 512)),
}
TOY = {"toy": (8, 4, 3, 128, 128, 8, (16,))}


def routed_sizes(rng, tokens, n_experts, held, top_k):
    """Group sizes of ``tokens`` tokens that each choose ``top_k`` distinct
    experts of ``n_experts`` evenly (random weights route evenly): the pairs
    on the first ``held``."""
    chosen = np.stack([
        rng.choice(n_experts, top_k, replace=False) for _ in range(tokens)
    ])
    return np.bincount(chosen.reshape(-1), minlength=n_experts)[:held]


def device_time(fn, args, calls):
    """Seconds of device time a call of ``fn(*args)``, and the operations'
    shares by name, from a trace of ``calls`` calls."""
    from jax.profiler import ProfileData

    jax.block_until_ready(fn(*args))
    logdir = tempfile.mkdtemp(prefix="gm_trace_")
    try:
        jax.profiler.start_trace(logdir)
        out = None
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = max(
            glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime,
        )
        by_name = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                if line.name.lower() != "xla ops":
                    continue
                for ev in line.events:
                    key = ev.name.split(" = ", 1)[0].strip("%")
                    by_name[key] = by_name.get(key, 0.0) + ev.duration_ns / 1e9
            break
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    total = sum(by_name.values())
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:4]
    return total / calls, {k: v / calls for k, v in top}


def variants(on_chip, k, n, item, sweep):
    """name -> ``f(rows, w, sizes)``: the compiler's product, the kernel as
    ``RoutedExperts`` calls it and, in a ``sweep``, every row tile and column
    tile beside XLA's own time to read the same weights once (the ceiling)."""
    interpret = not on_chip
    out = {
        "ragged_dot": lambda r, w, s: jax.lax.ragged_dot(
            r, w, s, preferred_element_type=jnp.float32),
        f"kernel tm{gm.ROW_TILE} tn{gm.column_tile(k, n, item)}":
            lambda r, w, s: gm._stationary(r, w, s, interpret=interpret)[0],
    }
    if not sweep:
        return out
    columns = [
        tn for tn in range(128, n + 1, 128)
        if n % tn == 0 and (1 << 19) <= k * tn * item <= (6 << 20)
    ]
    for tile in (16, 32):
        for tn in columns:
            out.setdefault(f"kernel tm{tile} tn{tn}", (
                lambda r, w, s, tile=tile, tn=tn: gm._stationary(
                    r, w, s, interpret=interpret, tile=tile, column=tn)[0]))
    out["stream (sum of w)"] = lambda r, w, s: jnp.sum(w, dtype=jnp.float32)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="", help="a model's name")
    ap.add_argument("--widths", default="", help="prefill widths, e.g. 256")
    ap.add_argument("--sweep", action="store_true",
                    help="every column tile and row tile, not the kernel's own")
    args = ap.parse_args()
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        raise SystemExit("no TPU here: a time from a CPU is no result "
                         "(--rehearse runs the toy through the interpreter)")
    models = TOY if args.rehearse else MODELS
    dtype = jnp.bfloat16
    item = 2
    rng = np.random.default_rng(args.seed)
    rows_out = []
    for name, (n_exp, held, top_k, d, f, slots, widths) in models.items():
        if args.only and args.only != name:
            continue
        key = jax.random.PRNGKey(args.seed)
        w_in = jax.random.normal(key, (held, d, 2 * f), dtype) * 0.02
        w_out = jax.random.normal(key, (held, f, d), dtype) * 0.02
        if args.widths:
            widths = tuple(int(x) for x in args.widths.split(","))
        for tokens in (slots,) + tuple(widths):
            sizes_np = routed_sizes(rng, tokens, n_exp, held, top_k)
            sizes = jnp.asarray(sizes_np, jnp.int32)
            pairs = tokens * top_k
            total = int(sizes_np.sum())
            for which, w in (("in", w_in), ("out", w_out)):
                k, n = w.shape[1:]
                x = jax.random.normal(key, (pairs, k), dtype)
                floor = int((sizes_np > 0).sum()) * k * n * item / HBM_BYTES_PER_S
                want = None
                fns = variants(
                    on_chip, k, n, item,
                    args.sweep and tokens in (slots, widths[-1]))
                for vname, fn in fns.items():
                    jitted = jax.jit(fn)
                    try:
                        got = np.asarray(jitted(x, w, sizes))
                        err = -1.0  # not a product: the stream
                        if got.ndim == 2:
                            got = got[:total]
                            want = got if want is None else want
                            err = float(np.abs(got - want).max()) if total else 0.0
                        secs, top = device_time(jitted, (x, w, sizes), args.calls)
                    except Exception as ex:  # a variant the compiler refuses
                        print(f"{name} {which} rows {pairs} {vname}: FAILED "
                              f"{str(ex)[:300]}", flush=True)
                        continue
                    rec = dict(
                        model=name, product=which, rows=pairs, held_rows=total,
                        groups=held, reached=int((sizes_np > 0).sum()), k=k, n=n,
                        variant=vname, device_us=secs * 1e6,
                        hbm_share=floor / secs if secs else None,
                        floor_us=floor * 1e6, max_err=err,
                        rows_computed=gm.rows_computed(sizes_np),
                        top={a: b * 1e6 for a, b in top.items()},
                    )
                    rows_out.append(rec)
                    print(
                        f"{name:8s} {which:3s} rows {pairs:5d} (held {total:5d}) "
                        f"{k:4d}x{n:4d} {vname:22s} "
                        + (f"{secs * 1e6:9.1f} us  {100 * floor / secs:5.1f}% of HBM"
                           if secs else "time not measured (no device trace)")
                        + f"  err {err:.2e}  "
                        + ", ".join(f"{a[:28]} {b * 1e6:.1f}" for a, b in top.items()),
                        flush=True,
                    )
    if not on_chip:
        return  # a rehearsal's table holds no time: nothing to keep
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_matmul.json", "w") as fh:
        json.dump(dict(device=str(jax.devices()[0].device_kind), rows=rows_out),
                  fh, indent=1)


if __name__ == "__main__":
    main()

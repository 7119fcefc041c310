"""MFU investigation probe: capture a real-chip trace of a bench workload and
break the step down per-op, with a bandwidth roofline.

Usage::

    python tools/mfu_probe.py resnet --batch 128 --logdir traces/resnet50_b128
    python tools/mfu_probe.py lm --seq 8192 --logdir traces/lm_t8192

Captures ``jax.profiler`` traces of N steady-state steps (matching the
reference's profiled-workload evidence, ``multigpu_profile.py:80-91``), then
parses the XPlane with ``jax.profiler.ProfileData`` and prints:

* the top ops by total device time (name, category, time, share);
* totals per category (convolution / fusion / copy / ...);
* XLA cost-analysis FLOPs + bytes accessed -> arithmetic intensity and the
  bandwidth-bound MFU ceiling for the chip.
"""

import argparse
import collections
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(step, state, batches, logdir, n_steps=5, warmup=5):
    import itertools
    import jax

    it = itertools.cycle(batches)
    loss = None
    for _ in range(warmup):
        state, loss = step(state, next(it))
    jax.block_until_ready(loss)
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    for _ in range(n_steps):
        state, loss = step(state, next(it))
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    return logdir


def analyze(logdir, n_steps, flops_per_step, peak_flops, peak_bw, bytes_per_step=None):
    """Aggregate the serialized per-op timeline (device plane, 'XLA Ops' line
    — non-overlapping, so durations sum to real busy time; the 'Async XLA Ops'
    line holds overlapping DMA spans and must NOT be summed)."""
    from jax.profiler import ProfileData

    xplanes = sorted(
        glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    )
    if not xplanes:
        raise SystemExit(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_serialized_xspace(open(xplanes[-1], "rb").read())

    op_time = collections.Counter()
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                op_time[event.name] += event.duration_ns

    total_ns = sum(op_time.values())
    per_step_ms = total_ns / n_steps / 1e6
    print(f"\ntrace: {xplanes[-1]}")
    print(f"device busy time: {per_step_ms:.3f} ms/step over {n_steps} steps")

    cat_time = collections.Counter()
    for name, ns in op_time.items():
        cat_time[op_category(name)] += ns

    print("\n-- by category (top 12) --")
    for cat, ns in cat_time.most_common(12):
        print(f"{ns / n_steps / 1e6:9.3f} ms/step  {100 * ns / total_ns:5.1f}%  {cat}")

    print("\n-- top 15 ops --")
    for name, ns in op_time.most_common(15):
        print(
            f"{ns / n_steps / 1e6:9.3f} ms/step  {100 * ns / total_ns:5.1f}%  "
            f"[{op_category(name):>12}]  {short_name(name)}"
        )

    if flops_per_step:
        achieved = flops_per_step / (per_step_ms / 1e3)
        print(
            f"\nmodel FLOPs/step {flops_per_step / 1e9:.2f} G -> "
            f"{achieved / 1e12:.1f} TFLOP/s busy-time MFU {achieved / peak_flops:.1%}"
        )
        if bytes_per_step:
            # Bandwidth roofline from XLA's logical bytes (understates reuse
            # the caches capture; the xprof op_profile's measured HBM traffic
            # is the sharper number when available). Same math as the serving
            # observatory's per-program attribution — one source of truth.
            from distributed_pytorch_tpu.obs.roofline import roofline_point

            point = roofline_point(
                flops_per_step, bytes_per_step, peak_flops, peak_bw
            )
            measured_s = per_step_ms / 1e3
            frac = (
                min(1.0, point["floor_s"] / measured_s)
                if point["floor_s"] > 0 and measured_s > 0
                else 0.0
            )
            print(
                f"roofline: intensity "
                f"{point['intensity_flops_per_byte']:.1f} FLOP/B vs ridge "
                f"{point['ridge_flops_per_byte']:.0f} FLOP/B -> "
                f"{point['bound']}-bound, floor "
                f"{point['floor_s'] * 1e3:.3f} ms/step "
                f"(compute {point['compute_floor_s'] * 1e3:.3f} / memory "
                f"{point['memory_floor_s'] * 1e3:.3f}), achieved "
                f"{frac:.1%} of the roofline at peak HBM "
                f"({peak_bw / 1e9:.0f} GB/s)"
            )
    return op_time, cat_time, per_step_ms


def op_category(name: str) -> str:
    """Family from the HLO instruction text (`%n = type opcode(...)`).

    Event names in the trace are truncated, so the opcode after a long tuple
    result type may be cut off — fall back to the op-name family (the name
    before `` = `` with the trailing instance number stripped), which the
    compiler derives from the fused ops and is never truncated."""
    import re

    base = re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))
    m = re.search(r"= (?:\([^)]*\)|\S+) ([\w-]+)\(", name)
    opcode = m.group(1) if m else None
    if " convolution(" in name:
        return "convolution"
    if opcode == "dot":
        return "matmul"
    if (opcode and "copy" in opcode) or base.startswith(("copy", "slice-start")):
        return "copy/layout"
    if opcode and ("all-reduce" in opcode or "collective" in opcode or "permute" in opcode):
        return "collective"
    if opcode == "fusion" or base.endswith("fusion"):
        return f"fusion:{base}" if base != "fusion" else "fusion(unnamed)"
    return opcode or base


def short_name(name: str) -> str:
    return name.split(" = ")[0].lstrip("%")[:80]


def cost_summary(compiled, label):
    try:
        a = compiled.cost_analysis()
        if isinstance(a, list):
            a = a[0]
        flops = float(a.get("flops", 0.0))
        byac = float(a.get("bytes accessed", 0.0))
        print(
            f"{label}: cost_analysis flops={flops / 1e9:.2f}G "
            f"bytes={byac / 1e9:.3f}GB intensity={flops / max(byac, 1):.1f} flop/B"
        )
        return flops, byac
    except Exception as e:
        print(f"{label}: no cost analysis ({e})")
        return None, None


def probe_resnet(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import compile_with_flops
    from distributed_pytorch_tpu.models import ResNet50
    from distributed_pytorch_tpu.obs.goodput import (
        peak_flops_per_chip,
        resnet50_train_flops,
    )
    from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    batch = args.batch
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 224, 224, 3)).astype(np.float32)
    y = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    optimizer = optax.sgd(1e-3, momentum=0.9)
    state = create_train_state(model, optimizer, x[:1])
    step_fn = make_train_step(model.apply, optimizer, softmax_cross_entropy_loss)
    device_batch = jax.device_put((x, y))
    compiled, flops = compile_with_flops(step_fn, state, device_batch)
    flops = flops or resnet50_train_flops(batch)
    _, nbytes = cost_summary(compiled, f"resnet50_b{batch}")

    logdir = args.logdir or f"traces/resnet50_b{batch}"
    capture(compiled, state, [device_batch], logdir, n_steps=args.steps)
    peak = peak_flops_per_chip(jax.devices()[0])
    analyze(logdir, args.steps, flops, peak, args.peak_bw, bytes_per_step=nbytes)


def probe_lm(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import compile_with_flops
    from distributed_pytorch_tpu.models.transformer import TransformerLM
    from distributed_pytorch_tpu.obs.goodput import (
        count_params,
        peak_flops_per_chip,
        transformer_train_flops,
    )
    from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    vocab, d_model, n_layers, n_heads, d_ff = 32768, 512, 6, 8, 2048
    seq = args.seq
    batch = max(1, 16384 // seq)
    rng = np.random.default_rng(0)
    x = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    y = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_layers=n_layers, n_heads=n_heads,
        d_ff=d_ff, dtype=jnp.bfloat16, remat=args.remat,
        fused_head_chunk=8192 if args.fused else 0,
    )
    optimizer = optax.adam(1e-4)
    state = create_train_state(model, optimizer, x[:1])
    if args.fused:
        step_fn = make_train_step(
            model.apply, optimizer, lambda out, _: out, apply_takes_targets=True
        )
    else:
        step_fn = make_train_step(model.apply, optimizer, softmax_cross_entropy_loss)
    device_batch = jax.device_put((x, y))
    compiled, flops = compile_with_flops(step_fn, state, device_batch)
    # When XLA won't report a cost analysis, fall back to the same analytic
    # PaLM-style formula bench.py and the serving engine use (obs.goodput is
    # the single source of truth for the FLOPs model).
    flops = flops or transformer_train_flops(
        n_params=count_params(state.params),
        embed_params=vocab * d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        head_dim=d_model // n_heads,
        seq_len=seq,
        batch=batch,
    )
    _, nbytes = cost_summary(compiled, f"lm_t{seq}")

    logdir = args.logdir or f"traces/lm_t{seq}"
    capture(compiled, state, [device_batch], logdir, n_steps=args.steps)
    peak = peak_flops_per_chip(jax.devices()[0])
    analyze(logdir, args.steps, flops, peak, args.peak_bw, bytes_per_step=nbytes)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("workload", choices=["resnet", "lm"])
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--fused", action="store_true")
    p.add_argument("--remat", action="store_true", default=None)
    p.add_argument("--no-remat", dest="remat", action="store_false")
    p.add_argument("--logdir", default=None)
    p.add_argument(
        "--peak_bw", type=float, default=None,
        help="HBM bandwidth B/s for the roofline (default: by device kind "
        "from obs.roofline.HBM_BYTES_PER_SEC; v5e-class 819 GB/s fallback)",
    )
    args = p.parse_args()
    if args.peak_bw is None:
        import jax

        from distributed_pytorch_tpu.obs.roofline import hbm_bandwidth_per_chip

        args.peak_bw = hbm_bandwidth_per_chip(jax.devices()[0])
    if args.workload == "lm" and args.remat is None:
        args.remat = False  # bench default: flash keeps activations linear in T
    if args.workload == "resnet":
        probe_resnet(args)
    else:
        probe_lm(args)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once through the entry points a user calls, at a
real width, and checks what comes out by the repo's own means:

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # the four-chip paths ONLY (run by hand)

One chip, in this order:

1. **launcher** — ``python -m distributed_pytorch_tpu.elastic`` with one worker
   running ``examples/multichip_envrun.py`` for two epochs, then again for a
   third to see the resume line. This runs BEFORE this process touches JAX: a
   chip belongs to one process, so the agent and its worker must take the chip,
   exit and give it back before the script itself opens it. It is also the
   proof that ``native/kvstore.cpp`` builds from the committed source here.
2. **kernels** — the committed block tables answer for this chip, both peak
   tables know its ``device_kind``, and the flash-attention kernel (forward
   and gradient) agrees with the dense reference.
3. **trainer** — ``Trainer.train`` as ``examples/multichip_profile.py`` drives
   it: ResNet-50, bf16, 224x224 synthetic images, batch 128. Finite, falling
   loss; one snapshot written, and read back bit-for-bit by a second Trainer.
4. **engine** — ``InferenceEngine.submit``/``run`` on the widest TransformerLM
   the records hold (d_model 2048, 16 heads of 128, 8 KV heads, d_ff 8192,
   vocab 32768; 6 layers), three ways: the default gather path,
   ``paged_kernel="auto"`` (must resolve to the compiled Pallas kernel and
   show ``tpu_custom_call`` in the decode program), and the kernel over int8
   pages. The paths are compared on LOGITS, teacher-forced over the same
   token streams through each engine's own decode model — never on greedy
   token equality, which flips on rounding.

``--chips 4`` runs only what exists across chips, each against its one-chip
twin: the Trainer on ``make_mesh()`` (data parallel) and the engine on
``make_serving_mesh(model=4)`` (tensor parallel, kernel on).

The last line of stdout is the result, and only a TPU run prints it:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check, or no TPU, is a non-zero exit with no result line. The CPU
rehearsal (``JAX_PLATFORMS=cpu python chip_smoke.py --rehearse``: tiny sizes,
kernels interpreted; add ``--chips 4`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) checks the script's
control flow only and says ``"ok": false`` with its CPU stamp.

Earlier lines are information for a reader — compile seconds, cache hits,
step and token times, kernel modes, block sizes and their tier, peak bytes —
not measurements to quote.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Stated tolerances. The network computes in bf16 (8 bits of mantissa: one
# ulp is 0.016 for values in [2, 4) and 0.031 in [4, 8)); logits are float32
# of size up to ~5 at random init. Every fp path runs the same bf16 matmuls
# and differs only in how attention accumulates, so what separates two paths
# is an ulp or two of the activations, carried through six layers.
LOGIT_TOL = 0.1  # max |logit_a - logit_b| between two fp paths, same tokens
GAP_TOL = 0.1  # an engine's greedy token vs the best logit of its own path
INT8_NLL_RTOL = 0.02  # int8 pages: mean NLL vs fp (tests/test_paged_attention)
FLASH_FWD_TOL = 0.05  # flash vs dense outputs (bf16, up to ~3): three ulps
FLASH_GRAD_TOL = 0.1  # gradients, relative to the largest reference gradient
DP_LOSS_RTOL = 2e-2  # four-chip DP loss vs one chip, step by step


@dataclasses.dataclass(frozen=True)
class Sizes:
    # Trainer: a bottleneck ResNet on synthetic images.
    stages: tuple
    image: int
    batch: int
    epochs: int
    # Engine: TransformerLM + request mix (prompt length, new tokens).
    lm: dict
    max_seq_len: int
    slots: int
    chunk: int
    requests: tuple
    # Flash-attention check.
    flash_t: int
    flash_heads: int


FULL = Sizes(
    stages=(3, 4, 6, 3),  # ResNet-50, nothing cut
    image=224, batch=128, epochs=5,
    lm=dict(vocab_size=32768, d_model=2048, n_layers=6, n_heads=16,
            n_kv_heads=8, d_ff=8192),
    max_seq_len=2048, slots=8, chunk=128,
    # More requests than slots, so admission queues and slots are reused.
    requests=((1000, 48), (777, 32), (512, 64), (300, 40), (640, 56),
              (200, 32), (901, 64), (433, 48), (350, 36), (256, 60)),
    flash_t=2048, flash_heads=16,
)
# The rehearsal's sizes: small enough to compile in seconds on the CPU (one
# block a stage; prompts of 1 + k*chunk tokens, so one prefill program).
TINY = Sizes(
    stages=(1, 1, 1, 1),
    image=32, batch=8, epochs=3,
    lm=dict(vocab_size=256, d_model=128, n_layers=2, n_heads=8,
            n_kv_heads=4, d_ff=256),
    max_seq_len=64, slots=4, chunk=8,
    requests=((33, 6), (17, 4), (25, 8), (9, 5), (41, 7)),
    flash_t=128, flash_heads=1,
)


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ launcher


def run_child(cmd, env, timeout):
    """Run one child in its own process group; on timeout the whole group is
    killed, so nothing the smoke starts outlives it."""
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise AssertionError(
            f"{' '.join(cmd)} hung past {timeout}s; output:\n{out[-3000:]}"
        )
    return proc.returncode, out


def phase_launcher(rehearse: bool, workdir: str) -> None:
    """tpurun + one worker, twice (train, then resume). The parent must not
    have initialised a backend: the worker needs the chip."""
    check("jax" not in sys.modules,
          "the launcher phase must run before this process imports JAX")
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    if not rehearse:
        # No quiet CPU run: the worker takes the TPU or fails.
        env["JAX_PLATFORMS"] = "tpu"
    snap = os.path.join(workdir, "launcher_snapshot.npz")
    base = [sys.executable, "-m", "distributed_pytorch_tpu.elastic",
            "--standalone", "--nproc-per-node", "1", "--max-restarts", "0",
            os.path.join("examples", "multichip_envrun.py")]
    for epochs, want in ((2, "Epoch 1 | Training snapshot saved"),
                         (3, "Resuming training from snapshot at Epoch 2")):
        t0 = time.perf_counter()
        rc, out = run_child(
            base + [str(epochs), "1", "--snapshot_path", snap], env, 420
        )
        check(rc == 0, f"launcher run to epoch {epochs} exited {rc}:\n"
              f"{out[-3000:]}")
        check(want in out, f"launcher output lacks {want!r}:\n{out[-3000:]}")
        line = next(l for l in out.splitlines() if want in l)
        say(f"launcher: total_epochs={epochs} rc=0 in "
            f"{time.perf_counter() - t0:.1f}s | {line.strip()}")

    from distributed_pytorch_tpu import native

    say(f"launcher: rendezvous store built from the committed source: "
        f"{os.path.relpath(native.kvstore_binary(), REPO)}")


# ------------------------------------------------------------ compile ledger


class CompileCounter:
    """Compiles, persistent-cache hits and compile seconds, from
    ``jax.monitoring`` — so every phase can say what it compiled and what the
    cache saved."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.writes = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def mark(self):
        return (self.requests, self.hits, self.writes, self.compile_s)

    def since(self, mark) -> str:
        r, h, w, s = (a - b for a, b in zip(self.mark(), mark))
        return (f"compile {s:.1f}s ({r} cacheable compiles, {h} cache hits, "
                f"{w} new cache entries)")


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2**30:.2f} GiB"


# ------------------------------------------------------------------- kernels


def phase_kernels(sz: Sizes, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.obs.goodput import peak_flops_per_chip
    from distributed_pytorch_tpu.obs.roofline import hbm_bandwidth_per_chip
    from distributed_pytorch_tpu.ops import flash_autotune as fa
    from distributed_pytorch_tpu.ops.attention import dot_product_attention
    from distributed_pytorch_tpu.ops.flash_attention import flash_attention

    dev = jax.devices()[0]
    # Both raise for a TPU kind they do not know.
    say(f"kernels: peaks for {dev.device_kind!r}: "
        f"{peak_flops_per_chip(dev) / 1e12:.0f} TFLOP/s bf16 (obs/goodput), "
        f"{hbm_bandwidth_per_chip(dev) / 1e9:.0f} GB/s (obs/roofline)")

    for var in ("FLASH_AUTOTUNE", "FLASH_BLOCKS_TABLE"):
        check(not os.environ.get(var),
              f"{var} is set: block sizes would not come from the "
              "committed tables")
    d = 128
    blocks, tier = fa.lookup_with_tier(sz.flash_t, d, "bfloat16", True)
    npb, ptier = fa.lookup_paged_with_tier(
        sz.max_seq_len, 16, sz.lm["d_model"] // sz.lm["n_heads"], "bfloat16"
    )
    say(f"kernels: flash blocks T={sz.flash_t} D={d}: {blocks} from "
        f"{tier}; paged pages_per_block={npb} from {ptier}")
    if not rehearse:
        check(tier == ptier == "shipped_table",
              f"block sizes came from {tier}/{ptier}, not the committed "
              "tables (a per-user cache file outside the checkout?)")

    rng = np.random.default_rng(0)
    shape = (1, sz.flash_t, sz.flash_heads, d)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    interpret = True if rehearse else None  # None: the kernel iff on TPU

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=interpret)
    dense = lambda q, k, v: dot_product_attention(  # noqa: E731
        q, k, v, causal=True)
    flash_fn = jax.jit(jax.value_and_grad(loss(flash), argnums=(0, 1, 2)))
    if not rehearse:
        check("tpu_custom_call" in flash_fn.lower(q, k, v).as_text(),
              "flash_attention fell back to the dense path on the TPU")
    out = jax.jit(flash)(q, k, v).astype(jnp.float32)
    ref = jax.jit(dense)(q, k, v).astype(jnp.float32)
    _, grads = flash_fn(q, k, v)
    _, ref_grads = jax.jit(
        jax.value_and_grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    fwd = float(jnp.max(jnp.abs(out - ref)))
    # Gradients of sum(out^2) scale with the outputs: compare relative to
    # the reference gradient's own size.
    gerr = max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - r.astype(jnp.float32)))
              / jnp.max(jnp.abs(r.astype(jnp.float32))))
        for g, r in zip(grads, ref_grads)
    )
    say(f"kernels: flash vs dense, causal bf16 {shape}: fwd max|diff| "
        f"{fwd:.4f} (tol {FLASH_FWD_TOL}), grad max rel diff {gerr:.4f} "
        f"(tol {FLASH_GRAD_TOL})")
    check(np.isfinite(fwd) and fwd <= FLASH_FWD_TOL, "flash forward off")
    check(np.isfinite(gerr) and gerr <= FLASH_GRAD_TOL, "flash gradient off")


# ------------------------------------------------------------------- trainer


def make_trainer(sz: Sizes, *, mesh, snapshot_path, epochs_to_save):
    """The ``examples/multichip_profile.py`` job (ResNet-50, bf16, synthetic
    images, SGD with momentum), its per-step losses recorded."""
    import jax.numpy as jnp
    import optax

    from distributed_pytorch_tpu import RandomDataset, ShardedLoader, Trainer
    from distributed_pytorch_tpu.metrics import MetricLogger
    from distributed_pytorch_tpu.models.resnet import ResNet
    from distributed_pytorch_tpu.training.losses import (
        softmax_cross_entropy_loss,
    )

    class Recorder(MetricLogger):
        def __init__(self):
            super().__init__()
            self.rows = []

        def log(self, step, **scalars):
            self.rows.append(dict(scalars, step=step))
            super().log(step, **scalars)

    # Two batches an epoch, seen again every epoch: a loss that can fall
    # within a handful of steps.
    dataset = RandomDataset(
        2 * sz.batch, (sz.image, sz.image, 3), num_classes=1000
    )
    loader = ShardedLoader(dataset, sz.batch, drop_last=True)
    recorder = Recorder()
    trainer = Trainer(
        ResNet(stage_sizes=sz.stages, dtype=jnp.bfloat16), loader,
        optax.sgd(1e-2, momentum=0.9), save_every=epochs_to_save,
        snapshot_path=snapshot_path, mesh=mesh,
        loss_fn=softmax_cross_entropy_loss, metrics=recorder, log_every=1,
    )
    return trainer, recorder


def step_losses(recorder):
    return [r["loss"] for r in recorder.rows if "loss" in r]


def phase_trainer(sz: Sizes, counter: CompileCounter, workdir: str) -> None:
    import jax
    import numpy as np

    snap = os.path.join(workdir, "trainer_snapshot.npz")
    mark = counter.mark()
    t0 = time.perf_counter()
    trainer, rec = make_trainer(
        sz, mesh=None, snapshot_path=snap, epochs_to_save=sz.epochs
    )
    trainer.train(1)
    jax.block_until_ready(trainer.state)
    first = time.perf_counter() - t0
    say(f"trainer: build + first epoch (2 steps) {first:.1f}s | "
        f"{counter.since(mark)}")

    t0 = time.perf_counter()
    trainer.train(sz.epochs)
    jax.block_until_ready(trainer.state)
    steady = time.perf_counter() - t0
    steps = 2 * (sz.epochs - 1)
    epoch_losses = [r["epoch_loss"] for r in rec.rows if "epoch_loss" in r]
    say(f"trainer: {steps} more steps + snapshot in {steady:.1f}s "
        f"(host batch assembly and the npz write included; dispatch p50 "
        f"{trainer.step_times.quantile(0.5) * 1e3:.1f} ms) | epoch losses "
        f"{[round(l, 4) for l in epoch_losses]} | peak {peak_bytes()}")
    check(all(np.isfinite(l) for l in step_losses(rec)), "non-finite loss")
    check(len(epoch_losses) == sz.epochs, "an epoch did not report")
    check(epoch_losses[-1] < epoch_losses[0],
          f"loss did not fall: {epoch_losses}")

    check(os.path.exists(snap), "no snapshot written")
    resumed, _ = make_trainer(
        sz, mesh=None, snapshot_path=snap, epochs_to_save=sz.epochs
    )
    check(resumed.epochs_run == sz.epochs,
          f"snapshot resumed at epoch {resumed.epochs_run}")
    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        resumed.state, trainer.state,
    ))
    check(same, "snapshot read back differs from the state that wrote it")
    say(f"trainer: snapshot {os.path.getsize(snap) / 2**20:.0f} MiB read "
        f"back bit-for-bit at epoch {resumed.epochs_run}")


# -------------------------------------------------------------------- engine


def make_lm(sz: Sizes, seed: int):
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(dtype=jnp.bfloat16, **sz.lm)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def make_prompts(sz: Sizes, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, sz.lm["vocab_size"], n).tolist()
            for n, _ in sz.requests]


def build_engine(sz: Sizes, model, params, **kw):
    from distributed_pytorch_tpu.serving import InferenceEngine

    return InferenceEngine(
        model, params, max_slots=sz.slots, max_seq_len=sz.max_seq_len,
        page_size=16, token_budget=2 * sz.chunk, max_prefill_chunk=sz.chunk,
        **kw,
    )


def serve(engine, sz: Sizes, prompts):
    """submit/run every request; returns the generated tokens."""
    from distributed_pytorch_tpu.serving import SamplingParams

    ids = [
        engine.submit(p, SamplingParams(max_new_tokens=new))
        for p, (_, new) in zip(prompts, sz.requests)
    ]
    engine.run()
    out = []
    for rid, (_, new) in zip(ids, sz.requests):
        status = engine.poll(rid)
        check(status.finished and len(status.generated) == new,
              f"request {rid} ended {status.state} with "
              f"{len(status.generated)}/{new} tokens")
        out.append(list(status.generated))
    return out


def decode_program_text(engine) -> str:
    """The engine's decode program, lowered with the operands it is run
    with — what actually executes each step."""
    import jax.numpy as jnp

    return engine._decode_step.lower(
        engine.params, engine.cache, jnp.asarray(engine._stage_tokens),
        engine._zero_prev, jnp.asarray(engine._stage_use_prev),
        jnp.asarray(engine._stage_tables), jnp.asarray(engine._stage_lens),
        jnp.asarray(engine._stage_temps), jnp.asarray(engine._stage_keys),
        engine._zero_bias,
    ).as_text()


def run_engine(label, sz, model, params, counter, warm, prompts, *,
               want_kernel: bool, rehearse: bool, **kw):
    """One engine: a warm-up pass that compiles every program, then the pass
    that is kept. Returns (engine, generated tokens)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops.paged_attention import resolve_kernel
    from distributed_pytorch_tpu.serving.admission import ServingMetrics

    mark = counter.mark()
    t0 = time.perf_counter()
    engine = build_engine(sz, model, params, **kw)
    serve(engine, sz, warm)
    say(f"engine[{label}]: build + warm-up pass "
        f"{time.perf_counter() - t0:.1f}s | {counter.since(mark)}")

    mode = resolve_kernel(engine.paged_kernel) if engine.paged_kernel else ""
    text = decode_program_text(engine)
    has_kernel = "tpu_custom_call" in text
    prefill_has = "tpu_custom_call" in engine._prefill_step(sz.chunk).lower(
        engine.params, engine.cache,
        *(jnp.zeros(shape, jnp.int32)
          for shape in (
              (1, sz.chunk), (1, engine.pages_per_seq), (1,), (1,))),
    ).as_text()
    say(f"engine[{label}]: paged_kernel={engine.paged_kernel or 'off'} "
        f"resolved to {mode or 'inline gather'}; tpu_custom_call in decode "
        f"program: {has_kernel}; prefill_step_c{sz.chunk} takes the "
        f"{'kernel' if prefill_has else 'XLA gather'} path")
    if want_kernel and not rehearse:
        check(mode == "pallas", f"'auto' resolved to {mode!r} on the TPU")
        check(has_kernel, "no tpu_custom_call in the decode program")
    if not want_kernel:
        check(not has_kernel, "the gather engine runs a kernel")

    engine.metrics = ServingMetrics(speculative=False)
    mark = counter.mark()
    t0 = time.perf_counter()
    tokens = serve(engine, sz, prompts)
    wall = time.perf_counter() - t0
    stats = engine.stats()
    check(counter.mark()[3] == mark[3],
          f"engine[{label}] compiled during its steady pass")
    say(f"engine[{label}]: {stats['requests_completed']} requests, "
        f"{stats['tokens_generated']} tokens in {wall:.2f}s, "
        f"{stats['engine_steps']} steps; TTFT p50 "
        f"{stats['ttft_s_p50'] * 1e3:.1f} ms, TPOT p50 "
        f"{stats['tpot_s_p50'] * 1e3:.2f} ms; preemptions "
        f"{stats['preemptions']}; peak {peak_bytes()}")
    return engine, tokens


def score(engine, streams):
    """Teacher-forced logits of ``streams`` (one token list per row, at most
    ``max_slots`` rows) through the engine's own decode model, parameters
    and pool placement: every position is one batched single-token decode
    step — the program shape the engine's decode step runs, and the only one
    that reaches the paged kernel. Returns float32 ``[T, rows, vocab]``;
    entry ``[t, r]`` predicts token ``t + 1`` of row ``r`` (garbage past a
    row's end)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu.generation import decode_token_step

    rows, pps = len(streams), engine.pages_per_seq
    check(rows <= engine.max_slots, "more rows than slots")
    steps = max(len(s) for s in streams)
    tokens = np.zeros((steps, engine.max_slots), np.int32)
    active = np.zeros((steps, engine.max_slots), bool)
    for r, s in enumerate(streams):
        tokens[: len(s), r] = s
        active[: len(s), r] = True
    # Row r owns pages [1 + r*pps, 1 + (r+1)*pps): page 0 is the null page.
    tables = 1 + np.arange(engine.max_slots * pps, dtype=np.int32).reshape(
        engine.max_slots, pps
    )
    check(tables.max() < engine.allocator.num_pages, "pool too small")

    def run(params, cache, tokens, active, tables):
        def body(cache, xs):
            t, tok, act = xs
            logits, cache = decode_token_step(
                engine.decode_model, params, cache, tok[:, None],
                block_tables=jnp.where(act[:, None], tables, 0),
                seq_lens=jnp.where(act, t, 0),
            )
            return cache, logits[:rows].astype(jnp.float32)

        _, logits = jax.lax.scan(
            body, cache, (jnp.arange(steps), tokens, active)
        )
        return logits

    # A fresh pool, placed like the engine's own.
    cache = jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding),
        engine.cache,
    )
    return jax.jit(run)(
        engine.params, cache, jnp.asarray(tokens), jnp.asarray(active),
        jnp.asarray(tables),
    )


def compare_logits(label, a, b, streams, tol):
    """max |a - b| over every live position."""
    import jax.numpy as jnp
    import numpy as np

    live = np.zeros(a.shape[:2], bool)
    for r, s in enumerate(streams):
        live[: len(s), r] = True
    mask = jnp.asarray(live)[..., None]
    worst = float(jnp.max(jnp.where(mask, jnp.abs(a - b), 0.0)))
    scale = float(jnp.max(jnp.where(mask, jnp.abs(a), 0.0)))
    say(f"engine: logits {label}: max|diff| {worst:.4f} over "
        f"{int(live.sum())} positions (tol {tol}; max|logit| {scale:.2f})")
    check(np.isfinite(worst) and worst <= tol,
          f"logits {label} differ by {worst} > {tol}")


def forced_nll(logits, streams):
    """Mean next-token NLL of the forced streams under ``logits``."""
    import jax
    import numpy as np

    logp = jax.nn.log_softmax(logits, axis=-1)
    total, count = 0.0, 0
    for r, s in enumerate(streams):
        t = np.arange(len(s) - 1)
        total += -float(logp[t, r, np.asarray(s[1:])].sum())
        count += len(t)
    return total / count


def check_greedy(label, logits, prompts, generated, reference):
    """The engine's greedy tokens against the scoring pass of the SAME path:
    wherever the engine had the forced context (up to and including its
    first departure from ``reference``, the tokens that were scored), the
    token it chose must be within GAP_TOL of the best logit."""
    import numpy as np

    where, chosen, agree = [], [], 0
    for r, (prompt, gen, ref) in enumerate(zip(prompts, generated, reference)):
        for i, tok in enumerate(gen):
            where.append((len(prompt) - 1 + i, r))
            chosen.append(tok)
            agree += tok == ref[i]
            if tok != ref[i]:
                break
    t_idx, r_idx = (np.asarray(x) for x in zip(*where))
    picked = np.asarray(logits[t_idx, r_idx])  # [checked, vocab]
    checked = len(chosen)
    gaps = picked.max(axis=-1) - picked[np.arange(checked), chosen]
    worst = float(gaps.max())
    say(f"engine[{label}]: greedy tokens vs own scoring: worst logit gap "
        f"{worst:.4f} over {checked} tokens (tol {GAP_TOL}); {agree} of them "
        f"equal the reference engine's")
    check(worst <= GAP_TOL, f"engine[{label}] chose a token {worst} below "
          "the best logit of its own path")


def phase_engine(sz: Sizes, counter: CompileCounter, rehearse: bool,
                 seed: int) -> None:
    import jax

    kernel = "interpret" if rehearse else "auto"
    model, params = make_lm(sz, seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say(f"engine: TransformerLM {sz.lm} bf16, {n_params / 1e6:.0f}M "
        f"parameters; {len(sz.requests)} requests (prompt, new) "
        f"{sz.requests} on {sz.slots} slots, max_seq_len {sz.max_seq_len}, "
        f"page 16, prefill chunks <= {sz.chunk}")
    warm, prompts = make_prompts(sz, seed + 1), make_prompts(sz, seed + 2)

    common = dict(counter=counter, warm=warm, prompts=prompts,
                  rehearse=rehearse)
    gather, tok_g = run_engine(
        "gather", sz, model, params, want_kernel=False, **common)
    hits_before = counter.hits
    fused, tok_k = run_engine(
        "kernel", sz, model, params, want_kernel=True, paged_kernel=kernel,
        **common)
    say(f"engine: the second engine build found "
        f"{counter.hits - hits_before} of its programs in the compile "
        f"cache at {os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    int8, tok_q = run_engine(
        "int8", sz, model, params, want_kernel=True, paged_kernel=kernel,
        kv_quant="int8", **common)

    # One scoring stream per slot: the gather engine's requests, prompt +
    # what it generated, through each path's decode model.
    rows = min(sz.slots, len(prompts))
    streams = [p + g for p, g in zip(prompts, tok_g)][:rows]
    mark = counter.mark()
    t0 = time.perf_counter()
    logits = {name: score(eng, streams) for name, eng in
              (("gather", gather), ("kernel", fused), ("int8", int8))}
    jax.block_until_ready(logits)
    say(f"engine: scored {sum(map(len, streams))} positions x 3 paths in "
        f"{time.perf_counter() - t0:.1f}s | {counter.since(mark)} | peak "
        f"{peak_bytes()}")
    compare_logits("kernel vs gather", logits["kernel"], logits["gather"],
                   streams, LOGIT_TOL)
    nll = {name: forced_nll(lg, streams) for name, lg in logits.items()}
    rel = abs(nll["int8"] - nll["gather"]) / nll["gather"]
    say(f"engine: forced-stream NLL gather {nll['gather']:.4f}, kernel "
        f"{nll['kernel']:.4f}, int8 {nll['int8']:.4f} (int8 vs gather "
        f"{rel:.4%}, tol {INT8_NLL_RTOL:.0%})")
    check(rel <= INT8_NLL_RTOL, "int8 pages moved the distribution")
    for name, toks in (("gather", tok_g), ("kernel", tok_k), ("int8", tok_q)):
        check_greedy(name, logits[name], prompts[:rows], toks[:rows],
                     tok_g[:rows])
    for eng in (gather, fused, int8):
        eng.close()  # asserts no leaked pages


# ---------------------------------------------------------------- four chips


def phase_dp_trainer(sz: Sizes, counter: CompileCounter) -> None:
    """Trainer on make_mesh() over four chips against the same Trainer on
    one, same global batch and seed."""
    import jax
    import numpy as np

    from distributed_pytorch_tpu import make_mesh
    from distributed_pytorch_tpu.parallel.sharding import put_global_batch

    epochs = 2
    mark = counter.mark()
    single, rec1 = make_trainer(
        sz, mesh=None, snapshot_path=None, epochs_to_save=0)
    single.train(epochs)
    del single
    mesh = make_mesh()
    n = mesh.devices.size
    multi, rec4 = make_trainer(
        sz, mesh=mesh, snapshot_path=None, epochs_to_save=0)

    # Where things live, read off the arrays.
    leaves = jax.tree_util.tree_leaves(
        (multi.state.params, multi.state.opt_state))
    for leaf in leaves:
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n
              and all(s.data.shape == leaf.shape for s in shards),
              f"state leaf {leaf.shape} is not replicated on {n} devices")
    xs, ys = next(iter(multi.train_data))
    bx, _ = put_global_batch(mesh, (xs, ys))
    check(len({s.device for s in bx.addressable_shards}) == n
          and all(s.data.shape[0] == sz.batch // n
                  for s in bx.addressable_shards),
          "batch is not split over the devices")
    hlo = multi.train_step.lower(
        multi.state, put_global_batch(mesh, (xs, ys))).compile().as_text()
    check("all-reduce" in hlo, "no all-reduce in the compiled DP step")
    say(f"dp: {len(leaves)} state leaves replicated on {n} distinct devices; "
        f"batch {bx.shape} split {sz.batch // n} a device; all-reduce in "
        f"the compiled step")

    multi.train(epochs)
    l1, l4 = step_losses(rec1), step_losses(rec4)
    rel = [abs(a - b) / abs(a) for a, b in zip(l1, l4)]
    say(f"dp: losses 1 chip {[round(l, 4) for l in l1]} | {n} chips "
        f"{[round(l, 4) for l in l4]} | max rel diff {max(rel):.2e} "
        f"(tol {DP_LOSS_RTOL}) | {counter.since(mark)} | peak {peak_bytes()}")
    check(len(l1) == len(l4) == 2 * epochs, "steps missing")
    check(all(np.isfinite(l) for l in l1 + l4), "non-finite loss")
    check(max(rel) <= DP_LOSS_RTOL, "DP losses diverge from one chip")


def phase_tp_engine(sz: Sizes, counter: CompileCounter, rehearse: bool,
                    seed: int) -> None:
    """Engine on make_serving_mesh(model=4), kernel on, against the
    one-chip engine, on logits."""
    import jax
    from jax.sharding import NamedSharding

    from distributed_pytorch_tpu.serving.mesh import (
        KV_POOL_SPEC,
        KV_SCALE_SPEC,
        make_serving_mesh,
        serving_param_shardings,
    )

    kernel = "interpret" if rehearse else "auto"
    model, params = make_lm(sz, seed)
    warm, prompts = make_prompts(sz, seed + 1), make_prompts(sz, seed + 2)
    common = dict(counter=counter, warm=warm, prompts=prompts,
                  rehearse=rehearse, want_kernel=True, paged_kernel=kernel)
    one, tok_1 = run_engine("1 chip", sz, model, params, **common)
    mesh = make_serving_mesh(model=4)
    tp, tok_4 = run_engine("tp4", sz, model, params, mesh=mesh, **common)

    for leaf in jax.tree_util.tree_leaves(tp.cache):
        spec = KV_POOL_SPEC if leaf.ndim == 4 else KV_SCALE_SPEC
        shards = leaf.addressable_shards
        check(leaf.sharding.is_equivalent_to(
                  NamedSharding(mesh, spec), leaf.ndim)
              and len({s.device for s in shards}) == 4
              and all(s.data.shape[2] == leaf.shape[2] // 4 for s in shards),
              f"pool {leaf.shape} is not split as {spec}")
    want = serving_param_shardings(mesh, params)
    split = 0
    for leaf, sh in zip(jax.tree_util.tree_leaves(tp.params),
                        jax.tree_util.tree_leaves(want)):
        check(leaf.sharding.is_equivalent_to(sh, leaf.ndim),
              f"param {leaf.shape} is not placed as SERVING_PARAM_RULES say")
        split += leaf.addressable_shards[0].data.size < leaf.size
    check(split > 0, "no parameter is actually split")
    say(f"tp: {split} parameter arrays split over 'model', the rest "
        f"replicated, as SERVING_PARAM_RULES say; every pool split on its "
        f"KV-head axis over 4 distinct devices")

    rows = min(sz.slots, len(prompts))
    streams = [p + g for p, g in zip(prompts, tok_1)][:rows]
    lg_1 = score(one, streams)
    lg_4 = jax.device_put(score(tp, streams), jax.devices()[0])
    compare_logits("tp4 vs 1 chip", lg_4, lg_1, streams, LOGIT_TOL)
    check_greedy("1 chip", lg_1, prompts[:rows], tok_1[:rows], tok_1[:rows])
    check_greedy("tp4", lg_4, prompts[:rows], tok_4[:rows], tok_1[:rows])
    one.close()
    tp.close()


# ---------------------------------------------------------------------- main


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run the four-chip paths only")
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU rehearsal at a tiny size (needs "
                        "JAX_PLATFORMS=cpu); never prints the TPU result")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights and prompts")
    args = parser.parse_args()
    sz = TINY if args.rehearse else FULL
    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if args.rehearse and platforms != "cpu":
        say("chip_smoke: --rehearse runs on the CPU only; set "
            "JAX_PLATFORMS=cpu")
        return 1
    if not args.rehearse and platforms == "cpu":
        say("chip_smoke: JAX_PLATFORMS=cpu — no accelerator to run on "
            "(the CPU rehearsal is --rehearse)")
        return 1

    say("chip_smoke: environment " + json.dumps({
        k: v for k, v in sorted(os.environ.items())
        if k.startswith(("TPU_", "JAX_", "XLA_", "CLOUD_TPU", "FLASH_"))
        or k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID")
    }))
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 1:
            phase_launcher(args.rehearse, workdir)

        from distributed_pytorch_tpu.parallel.bootstrap import (
            setup_distributed,
        )
        from distributed_pytorch_tpu.utils.platform import (
            enable_compile_cache,
        )

        cache_dir = enable_compile_cache()
        # The single-host case must be a no-op, whatever TPU_* the machine
        # exports; were it not, this is where the smoke would hang.
        setup_distributed()

        import jax

        counter = CompileCounter()
        dev = jax.devices()[0]
        stamp = {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}
        say(f"chip_smoke: device {json.dumps(stamp)}; processes "
            f"{jax.process_count()}; jax {jax.__version__}; compile cache "
            f"{cache_dir}")
        if not args.rehearse and dev.platform != "tpu":
            say(f"chip_smoke: platform is {dev.platform!r}, not 'tpu'")
            return 1
        if stamp["count"] < args.chips:
            say(f"chip_smoke: --chips {args.chips} but JAX sees "
                f"{stamp['count']} device(s)")
            return 1

        if args.chips == 1:
            phase_kernels(sz, args.rehearse)
            phase_trainer(sz, counter, workdir)
            phase_engine(sz, counter, args.rehearse, args.seed)
        else:
            # One chip is known to hold ResNet-50 at batch 32: the same
            # global batch of 128 is 32 a chip on four.
            phase_dp_trainer(sz, counter)
            phase_tp_engine(sz, counter, args.rehearse, args.seed)

    if args.rehearse:
        say(json.dumps({"ok": False, "rehearsal": "passed", "device": stamp}))
    else:
        say(json.dumps({"ok": True, "device": stamp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

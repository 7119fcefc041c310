"""Driver for configurations of kind ``serve``: a closed loop of clients over a
replay deck, through the program's normal ``InferenceEngine``.

The traffic file fixes everything about the work: the deck of (prompt length,
output length) pairs in order, the number of clients, the warm-up requests.
``--seed`` decides the weights and the token ids, nothing else. Each client
submits the next deck entry the moment its previous request finishes. The
window opens when the first client draws from the deck (the rest are still
finishing their staggered warm-up requests, so clients enter one by one) and
closes ``--seconds`` later, on the clock; requests in flight then are not
counted. See ``benchmarks/README.md`` for the counting rules.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from harness import stats
from harness.spans import Spans
from harness.trace import Profiler, load_xplane, reduce_trace

SPAN_NAMES = ("engine.step", "client.poll", "client.submit")
TRACE_SECONDS = 6.0  # of the window's end that a traced run traces


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> List[int]:
    """Token ids of the ``index``-th request of a run: distinct for every
    request, so no prompt shares a prefix with another."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, index])
    return rng.integers(1, vocab, size=length).tolist()


def build_program(cfg: dict, weights: dict):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], dtype=jnp.dtype(cfg["torch_dtype"]),
        n_kv_heads=cfg["num_key_value_heads"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
    )
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"], "bias": weights["lnf_b"]},
    }
    for i, w in enumerate(weights["layers"]):
        params[f"block_{i}"] = {
            "ln_attn": {"scale": w["ln1_g"], "bias": w["ln1_b"]},
            "attention": {
                "query": {"kernel": w["wq"], "bias": w["bq"]},
                "key": {"kernel": w["wk"], "bias": w["bk"]},
                "value": {"kernel": w["wv"], "bias": w["bv"]},
                "out": {"kernel": w["wo"], "bias": w["bo"]},
            },
            "ln_mlp": {"scale": w["ln2_g"], "bias": w["ln2_b"]},
            "mlp": {
                "up": {"kernel": w["w_up"], "bias": w["b_up"]},
                "down": {"kernel": w["w_down"], "bias": w["b_down"]},
            },
        }
    return model, params


def build_engine(cfg: dict, model, params, tracer=None):
    from distributed_pytorch_tpu.serving import InferenceEngine

    opts = dict(cfg["assumed"]["engine"])
    if cfg["sliding_window"] and opts["max_seq_len"] > cfg["sliding_window"]:
        raise ValueError(
            "max_seq_len above sliding_window: the engine cannot window yet")
    return InferenceEngine(model, params, tracer=tracer, **opts)


@dataclasses.dataclass
class Served:
    """One request as the harness saw it."""

    index: int  # position in the run's submission order
    deck_index: int  # -1 for a warm-up request
    prompt: List[int]
    want: int
    submit_t: float
    token_t: List[float] = dataclasses.field(default_factory=list)
    generated: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    failed: Optional[str] = None

    @property
    def ttft_ms(self) -> float:
        return (self.token_t[0] - self.submit_t) * 1e3

    @property
    def tpot_ms(self) -> float:
        return (self.token_t[-1] - self.token_t[0]) * 1e3 / (len(self.token_t) - 1)


class ClosedLoop:
    """``clients`` callers over one engine; each keeps one request open."""

    def __init__(self, engine, cfg: dict, traffic: dict, seed: int, spans: Spans):
        from distributed_pytorch_tpu.serving import SamplingParams

        self._params = SamplingParams
        self.engine, self.cfg, self.traffic = engine, cfg, traffic
        self.seed, self.spans = seed, spans
        self.deck = [tuple(e) for e in traffic["deck"]]
        self.open: Dict[int, Served] = {}  # engine request id -> request
        self.done: List[Served] = []
        self.submitted = 0
        self.deck_cursor = 0
        self.t_open: Optional[float] = None

    def submit(self, deck_index: int, prompt_len: int, want: int) -> None:
        prompt = prompt_tokens(
            self.seed, self.submitted, prompt_len, self.cfg["vocab_size"])
        now = time.perf_counter()
        rid = self.engine.submit(prompt, self._params(max_new_tokens=want))
        self.open[rid] = Served(self.submitted, deck_index, prompt, want, now)
        self.submitted += 1

    def submit_next(self) -> None:
        if self.t_open is None:
            self.t_open = time.perf_counter()
            self.spans.recording = True
        k = self.deck_cursor
        self.deck_cursor += 1
        self.submit(k, *self.deck[k % len(self.deck)])

    def step(self) -> None:
        """One engine step, then what the clients see of it."""
        with self.spans.span("engine.step"):
            finished = self.engine.step()
        now = time.perf_counter()
        with self.spans.span("client.poll"):
            for rid, req in self.open.items():
                status = self.engine.poll(rid)
                fresh = status.generated[len(req.generated):]
                req.generated.extend(fresh)
                req.token_t.extend([now] * len(fresh))
                if status.finished:
                    req.finished = True
                    if status.state != "finished" or len(req.generated) != req.want:
                        req.failed = (
                            f"ended {status.state} with "
                            f"{len(req.generated)}/{req.want} tokens")
        if set(finished) - set(self.open):
            raise RuntimeError(f"engine finished unknown requests {finished}")
        with self.spans.span("client.submit"):
            for rid in [r for r, q in self.open.items() if q.finished]:
                self.done.append(self.open.pop(rid))
                self.submit_next()


def run_requests(engine, loop: ClosedLoop, requests) -> None:
    """Submit ``requests`` ((prompt, output) pairs) and run them to the end,
    outside any window: the compile warm-up."""
    for prompt_len, want in requests:
        loop.submit(-1, prompt_len, want)
    engine.run()
    for rid in list(loop.open):
        status = engine.poll(rid)
        if not status.finished or len(status.generated) != loop.open[rid].want:
            raise RuntimeError(f"warm-up request {rid} ended {status.state}")
        del loop.open[rid]


def counted(loop: ClosedLoop, t_close: float) -> List[Served]:
    """The requests a cell's medians are taken over: those of the deck
    passes of which every request finished inside the window, so that the
    clock decides how many times the deck's multiset of lengths is counted
    and never which lengths."""
    n = len(loop.deck)
    by_pass: Dict[int, List[Served]] = {}
    for r in loop.done:
        if r.deck_index >= 0 and r.token_t and r.token_t[-1] <= t_close:
            by_pass.setdefault(r.deck_index // n, []).append(r)
    return [r for p, rows in sorted(by_pass.items()) if len(rows) == n
            for r in rows]


def reference_logits(cell, weights, r: Served, sample, **kw):
    """The reference's logits at the positions that predict ``r``'s served
    tokens, padded so that one compiled program serves the whole sample."""
    pad_to = cell.traffic["check"]["pad_tokens_to"]
    if len(r.prompt) + len(r.generated) > pad_to:
        raise ValueError(f"request {r.index} is longer than the pad {pad_to}")
    rows = [len(r.prompt) - 1 + i for i in range(len(r.generated))]
    return cell.reference.logits_at(
        cell.config, weights, r.prompt + r.generated, rows,
        pad_tokens_to=pad_to, pad_rows_to=max(q.want for q in sample), **kw)


def check_against_reference(cell, weights, sample: List[Served]) -> dict:
    """How far each served (greedy) token's reference logit lies below the
    reference's best, over every served token of ``sample``: the widest such
    gap, and the mean."""
    worst, total, compared, flips = 0.0, 0.0, 0, 0
    for r in sample:
        n = len(r.generated)
        logits = np.asarray(reference_logits(cell, weights, r, sample))
        gaps = logits.max(axis=-1) - logits[np.arange(n), r.generated]
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        flips += int((gaps > 0).sum())
        compared += n
    return {"logit_gap": worst, "mean_gap": total / compared, "flips": flips,
            "tokens": compared, "requests": len(sample)}


def pick_sample(rows: List[Served], seed: int, n: int) -> List[Served]:
    """``n`` of the counted requests: the longest, and the rest drawn from
    the seed."""
    if not rows:
        return []
    longest = max(rows, key=lambda r: (len(r.prompt) + r.want, -r.index))
    rest = [r for r in rows if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    picks = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(picks)]


def run(cell):
    """Run one serving cell once; returns the result dict for ``run.py``."""
    import jax

    from distributed_pytorch_tpu.obs.tracer import Tracer
    from distributed_pytorch_tpu.obs.xla import RecompileSentinel

    cfg, traffic = cell.config, cell.traffic
    spans = Spans()
    weights = cell.reference.make_weights(cfg, cell.seed)
    model, params = build_program(cfg, weights)
    tracer = Tracer() if cell.trace else None
    engine = cell.hooks.get("build_engine", build_engine)(
        cfg, model, params, tracer)
    loop = ClosedLoop(engine, cfg, traffic, cell.seed, spans)
    counters: Dict[str, list] = {"plans": [], "pages": []}
    if cell.trace:
        schedule = engine.scheduler.schedule

        def recording_schedule():
            before = {
                slot: req.len_cached
                for slot, req in enumerate(engine.scheduler.slots) if req}
            plan = schedule()
            row = dict(
                decode_rows=len(plan.decode_slots),
                decode_context=sum(before.get(s, 0) for s in plan.decode_slots),
                prefill_tokens=0, prefill_context=0.0, prefill_keys=0)
            for slot, chunk in plan.prefill:
                start = before.get(slot, 0)  # 0: admitted by this schedule
                row["prefill_tokens"] += chunk
                row["prefill_context"] += chunk * (start + (chunk + 1) / 2)
                row["prefill_keys"] += start
                before[slot] = start + chunk
            counters["plans"].append(row)
            return plan

        engine.scheduler.schedule = recording_schedule

    # Compile warm-up: every program the deck can reach, outside the window.
    run_requests(engine, loop, traffic["compile_warm"])
    cell.say(f"compile warm-up done at {time.perf_counter() - cell.t_start:.1f}s")
    sentinel = RecompileSentinel()
    # Staggered warm-up: each client's first request is short and of its own
    # length, so clients reach the deck one by one.
    for prompt_len, want in traffic["stagger_warm"]:
        loop.submit(-1, prompt_len, want)
    if len(loop.open) != traffic["clients"]:
        raise ValueError("stagger_warm must have one request per client")
    while loop.t_open is None:
        loop.step()
    t_open = loop.t_open
    sentinel.arm()
    counters["plans"].clear()
    preempt0 = engine.scheduler.preemptions
    if tracer is not None:
        tracer.events.clear()
    setup_s = t_open - cell.t_start
    t_close = t_open + cell.seconds

    # A traced run traces the LAST seconds of the window, so that writing the
    # trace out (which takes a while) happens after the window has closed.
    profiler = Profiler(cell.scratch("trace")) if cell.trace else None
    trace_for = min(cell.seconds * 0.5, TRACE_SECONDS)
    settle_s = 1.0  # the profiler's own start-up, kept out of the traced window
    step_rows = []
    started = False
    while time.perf_counter() < t_close:
        now = time.perf_counter()
        if profiler is not None and not started and now >= t_close - trace_for - settle_s:
            profiler.start()
            started = True
        elif started and not profiler.window_open and now >= t_close - trace_for:
            profiler.open_window()
            spans.annotate = True
        t0 = time.perf_counter()
        loop.step()
        counters["pages"].append(engine.allocator.counters()["pages_referenced"])
        if cell.trace:
            step_rows.append((t0, time.perf_counter()))
    window_s = time.perf_counter() - t_open
    if profiler is not None:
        jax.block_until_ready(engine.cache)
        xplane_path = profiler.stop()
        spans.annotate = False
    spans.recording = False
    sentinel.disarm()
    # The allocator's peak is what the engine reserves: the pool is sized to
    # what the chip has left, whatever the traffic holds of it. Say both.
    device = cell.stamp()
    nbytes = lambda tree: sum(  # noqa: E731
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))
    pool, num_pages = nbytes(engine.cache), cfg["assumed"]["engine"]["num_pages"]
    device["memory_weights_bytes"] = nbytes(weights)
    device["memory_kv_pool_reserved_bytes"] = pool
    device["memory_kv_pool_used_peak_bytes"] = (
        pool * max(counters["pages"]) // num_pages)
    cell.say(
        f"memory: allocator peak {device['memory_peak_bytes']} B, of which "
        f"weights {device['memory_weights_bytes']} B and the KV pool "
        f"{pool} B reserved; requests held at most {max(counters['pages'])} "
        f"of its {num_pages} pages "
        f"({device['memory_kv_pool_used_peak_bytes']} B)")

    rows = counted(loop, t_close)
    deck_reqs = [r for r in loop.done if r.deck_index >= 0] + [
        r for r in loop.open.values() if r.deck_index >= 0]
    failed = [r for r in deck_reqs if r.failed]
    rows = [r for r in rows if not r.failed]
    note = ""
    if not rows:
        # Nothing to take a median over: the run's deck requests all failed.
        failed = [r for r in loop.done if r.deck_index >= 0]
        note = "no counted request (no whole pass of the deck fitted?)"
    gaps = [
        (b - a) * 1e3
        for r in deck_reqs
        for a, b in zip(r.token_t, r.token_t[1:]) if b <= t_close
    ]
    cell.say(
        f"window {window_s:.2f}s: {len(spans.durations('engine.step'))} steps, "
        f"{sum(r.deck_index >= 0 for r in loop.done)} deck requests finished, "
        f"{len(rows)} counted (whole passes of {len(loop.deck)}), "
        f"{len(gaps)} token gaps, "
        f"{len(failed)} failed; compiles in window {sentinel.count}. {note}")

    # The program's pools go before the reference runs, so that the peak
    # above stays the program's and the reference fits.
    engine.close()
    for leaf in jax.tree_util.tree_leaves(engine.cache):
        leaf.delete()

    values: Dict[str, float] = {}
    if rows:
        values["ttft_ms_p50"] = stats.median([r.ttft_ms for r in rows])
        values["tpot_ms_p50"] = stats.median([r.tpot_ms for r in rows])
    values["setup_s"] = setup_s

    t_ref = time.perf_counter()
    sample = pick_sample(rows, cell.seed, traffic["check"]["requests"])
    limits = traffic["check"]
    if sample:
        check = check_against_reference(cell, weights, sample)
        correct = (check["logit_gap"] <= limits["logit_gap_limit"]
                   and check["mean_gap"] <= limits["mean_gap_limit"])
        cell.say(
            f"correct: served tokens' reference logit below the reference's "
            f"best: widest gap {check['logit_gap']:.6f} (limit "
            f"{limits['logit_gap_limit']}), mean gap {check['mean_gap']:.6f} "
            f"(limit {limits['mean_gap_limit']}); {check['flips']} of "
            f"{check['tokens']} tokens of {check['requests']} requests are "
            f"not the reference's first; reference took "
            f"{time.perf_counter() - t_ref:.1f}s")
        if "after_check" in cell.hooks:  # control.py and the tests
            cell.hooks["after_check"](cell, weights, sample, check)
    else:
        correct = False
        cell.say("correct: nothing finished, nothing to compare")

    context = None
    if cell.trace:
        summary = reduce_trace(
            load_xplane(xplane_path), SPAN_NAMES, cell.allow_cpu)
        context = dict(
            trace=summary, spans=spans, counters=counters, cfg=cfg,
            traffic=traffic, window_s=window_s, chips=cell.chips,
            device_kind=device["kind"], compiles=sentinel.count,
            engine_events=tracer.events, step_rows=step_rows,
            preemptions=engine.scheduler.preemptions - preempt0,
            num_pages=num_pages,
            traced=(profiler.t0, profiler.t1), requests=deck_reqs,
            gaps_ms=gaps,
        )
    return dict(
        correct=bool(correct), attempted=len(rows) + len(failed),
        failed=len(failed), values=values, device=device, context=context,
    )

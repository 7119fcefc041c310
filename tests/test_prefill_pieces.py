"""How a prompt is cut into prefill pieces (``serving/scheduler.py``) and the
fixed set of programs the engine runs them in (``serving/engine.py``).

A piece is what is left of the prompt, of ``max_prefill_chunk`` and of the
step's budget; only a request's last piece is ragged, a budget sliver under
a granule waits, and a budget that never reaches a granule starves nobody.
The engine pads a piece to the next whole number of granules, owns one
program a width, and has them all once the first has run.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.models.mamba import STATE_KEYS
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams
from distributed_pytorch_tpu.serving.kv_cache import PagedBlockAllocator
from distributed_pytorch_tpu.serving.scheduler import (
    PREFILL_GRANULE,
    Request,
    RequestState,
    Scheduler,
)

# ------------------------------------------------------------- the scheduler


def make_scheduler(cap, budget, slots=4, max_seq_len=4096, page=16):
    pages_per_seq = max_seq_len // page
    return Scheduler(
        PagedBlockAllocator(slots * pages_per_seq + 1), max_slots=slots,
        page_size=page, pages_per_seq=pages_per_seq, token_budget=budget,
        max_prefill_chunk=cap, debug=True,
    )


def drive(sched, max_steps=10_000):
    """Run the scheduler dry (every decode emits token 1). Returns the
    pieces planned, a list a step: ``[(req_id, tokens), ...]``."""
    steps = []
    for _ in range(max_steps):
        if not sched.has_work:
            return steps
        plan = sched.schedule()
        steps.append([(sched.slots[s].req_id, n) for s, n in plan.prefill])
        for slot, n in plan.prefill:
            sched.note_prefilled(slot, n)
        for slot in plan.decode_slots:
            done = sched.note_decoded(slot, token=1, now=0.0)
            if done is not None:
                sched.retire(done, now=0.0)
    raise AssertionError("the scheduler never ran dry: livelock")


def request(req_id, prompt_len, new=1):
    return Request(
        req_id=req_id, prompt=[1 + i % 50 for i in range(prompt_len)],
        params=SamplingParams(max_new_tokens=new),
    )


def pieces_of(steps, req_id):
    return [n for step in steps for r, n in step if r == req_id]


# (prompt tokens, cap, budget) -> the steps' pieces. The prompt's last token
# goes through the decode step, so prompt - 1 tokens are prefilled. Where the
# ladder cut powers of two (the old rule, case for case: 200 = 128 + 64 + 8;
# 1,099 under 540 a step = 512 + 16 + 8 + 4, ...) a piece is now whatever the
# cap and the budget leave, cut down to whole granules unless it is the last.
ONE_REQUEST = [
    # the generation deck's median prompt: one piece where the ladder had 3
    (201, 512, 544, [[200]]),
    # the completion deck's: 512, 512, 75 over three steps (the ladder: 11)
    (1100, 512, 540, [[512], [512], [75]]),
    # a budget that holds the whole prompt: the cap still cuts it
    (1100, 512, 4096, [[512, 512, 75]]),
    # a prompt of exactly the cap, and one token more
    (513, 512, 544, [[512]]),
    (514, 512, 1024, [[512, 1]]),
    # granule, granule + 1, granule - 1
    (65, 512, 544, [[64]]),
    (66, 512, 544, [[65]]),
    (64, 512, 544, [[63]]),
    # one token to prefill, and none (the prompt's one token is decoded)
    (2, 512, 544, [[1]]),
    (1, 512, 544, []),
    # a cap under the granule is the granule: pieces of 8, the last ragged
    (30, 8, 16, [[8, 8], [8, 5]]),
    # the engine's defaults (cap 32, budget 64): granule 32
    (101, 32, 64, [[32, 32], [32, 4]]),
    # the budget cuts a piece that is not the last down to whole granules:
    # 200 left of the step -> 192, the sliver of 8 waits
    (400, 512, 200, [[192], [192], [15]]),
    # a budget of less than a granule, for ever: the sliver is taken, ragged
    (100, 512, 40, [[40], [40], [19]]),
    (20, 8, 3, [[3]] * 6 + [[1]]),
]


@pytest.mark.parametrize("prompt, cap, budget, want", ONE_REQUEST)
def test_pieces_of_one_prompt(prompt, cap, budget, want):
    sched = make_scheduler(cap, budget)
    sched.add(request(0, prompt))
    steps = [s for s in drive(sched) if s]
    assert [[n for _r, n in step] for step in steps] == want
    assert sum(pieces_of(steps, 0)) == prompt - 1


def test_a_sliver_under_a_granule_waits_for_the_next_step():
    """540 of budget: the oldest request's piece takes 512, and the 28 left
    buy the next request nothing (a 28-token program reads every weight a
    512-token one reads), unless 28 finish its prompt."""
    sched = make_scheduler(512, 540)
    sched.add(request(0, 1100))
    sched.add(request(1, 300))
    sched.add(request(2, 21))  # 20 to prefill: its last piece fits the sliver
    steps = drive(sched)
    assert steps[0] == [(0, 512), (2, 20)]
    assert steps[1] == [(0, 512)]
    assert steps[2] == [(0, 75), (1, 299)]
    # the budget was charged the tokens, not the widths: 75 + 299 <= 540
    assert all(sum(n for _r, n in step) <= 540 for step in steps)


def test_decode_rows_that_leave_under_a_granule_starve_nobody():
    """The engine's defaults at a full batch: 40 decoding rows leave 24 of a
    budget of 64, under the granule of 32, step after step. The oldest
    prefilling request takes them, ragged; the one behind it waits its
    turn; both finish."""
    sched = make_scheduler(32, 64, slots=48, max_seq_len=256)
    for i in range(40):
        sched.add(request(i, 1, new=100))
    for _ in range(2):  # the 40 rows are admitted and decoding
        plan = sched.schedule()
        for slot in plan.decode_slots:
            sched.note_decoded(slot, token=1, now=0.0)
    sched.add(request(100, 60, new=1))
    sched.add(request(101, 30, new=1))
    steps = []
    for _ in range(7):
        plan = sched.schedule()
        steps.append((
            len(plan.decode_slots),
            [(sched.slots[s].req_id, n) for s, n in plan.prefill],
        ))
        for slot, n in plan.prefill:
            sched.note_prefilled(slot, n)
        for slot in plan.decode_slots:
            done = sched.note_decoded(slot, token=1, now=0.0)
            if done is not None:
                sched.retire(done, now=0.0)
    assert steps == [
        (40, [(100, 24)]),
        (40, [(100, 24)]),
        # its last piece; the 13 left are a sliver to the next request (29
        # to go), and this step already prefills: they wait
        (40, [(100, 11)]),
        (41, [(101, 23)]),  # request 100 decodes its one token
        (40, [(101, 6)]),
        (41, []),
        (40, []),
    ]


@pytest.mark.parametrize("seed", range(8))
def test_only_a_requests_last_piece_is_ragged(seed):
    """Random prompts, caps and budgets: every request is prefilled whole
    and in order; a piece is at most the cap; a step is charged at most its
    budget; a piece that is not its request's last is whole granules,
    unless it is the only prefill of its step (the sliver rule)."""
    rng = random.Random(seed)
    cap = rng.choice([8, 32, 64, 256, 512])
    budget = rng.choice([3, 16, 64, 100, 544, 640])
    granule = min(PREFILL_GRANULE, cap)
    sched = make_scheduler(cap, budget, slots=4, max_seq_len=2048)
    assert sched.prefill_granule == granule
    prompts = {i: rng.randint(1, 1500) for i in range(10)}
    for i, n in prompts.items():
        sched.add(request(i, n, new=rng.randint(1, 4)))
    steps = drive(sched)
    for i, n in prompts.items():
        assert sum(pieces_of(steps, i)) == n - 1
    for step in steps:
        assert sum(n for _r, n in step) <= budget
        for r, n in step:
            assert 1 <= n <= cap
    for i in prompts:
        mine = [(step, n) for step in steps for r, n in step if r == i]
        for step, n in mine[:-1]:
            assert n % granule == 0 or step == [(i, n)], (i, n, step)


def test_the_cap_is_still_a_power_of_two():
    for cap in (0, 3, 48, 100):
        with pytest.raises(ValueError, match="power of two"):
            make_scheduler(cap, 64)


# ---------------------------------------------------------------- the engine


def toy_lm(**kw):
    kw.setdefault("dtype", jnp.float32)
    model = TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, **kw)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def engine_of(kind, **kw):
    """A toy engine with FOUR prefill widths (cap 256: 64, 128, 192, 256):
    plain, speculative (a draft pool beside the target's) or recurrent (S6
    layers beside attention)."""
    kw = dict(dict(
        max_slots=2, max_seq_len=1024, page_size=16, max_prefill_chunk=256,
        token_budget=300, xla_ledger=True,
    ), **kw)
    if kind == "recurrent":
        model, params = toy_lm(
            layer_types=("mamba", "attention"), mamba_dt_rank=4,
            mamba_d_state=4, norm="rmsnorm", rope=False)
        return InferenceEngine(model, params, prefix_cache=False, **kw)
    model, params = toy_lm()
    if kind == "speculative":
        draft = TransformerLM(
            vocab_size=64, d_model=16, n_layers=1, n_heads=2, d_ff=32,
            dtype=jnp.float32)
        draft_params = draft.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        return InferenceEngine(
            model, params, draft_model=draft, draft_params=draft_params,
            gamma=2, **kw)
    return InferenceEngine(model, params, **kw)


def serve(eng, prompt_len, new=2, seed=0):
    prompt = np.random.default_rng(seed + prompt_len).integers(
        1, 64, size=prompt_len).tolist()
    rid = eng.submit(prompt, SamplingParams(max_new_tokens=new))
    eng.run()
    assert eng.poll(rid).finished
    return rid


@pytest.mark.parametrize("kind", ["plain", "speculative", "recurrent"])
def test_the_set_of_programs_is_whole_after_the_first(kind):
    """One request of any length, then the sentinel armed: prompts whose
    pieces cover every width, both sides of every boundary between widths,
    and more than two caps compile nothing."""
    eng = engine_of(kind)
    serve(eng, 10)
    assert sorted(eng._prefill_programs) == [64, 128, 192, 256]
    sentinel = eng.arm_recompile_sentinel()
    before = eng.stats()["prefill_programs"]
    # prefilled are prompt - 1 tokens: 1, 63, 64, 65, ... and 600 (256 +
    # 256 + 88 over three steps of budget 300)
    for tokens in (1, 63, 64, 65, 127, 128, 129, 191, 192, 193, 255, 256,
                   257, 600):
        serve(eng, tokens + 1)
    assert sentinel.count == 0, sentinel.trips
    stats = eng.stats()
    assert stats["prefill_programs"] - before == 12 + 2 + 3
    assert stats["prefill_tokens"] < stats["prefill_width"]
    assert stats["prefill_width"] % 64 == 0
    eng.close()


@pytest.mark.parametrize("kind", ["plain", "speculative", "recurrent"])
def test_the_first_pass_leaves_the_engine_as_it_found_it(kind):
    """Building the set runs every program once on a null table with valid
    length 0 and no slot: no page but the null one, no state and no counter
    may move."""
    eng = engine_of(kind, xla_ledger=False)
    # pools and states that are not all zeros, so that a write would show
    for name in eng.pools.names:
        eng.pools[name] = jax.tree_util.tree_map(
            lambda x: jnp.full(x.shape, 3, x.dtype), eng.pools[name])

    def counters():
        return {k: v for k, v in eng.stats().items() if k != "elapsed_s"}

    stats = counters()
    assert len(eng._prefill_programs) == 4
    assert all((draft is not None) == (kind == "speculative")
               for _target, draft in eng._prefill_programs.values())
    assert counters() == stats
    assert eng.state_resets == 0 and eng.routing_counts == []
    for name in eng.pools.names:
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                eng.pools[name])[0]:
            leaf = np.asarray(leaf)
            kept = leaf if path[-1].key in STATE_KEYS else leaf[1:]
            assert (kept == 3).all(), (name, path)
    eng.close()


def test_a_piece_runs_in_the_next_width_and_says_so():
    """The ``prefill.chunk`` slice of a piece carries its tokens, its start
    and the width it was padded to; the step slice the step's sums."""
    from distributed_pytorch_tpu.obs.tracer import Tracer

    tracer = Tracer()
    eng = engine_of("plain", tracer=tracer, xla_ledger=False)
    serve(eng, 601)  # 600 to prefill: 256, then 256, then 88 (budget 300)
    chunks = [e["args"] for e in tracer.events
              if e["name"] == "prefill.chunk" and e.get("ph") == "X"]
    assert [(c["tokens"], c["start"], c["width"]) for c in chunks] == [
        (256, 0, 256), (256, 256, 256), (88, 512, 128)]
    steps = [e["args"] for e in tracer.events
             if e["name"] == "step" and e.get("ph") == "X"]
    assert [(s["prefill_programs"], s["prefill_tokens"], s["prefill_width"])
            for s in steps[:3]] == [(1, 256, 256), (1, 256, 256), (1, 88, 128)]
    assert eng.stats()["prefill_programs"] == 3
    assert eng.stats()["prefill_tokens"] == 600
    assert eng.stats()["prefill_width"] == 640
    eng.close()


def test_served_tokens_are_offline_generates_at_every_ragged_length():
    """float32 on the CPU: whatever a prompt's length leaves of padding, the
    engine's greedy tokens are ``generate()``'s."""
    from distributed_pytorch_tpu.generation import generate

    model, params = toy_lm()
    eng = InferenceEngine(
        model, params, max_slots=2, max_seq_len=256, page_size=16,
        max_prefill_chunk=64, token_budget=80,
    )
    for n in (2, 17, 64, 65, 66, 100, 130):
        prompt = np.random.default_rng(n).integers(1, 64, size=n).tolist()
        rid = eng.submit(prompt, SamplingParams(max_new_tokens=4))
        eng.run()
        want = np.asarray(generate(
            model, params, jnp.asarray([prompt], jnp.int32),
            max_new_tokens=4, temperature=0.0, rng=jax.random.PRNGKey(0),
        ))[0, n:].tolist()
        assert eng.poll(rid).generated == want, n
    assert eng.scheduler.slots == [None, None]
    assert all(r.state is RequestState.FINISHED for r in eng.requests.values())
    eng.close()

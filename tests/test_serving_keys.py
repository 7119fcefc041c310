"""Where the sampling keys are made: on the host as data, folded inside the
compiled programs.

A request's token i is drawn with ``fold_in(PRNGKey(seed), i)``. The engine
keeps ``PRNGKey(seed)`` as ``uint32[2]`` host data, stages it with the count
beside it, and the decode and speculative programs do the fold. These tests
hold the FORMULA (every stream is drawn again here, from the offline model's
logits, with plain ``jax.random`` calls), not only its independence of batch
composition, and hold the host to touching no device for a key. All on CPU.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.generation import fold_row_keys, host_prng_key
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.obs import Tracer
from distributed_pytorch_tpu.serving import (
    InferenceEngine,
    SamplingParams,
    make_serving_mesh,
    restore_engine,
    snapshot_engine,
)
from distributed_pytorch_tpu.serving.mods import Mods, ModState

VOCAB = 48
GAMMA = 3
PROMPTS = [[5, 7, 11, 2, 9, 3], [1, 4, 8], [2, 2, 3, 17, 40], [6, 1, 9, 9]]
# One greedy row beside three sampled ones, each its own seed and heat.
PARAMS = [
    SamplingParams(max_new_tokens=8, temperature=1.0, seed=42),
    SamplingParams(max_new_tokens=8, temperature=0.7, seed=2**31 - 1),
    SamplingParams(max_new_tokens=8),
    SamplingParams(max_new_tokens=8, temperature=1.3, seed=7),
]
GRAMMAR = "[5-40]+"
ENGINE_KW = dict(
    max_slots=4, max_seq_len=32, page_size=4, token_budget=16,
    max_prefill_chunk=8,
)


def _lm(d_model, n_layers, key):
    model = TransformerLM(
        vocab_size=VOCAB, d_model=d_model, n_layers=n_layers, n_heads=2,
        d_ff=2 * d_model, dtype=jnp.float32,
    )
    params = model.init(
        jax.random.PRNGKey(key), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def target():
    return _lm(16, 2, 0)


@pytest.fixture(scope="module")
def draft():
    return _lm(8, 1, 1)


_FORWARD = {}


def offline_logits(lm, seq):
    """The next token's logits after ``seq``: one whole forward, no cache
    (padded to one length so it compiles once; the model is causal)."""
    model, params = lm
    if id(model) not in _FORWARD:
        _FORWARD[id(model)] = jax.jit(
            lambda toks: model.apply({"params": params}, toks)[0]
        )
    toks = np.zeros((1, ENGINE_KW["max_seq_len"]), np.int32)
    toks[0, : len(seq)] = seq
    return _FORWARD[id(model)](toks)[len(seq) - 1]


_DRAWN = {}


def _drawn_once(draw, *args):
    """Each reference stream is drawn once a session: cases share them."""
    key = (draw, *(id(a[0]) if isinstance(a, tuple) else a for a in args))
    if key not in _DRAWN:
        _DRAWN[key] = draw(*args)
    return list(_DRAWN[key])


def drawn_stream(lm, prompt, sp, mods=None):
    return _drawn_once(_draw_stream, lm, sp, mods, *prompt)


def drawn_spec_stream(target, draft, prompt, sp):
    return _drawn_once(_draw_spec_stream, target, draft, sp, *prompt)


def _draw_stream(lm, sp, mods, *prompt):
    """The stream the plain engine owes: token i is the argmax (greedy) or
    ``categorical(fold_in(PRNGKey(seed), i), logits / temperature)``, the
    request's own mods added to the logits first."""
    base = jax.random.PRNGKey(sp.seed)
    state = ModState(mods, VOCAB) if mods is not None else None
    seq, out = list(prompt), []
    for i in range(sp.max_new_tokens):
        logits = offline_logits(lm, seq)
        if state is not None:
            logits = logits + state.bias_row()
        if sp.temperature > 0:
            tok = jax.random.categorical(
                jax.random.fold_in(base, i), logits / sp.temperature
            )
        else:
            tok = jnp.argmax(logits)
        out.append(int(tok))
        seq.append(int(tok))
        if state is not None and state.note_token(int(tok)):
            break
    return out


def _draw_spec_stream(target, draft, sp, *prompt):
    """The stream the speculative engine owes. A round that starts with n
    tokens generated has the key ``fold_in(PRNGKey(seed), n)``; its draft
    step i draws with that key folded with i, its acceptance uniforms with
    gamma, its residual draw with gamma + 1 (Leviathan et al.'s rule)."""
    base = jax.random.PRNGKey(sp.seed)
    temp = sp.temperature
    seq, out = list(prompt), []
    while len(out) < sp.max_new_tokens:
        key = jax.random.fold_in(base, len(out))
        props, q = [], []
        for i in range(GAMMA):
            q.append(jax.nn.softmax(offline_logits(draft, seq + props) / temp))
            props.append(int(jax.random.categorical(
                jax.random.fold_in(key, i), jnp.log(q[-1])
            )))
        p = [
            jax.nn.softmax(offline_logits(target, seq + props[:j]) / temp)
            for j in range(GAMMA)
        ]
        u = jax.random.uniform(jax.random.fold_in(key, GAMMA), (GAMMA,))
        n_acc = 0
        while n_acc < GAMMA and (
            u[n_acc] * q[n_acc][props[n_acc]] < p[n_acc][props[n_acc]]
        ):
            n_acc += 1
        emitted = props[:n_acc]
        if n_acc < GAMMA:
            residual = jnp.maximum(p[n_acc] - q[n_acc], 0.0)
            dist = jnp.where(jnp.sum(residual) > 0, residual, p[n_acc])
            emitted.append(int(jax.random.categorical(
                jax.random.fold_in(key, GAMMA + 1), jnp.log(dist)
            )))
        emitted = emitted[: sp.max_new_tokens - len(out)]
        out += emitted
        seq += emitted
    return out


def serve(eng, prompts=PROMPTS, params=PARAMS, mods=None):
    mods = mods or [None] * len(prompts)
    ids = [
        eng.submit(p, sp, mods=m) for p, sp, m in zip(prompts, params, mods)
    ]
    eng.run()
    return [eng.poll(rid).generated for rid in ids]


# ------------------------------------------------- (a) the formula, per mode


@pytest.mark.parametrize(
    "case",
    ["overlap", "sync", "preempted", "mods_group", "speculative", "mesh_1x1"],
)
def test_sampled_stream_is_the_one_drawn_offline(case, target, draft):
    model, params = target
    prompts, sps, mods = PROMPTS, PARAMS, None
    kw = dict(ENGINE_KW)
    if case == "sync":
        kw["overlap"] = False
    elif case == "preempted":
        # A pool too small for three sequences of 14: some are evicted and
        # resumed, and draw on with the count they had reached.
        prompts, sps = PROMPTS[:3], [PARAMS[0], PARAMS[1], PARAMS[3]]
        kw.update(max_slots=3, max_seq_len=16, page_size=2, num_pages=10,
                  token_budget=8, max_prefill_chunk=4)
    elif case == "mods_group":
        # Grammar rows dispatch as a synchronous group beside the async one.
        mods = [Mods(grammar=GRAMMAR), None, Mods(grammar=GRAMMAR), None]
    elif case == "speculative":
        kw.update(draft_model=draft[0], draft_params=draft[1], gamma=GAMMA)
    elif case == "mesh_1x1":
        kw["mesh"] = make_serving_mesh(1, 1)

    eng = InferenceEngine(model, params, **kw)
    got = serve(eng, prompts, sps, mods)
    if case == "preempted":
        assert eng.stats()["preemptions"] > 0, "pool sized to force it"
    if case == "speculative":
        want = [
            drawn_spec_stream(target, draft, p, sp) if sp.temperature > 0
            else drawn_stream(target, p, sp)
            for p, sp in zip(prompts, sps)
        ]
    else:
        want = [
            drawn_stream(target, p, sp, m)
            for p, sp, m in zip(prompts, sps, mods or [None] * len(prompts))
        ]
    assert got == want
    sampled = [g for g, sp in zip(got, sps) if sp.temperature > 0]
    greedy = [drawn_stream(target, p, SamplingParams(max_new_tokens=8))
              for p, sp in zip(prompts, sps) if sp.temperature > 0]
    assert sampled != greedy, "the sampled rows must not all read as greedy"


# ------------------------------------------- (b) no key work on the device


def _refuse_fold_in(*args, **kwargs):
    raise AssertionError("jax.random.fold_in called from Python")


@contextlib.contextmanager
def counting(monkeypatch, eng):
    """Count what ``_dispatch_decode`` makes on the device through the
    public constructors, and refuse every ``jax.random.fold_in`` called
    from Python (inside an already compiled program it is not called)."""
    made = {"array": 0, "other": 0}
    inside = [False]
    real_dispatch = eng._dispatch_decode

    def dispatch(*a, **k):
        inside[0] = True
        try:
            return real_dispatch(*a, **k)
        finally:
            inside[0] = False

    def count(name, real):
        def wrapped(*a, **k):
            if inside[0]:
                made[name] += 1
            return real(*a, **k)
        return wrapped

    with monkeypatch.context() as m:
        m.setattr(eng, "_dispatch_decode", dispatch)
        m.setattr(jnp, "array", count("array", jnp.array))
        for mod, name in [(jnp, "asarray"), (jnp, "zeros"), (jnp, "stack"),
                          (jax, "device_put")]:
            m.setattr(mod, name, count("other", getattr(mod, name)))
        m.setattr(jax.random, "fold_in", _refuse_fold_in)
        yield made


def test_steps_after_the_compile_fold_nothing_on_the_host(target, monkeypatch):
    eng = InferenceEngine(*target, **ENGINE_KW)
    long = [
        SamplingParams(max_new_tokens=12, temperature=sp.temperature or 0.9,
                       seed=sp.seed)
        for sp in PARAMS
    ]
    ids = [eng.submit(p, sp) for p, sp in zip(PROMPTS, long)]
    while not all(eng.poll(r).generated for r in ids):
        eng.step()          # prefill, and the decode program's one compile
    dispatches = 0
    with counting(monkeypatch, eng) as made:
        for _ in range(4):
            eng.step()
            dispatches += 1
    # tokens, use_prev, tables, lens, temps and the key operand: six copies
    # a launch, and not one device array besides.
    assert made == {"array": 6 * dispatches, "other": 0}
    eng.run()
    assert [eng.poll(r).generated for r in ids] == [
        drawn_stream(target, p, sp) for p, sp in zip(PROMPTS, long)
    ]


def test_speculative_rounds_fold_nothing_on_the_host(
    target, draft, monkeypatch
):
    eng = InferenceEngine(
        *target, draft_model=draft[0], draft_params=draft[1], gamma=GAMMA,
        **ENGINE_KW,
    )
    sp = SamplingParams(max_new_tokens=12, temperature=1.0, seed=42)
    rid = eng.submit(PROMPTS[0], sp)
    while not eng.poll(rid).generated:
        eng.step()
    with monkeypatch.context() as m:
        m.setattr(jax.random, "fold_in", _refuse_fold_in)
        eng.run()
    assert eng.poll(rid).generated == drawn_spec_stream(
        target, draft, PROMPTS[0], sp
    )


def test_a_refill_cannot_reach_a_dispatch_in_flight(target):
    """No dispatch waits for the device now, so a staging buffer can be
    refilled while the transfer that reads it is still queued (the CPU
    backend reads an aligned host array only when the device gets to it).
    Keep the device busy across every dispatch and scribble over the
    buffers the moment it returns: the streams must not notice."""
    eng = InferenceEngine(*target, **ENGINE_KW)
    busy = jax.jit(lambda x: jnp.sum(x @ x @ x @ x))
    block = jnp.ones((1024, 1024), jnp.float32)
    busy(block).block_until_ready()
    real_dispatch = eng._dispatch_decode

    def dispatch(*a, **k):
        held = busy(block)          # the device has work queued ahead
        nxt = real_dispatch(*a, **k)
        for name in ("tokens", "use_prev", "tables", "lens", "keys"):
            getattr(eng, f"_stage_{name}").fill(1)
        eng._stage_temps.fill(9.0)
        del held
        return nxt

    eng._dispatch_decode = dispatch
    assert serve(eng) == [
        drawn_stream(target, p, sp) for p, sp in zip(PROMPTS, PARAMS)
    ]


# --------------------------------------------- (c) one key slice a launch


@pytest.mark.parametrize("rows", [1, ENGINE_KW["max_slots"]])
def test_a_step_writes_one_key_slice(rows, target):
    tr = Tracer()
    eng = InferenceEngine(*target, tracer=tr, **ENGINE_KW)
    serve(eng, PROMPTS[:rows], PARAMS[:rows])
    steps = {}
    for e in tr.events:
        if e["ph"] == "X":
            steps.setdefault(e["args"]["step"], []).append(e)
    full = 0
    for step, slices in steps.items():
        (whole,) = [e for e in slices if e["name"] == "step"]
        keys = [e for e in slices if e["name"] == "dispatch.key"]
        assert len(keys) == (1 if whole["args"]["decode_rows"] else 0)
        for e in keys:
            assert e["args"]["step"] == step
            assert e["args"]["rows"] == whole["args"]["decode_rows"]
        full += whole["args"]["decode_rows"] == rows
    assert full > 0, f"no step ran {rows} decode rows"


# ------------------------------------------------- (d) the host key helper

# PRNGKey takes a Python int as an int64: 2**63 - 1 is the largest seed a
# SamplingParams can carry into it.
SEEDS = [0, 1, 2**31 - 1, 2**63 - 1]


@pytest.mark.parametrize("seed", SEEDS + [2**31, 2**32 + 5, -1])
def test_host_key_is_prngkey_word_for_word(seed):
    want = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
    got = host_prng_key(seed)
    assert isinstance(got, np.ndarray)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tolist() == want.tolist()


def test_host_key_refuses_what_prngkey_refuses():
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(2**63)
    with pytest.raises(OverflowError):
        host_prng_key(2**63)


def test_fold_row_keys_is_fold_in_row_by_row():
    seeds, counts = [0, 7, 2**31 - 1, 2**63 - 1], [0, 1, 4095, 2**32 - 1]
    staged = np.array(
        [[*host_prng_key(s), c] for s, c in zip(seeds, counts)], np.uint32
    )
    want = [
        np.asarray(jax.random.fold_in(jax.random.PRNGKey(s), c)).tolist()
        for s, c in zip(seeds, counts)
    ]
    assert np.asarray(jax.jit(fold_row_keys)(staged)).tolist() == want


def test_submit_keeps_the_key_on_the_host(target):
    eng = InferenceEngine(*target, **ENGINE_KW)
    rid = eng.submit(PROMPTS[0], PARAMS[1])
    key = eng._keys[rid]
    assert isinstance(key, np.ndarray) and not isinstance(key, jax.Array)
    assert key.tolist() == host_prng_key(PARAMS[1].seed).tolist()
    eng.run()
    assert rid not in eng._keys


# ------------------------------------------------------ (e) elastic restore


@pytest.mark.parametrize("overlap", [True, False])
def test_a_restored_request_continues_its_stream(overlap, target):
    kw = dict(ENGINE_KW, overlap=overlap)
    eng = InferenceEngine(*target, **kw)
    ids = [eng.submit(p, sp) for p, sp in zip(PROMPTS, PARAMS)]
    for _ in range(5):
        eng.step()
    assert any(eng.poll(r).generated for r in ids), "restore mid-stream"
    snap = snapshot_engine(eng)
    fresh = InferenceEngine(*target, **kw)
    restored = restore_engine(fresh, snap)
    assert all(
        isinstance(fresh._keys[r], np.ndarray) for r in restored
    )
    fresh.run()
    assert [fresh.poll(r).generated for r in ids] == [
        drawn_stream(target, p, sp) for p, sp in zip(PROMPTS, PARAMS)
    ]

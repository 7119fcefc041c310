"""Set-up's timeline: every compile a slice that names its program and says
what the persistent cache did, a backend's opening, the engine's and the
Trainer's start-up, all in the process's tracer and out of its ring's reach.
"""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_pytorch_tpu import ShardedLoader, Trainer
from distributed_pytorch_tpu.obs import xla
from distributed_pytorch_tpu.obs.tracer import (
    PROCESS_TRACER_EVENTS,
    SETUP_EVENTS_MAX,
    Tracer,
    process_start,
    process_tracer,
)
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def timeline():
    """The process tracer's set-up slices that begin from here on (wherever
    it keeps them: a worker that has compiled 4,096 programs before this
    test keeps the later ones in its ring)."""
    assert xla.install_dispatcher()
    tr = process_tracer()
    mark = time.perf_counter_ns()
    return lambda name="compile": [
        e for e in tr.setup_events + list(tr.events)
        if e.get("cat") == "setup" and e["name"] == name
        and e["args"]["perf_counter_ns"] >= mark]


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent cache of this test's own that takes every program."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {name: getattr(jax.config, name) for name in CACHE_OPTIONS}
    cc.reset_cache()
    for name, value in zip(CACHE_OPTIONS, (str(tmp_path), 0.0, 0)):
        jax.config.update(name, value)
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def parts_of(event):
    args = event["args"]
    return args["trace_s"] + args["lower_s"] + args["backend_s"]


def mine(events, fun_name):
    return [e for e in events if e["args"]["fun_name"] == fun_name]


def test_a_compile_is_one_slice_and_says_what_the_cache_did(
    timeline, cache_dir
):
    """Cold, the program misses the cache and is written to it; after
    ``jax.clear_caches()`` the same program is a hit with the time the load
    took. Either way ONE slice, named by JAX's own name of the program, on
    ``perf_counter`` like every slice, whose three parts lie inside it: what
    the slice holds beyond them is JAX's own Python between its parts (a
    millisecond or two a program on this machine), never more than the
    parts themselves here."""

    @jax.jit
    def steps_of_a_toy(x):
        return jnp.sin(x) @ x.T

    x = jnp.ones((8, 8))
    steps_of_a_toy(x).block_until_ready()
    (cold,) = mine(timeline(), "jit(steps_of_a_toy)")
    assert cold["ph"] == "X" and cold["args"]["perf_counter_ns"] > 0
    assert cold["args"]["cache"] == "miss" and cold["args"]["written"] is True
    assert "retrieval_s" not in cold["args"]
    assert all(cold["args"][k] > 0 for k in ("trace_s", "lower_s", "backend_s"))
    assert parts_of(cold) <= cold["dur"] / 1e6 + 1e-3
    assert cold["dur"] / 1e6 - parts_of(cold) < 0.05

    steps_of_a_toy(x)  # held by the process: JAX compiles nothing
    assert len(mine(timeline(), "jit(steps_of_a_toy)")) == 1

    jax.clear_caches()
    steps_of_a_toy(x).block_until_ready()
    _, warm = mine(timeline(), "jit(steps_of_a_toy)")
    assert warm["args"]["cache"] == "hit" and "written" not in warm["args"]
    assert 0 < warm["args"]["retrieval_s"] <= warm["args"]["backend_s"]
    assert parts_of(warm) <= warm["dur"] / 1e6 + 1e-3
    assert warm["ts"] >= cold["ts"] + cold["dur"]
    # every program of the stretch, the small ones jnp compiles too, says so
    assert {e["args"]["cache"] for e in timeline()} <= {"hit", "miss"}


def test_without_a_cache_directory_the_slice_says_off(timeline, no_cache):
    @jax.jit
    def nobody_caches_me(x):
        return x * 3 + 1

    nobody_caches_me(jnp.ones(5))
    (event,) = mine(timeline(), "jit(nobody_caches_me)")
    assert event["args"]["cache"] == "off"
    assert "written" not in event["args"] and "retrieval_s" not in event["args"]


def test_a_trace_that_nothing_compiles_is_nobodys_part(timeline, no_cache):
    """``jax.eval_shape`` traces and compiles nothing: no slice, and the
    next program does not inherit its trace."""

    def shape_only(x):
        return jnp.tanh(x) @ x

    jax.eval_shape(shape_only, jnp.ones((4, 4)))
    assert mine(timeline(), "jit(shape_only)") == []

    @jax.jit
    def the_next_program(x):
        return x - 1

    the_next_program(jnp.ones(3))
    (event,) = mine(timeline(), "jit(the_next_program)")
    assert event["dur"] / 1e6 - parts_of(event) < 0.05


def test_a_lowering_that_traces_does_not_take_the_programs_trace(
    timeline, no_cache
):
    """Lowering a program that draws random numbers traces the generator's
    own functions (hundreds of trace events between the program's trace and
    the lowering's event); the slice still has the PROGRAM's trace, from
    before the lowering began."""

    @jax.jit
    def draws_inside(key, x):
        return x + jax.random.uniform(jax.random.fold_in(key, 3), x.shape)

    draws_inside(jax.random.PRNGKey(0), jnp.ones(4))
    (event,) = mine(timeline(), "jit(draws_inside)")
    assert event["args"]["trace_s"] > 0 and event["args"]["lower_s"] > 0
    trace_ends = event["args"]["perf_counter_ns"] / 1e9 + event["args"]["trace_s"]
    ends = event["args"]["perf_counter_ns"] / 1e9 + event["dur"] / 1e6
    assert trace_ends <= ends - event["args"]["lower_s"] - event["args"]["backend_s"] + 1e-3


def test_an_aot_compile_is_one_slice_too(timeline, no_cache):
    def lowered_then_compiled(x):
        return x * x

    jax.jit(lowered_then_compiled).lower(jnp.ones(6)).compile()
    (event,) = mine(timeline(), "jit(lowered_then_compiled)")
    assert event["args"]["trace_s"] > 0 and event["args"]["lower_s"] > 0


def test_threads_that_compile_at_once_keep_their_own_parts(timeline, no_cache):
    """The parts are gathered a thread: eight threads compiling their own
    programs at once each get their slices whole, none the parts of another."""
    rounds, errors = 3, []

    def worker(k):
        try:
            for r in range(rounds):
                def program(x):
                    return jnp.cos(x) * k + r
                program.__name__ = f"thread_{k}_round_{r}"
                jax.jit(program)(jnp.ones(4 + r))
        except Exception as e:  # read after the join
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not errors
    events = timeline()
    for k in range(8):
        for r in range(rounds):
            (event,) = mine(events, f"jit(thread_{k}_round_{r})")
            assert event["args"]["trace_s"] > 0 and event["args"]["lower_s"] > 0
            assert parts_of(event) <= event["dur"] / 1e6 + 1e-3


def test_a_backends_opening_is_a_slice_and_prints_nothing(timeline, caplog):
    """The tap reads JAX's two DEBUG records of a backend's opening and lets
    no record through that the logger would not have made without it."""
    logger = logging.getLogger("jax._src.xla_bridge")
    assert logging.getLogger("jax").getEffectiveLevel() > logging.DEBUG
    logger.debug("Initializing backend '%s'", "toy")
    logger.debug("something else the bridge says")
    logger.debug("Backend '%s' initialized", "toy")
    logger.warning("a warning still gets through")
    (event,) = timeline("backend.open")
    assert event["args"]["platform"] == "toy" and event["dur"] >= 0
    said = lambda: [r.getMessage() for r in caplog.records  # noqa: E731
                    if r.name == logger.name]
    assert said() == ["a warning still gets through"]
    logger.debug("Backend '%s' initialized", "never opened")
    assert len(timeline("backend.open")) == 1
    # whoever turns the bridge's DEBUG records on still gets them
    with caplog.at_level(logging.DEBUG, logger="jax"):
        logger.debug("Initializing backend '%s'", "asked for")
    assert said()[-1] == "Initializing backend 'asked for'"


def test_setup_slices_outlive_the_ring():
    """70,000 later events push everything out of a ring of 65,536; the
    set-up slices are still written out, first."""
    tr = Tracer(max_events=PROCESS_TRACER_EVENTS)
    with tr.setup_phase("engine.init", slots=2) as init:
        tr.setup_slice("compile", tr._clock(), 0.0, fun_name="jit(f)")
        init.note(pages=17)
    with tr.phase("an early step"):
        pass
    for _ in range(70_000):
        tr.instant("later")
    assert len(tr.events) == PROCESS_TRACER_EVENTS
    events = [e for e in tr.to_perfetto()["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in events[:3]] == ["compile", "engine.init", "later"]
    assert events[1]["args"]["slots"] == 2 and events[1]["args"]["pages"] == 17
    assert not any(e["name"] == "an early step" for e in events)
    lanes = {e["args"]["name"] for e in tr.to_perfetto()["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "set-up" in lanes
    # a process that compiles without end does not grow the list without end
    for _ in range(SETUP_EVENTS_MAX):
        tr.setup_slice("compile", tr._clock(), 0.0)
    assert len(tr.setup_events) == SETUP_EVENTS_MAX
    assert tr.events[-1]["name"] == "compile"


def test_the_process_start_is_on_the_timeline():
    """``ts`` 0 of the process's tracer is the process's start as the OS has
    it, and ``process.start`` is the stretch from there to the tracer's
    making, so nothing before the first slice is lost."""
    tr = process_tracer()
    first = tr.setup_events[0]
    assert first["name"] == "process.start" and first["ts"] == 0.0
    assert first["args"]["source"] in ("proc_stat", "import")
    started, source = process_start()
    assert source == first["args"]["source"]
    assert abs(started * 1e9 - first["args"]["perf_counter_ns"]) < 0.05e9
    assert all(e["ts"] >= first["dur"] - 1 for e in tr.setup_events[1:])
    anchored = tr.to_perfetto()
    assert anchored["traceEvents"][-len(tr.events) - len(tr.setup_events)] == first
    # the wall-clock anchor moved with ts 0
    now_s = (time.perf_counter() * 1e9 - first["args"]["perf_counter_ns"]) / 1e9
    assert abs(anchored["metadata"]["wall_epoch_s"] + now_s - time.time()) < 1.0


def test_engine_and_trainer_write_their_start_up(timeline, tmp_path):
    from distributed_pytorch_tpu.models.transformer import TransformerLM
    from distributed_pytorch_tpu.utils.data import ArrayDataset
    from distributed_pytorch_tpu.models.toy import ToyRegressor

    model = TransformerLM(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        dtype=jnp.float32,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(
        model, params, max_slots=4, max_seq_len=32, page_size=4,
        token_budget=16, max_prefill_chunk=8,
    )
    assert not engine.tracer.enabled  # set-up is written all the same
    rid = engine.submit([5, 7, 11, 2, 9, 3], SamplingParams(max_new_tokens=3))
    engine.run()
    assert engine.poll(rid).finished

    inside = lambda c, p: (  # noqa: E731
        p["ts"] <= c["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"])
    (init,) = timeline("engine.init")
    (pools,) = timeline("engine.init.pools")
    (built,) = timeline("engine.build_prefill_programs")
    assert inside(pools, init) and built["ts"] >= init["ts"] + init["dur"]
    assert init["args"]["slots"] == 4 and init["args"]["pages"] == 33
    assert pools["args"]["bytes"] == sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(engine.cache))
    assert built["args"]["programs"] == 1 and built["args"]["widths"] == [8]
    compiled = [e["args"]["fun_name"] for e in timeline()
                if inside(e, built)]
    assert compiled == ["jit(run)"]  # the one prefill program of a toy engine

    rng = np.random.default_rng(0)
    data = ArrayDataset(rng.random((32, 20), dtype=np.float32),
                        rng.random((32, 1), dtype=np.float32))
    trainer = Trainer(
        ToyRegressor(), ShardedLoader(data, 16), optax.sgd(1e-2),
        save_every=0, checkpoint_path=str(tmp_path / "c.npz"),
    )
    (made,) = timeline("trainer.init")
    assert made["args"]["resumed_at_epoch"] == 0
    trainer.train(1)
    # the newest: this worker's earlier tests trained too
    epoch = [e for e in process_tracer().events if e["name"] == "epoch"][-1]
    assert epoch["ts"] >= made["ts"] + made["dur"]
    # the step's compile lies inside the first epoch by time containment
    assert any(inside(e, epoch) for e in timeline())

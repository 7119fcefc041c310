"""What the engine counts of its decode dispatches' reads, on scripted runs of
five toy engines: a default block with grouped KV heads, the DeepSeek-shaped
latent model, the dots3-shaped sparse and windowed latent model, the
Granite-shaped hybrid and the gated-delta hybrid (the models of
``tests/lowered_defaults.py``). Each is served twice, with its decode kernels
interpreted and on the gather path: seeded prompts, two askers of one cached
document side by side where the prefix cache is served, a few dozen steps.
The two latent models are served a third time at blocks the toy's tables can
show something on (:func:`other_blocks`): the CPU's own lookups give blocks at
which nothing goes as a run and the index kernel groups nobody.

``tests/test_decode_reads_recorded.py`` holds the tree to
``tests/data/decode_reads_recorded.json``: every ``step`` slice's ``decode_*``
and ``state_*`` args, every ``dsa.select`` and ``prefill.chunk`` event's args
and the same keys of ``stats()``, key for key and value for value. The names
are what ``benchmarks/harness/`` and ``benchmarks/metrics/`` read; the values
are a rule each of ``ops/paged_attention.py`` applied to the dispatch's
tables. A PR that moves who counts them leaves them as they were: record at
the parent commit (``PYTHONPATH=<checkout> python
tests/decode_reads_recorded.py``), and compare on the tree. A PR that changes
what a kernel copies records again, and says so.
"""

import contextlib
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "decode_reads_recorded.json")
ENGINE = dict(max_slots=3, max_seq_len=64, page_size=4, max_prefill_chunk=8,
              token_budget=11)
#: family (a model of ``lowered_defaults.MODELS``) -> whether the engine serves
#: it with the prefix cache.
FAMILIES = {
    "default_block_gqa": True,
    "latent_routed": True,
    "sparse_window_latent": True,
    "hybrid_mamba2_routed": False,
    "hybrid_gated_delta": False,
}
COUNTED = ("decode_", "state_")


def modes(family: str) -> tuple:
    latent = family in ("latent_routed", "sparse_window_latent")
    return ("interpret", "gather") + ("interpret_other_blocks",) * latent


@contextlib.contextmanager
def other_blocks(family: str):
    """The kernels' block lookups steered from outside, as
    ``tests/test_deepseek_serving.py`` and ``tests/test_dots3_serving.py``
    steer them. The latent model's blocks 16 pages, the table's width: turns
    of 2 pages, which a resident document's are runs of (and the document's 6
    pages no group's: a group shares a block at least). The sparse model's
    index block 2 pages: the document's three blocks are runs, and its askers
    a group."""
    from distributed_pytorch_tpu.ops import paged_attention as pa

    kept = pa.block_pages, pa.INDEX_BLOCK_PAGES
    if family == "latent_routed":
        pa.block_pages = lambda pages_per_seq, *a, **kw: min(16, pages_per_seq)
    else:
        pa.INDEX_BLOCK_PAGES = 2
    try:
        yield
    finally:
        pa.block_pages, pa.INDEX_BLOCK_PAGES = kept


def counted(args: dict) -> dict:
    return {k: v for k, v in args.items() if k.startswith(COUNTED)}


def run(model, params, kernel, shared: bool, traced: bool) -> dict:
    """One scripted run: a document and a question, then two more questions
    on that document side by side and a request of its own beside them."""
    from distributed_pytorch_tpu.obs.tracer import Tracer
    from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams

    vocab = model.vocab_size
    draw = lambda n, seed: np.random.default_rng(seed).integers(  # noqa: E731
        1, vocab, size=n).tolist()
    document = draw(26, 1)
    tracer = Tracer() if traced else None
    engine = InferenceEngine(
        model, params, paged_kernel=kernel, prefix_cache=shared,
        tracer=tracer, **ENGINE)

    def serve(prompts, new_tokens):
        for prompt in prompts:
            engine.submit(prompt, SamplingParams(max_new_tokens=new_tokens))
        engine.run()

    serve([document + draw(2, 2)], 2)
    serve([document + draw(3, 3), document + draw(5, 4), draw(9, 5)], 7)
    out = {"stats": counted(engine.stats())}
    if traced:
        events = tracer.events
        out["step"] = [counted(e["args"]) for e in events
                       if e["name"] == "step" and e.get("ph") == "X"]
        out["dsa.select"] = [
            e["args"] for e in events if e["name"] == "dsa.select"]
        # A slice's args begin with its step and its start on the clock.
        out["prefill.chunk"] = [
            {k: v for k, v in e["args"].items() if k != "perf_counter_ns"}
            for e in events if e["name"] == "prefill.chunk"]
    engine.close()
    return out


def record(family: str, traced: bool = True) -> dict:
    """mode -> what :func:`run` gives for ``family``'s toy model. Without a
    tracer only ``stats()`` is left to read, and the run at other blocks is
    left out: the two plans (kernels, gather) have been through by then."""
    import lowered_defaults

    model, params = lowered_defaults.MODELS[family]()
    out = {}
    for mode in modes(family)[:None if traced else 2]:
        kernel = False if mode == "gather" else "interpret"
        with (other_blocks(family) if "other" in mode
              else contextlib.nullcontext()):
            out[mode] = run(model, params, kernel, FAMILIES[family], traced)
    return out


if __name__ == "__main__":
    recorded = {family: record(family) for family in FAMILIES}
    os.makedirs(os.path.dirname(RECORDED), exist_ok=True)
    with open(RECORDED, "w") as f:
        json.dump(recorded, f, indent=1, sort_keys=True)
        f.write("\n")
    print(RECORDED)

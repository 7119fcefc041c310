"""Driver for configurations of kind ``serve_hybrid``: a decoder whose layers
are Mamba mixers with a few attention layers between them (the ``jamba``
family), served through the program's normal ``InferenceEngine``.

Its own is ``build_program``: the program's model for such a configuration
and its parameter tree, filled with the reference's weights. The closed loop,
the counting rule, the window, the trace and the comparison of the served
tokens are ``drivers/serve.py``'s: ``run`` loads a private copy of that file,
rebinds its ``build_program`` to this one and calls its ``run``. Folding the
two drivers into one is a ``benchmark`` PR's. Round that call it adds:

* **The state's own comparison.** ``serve.py``'s ``correct`` reads served
  tokens, and the noise of bfloat16 projections hides a scan state kept in
  less than the float32 the configuration states (PERF.md section 2). So,
  after the window and before the engine is closed, ONE more request goes
  through the same engine and the same compiled programs (the deck's longest
  prompt in chunks, then decode, ``check.pad_tokens_to`` tokens in all), the
  scan states of its slot are read, and after the served tokens' comparison
  they are held against the reference's ``final_states`` over the same
  tokens, layer by layer, as ``|h - h_ref| / |h_ref|``. Judged is the FIRST
  Mamba layer's, against ``check.state_gap_limit``: it reads embeddings
  through one projection, so its distance is the state's own arithmetic and
  little else, where a deeper layer's is mostly the bfloat16 noise of the
  activations that reached it (twenty times the first's by the last layer).
  The other layers' are printed. ``correct`` is both comparisons.
* **The trace, read once.** A step of such a cell is a decode program and
  several prefill chunk programs, each chunk's scan a loop that writes an
  event for every operation of every iteration: 570,000 device events a traced
  second. ``harness/hybrid.py``'s ``TraceOnce`` stands in ``load_xplane``'s
  place for ``serve.py`` and ``harness/phases.py`` (whose pass is made here,
  before the readers ask for it), so that the file is read once and not three
  times. Writing the trace out still costs ~14 s for every
  second the profiler ran, so a traced run traces the last ``TRACE_SECONDS``
  of the window and not ``serve.py``'s 6: a run whose set-up compiles has to
  end inside the driver's 360 s.
* **The memory split** with the state pool as a line of its own (the engine
  keeps the states in the same ``cache`` tree as the KV pools, so ``serve.py``
  counts them into its "KV pool").
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 1.0  # of the window's end that a traced run traces
PROBE_INDEX = 10**6  # the probe's place in prompt_tokens' numbering: no deck reaches it


def layer_types(cfg: dict) -> tuple:
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(cfg["num_hidden_layers"]))


def build_program(cfg: dict, weights: dict):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    kinds = layer_types(cfg)
    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], dtype=jnp.dtype(cfg["torch_dtype"]),
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, rope=False, layer_types=kinds,
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
    )
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
    }
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        layer = {
            "ln_attn": {"scale": w["ln1_g"]},
            "ln_mlp": {"scale": w["ln2_g"]},
            "mlp": {"gate": {"kernel": w["w_gate"]},
                    "up": {"kernel": w["w_up"]},
                    "down": {"kernel": w["w_down"]}},
        }
        if kind == "attention":
            layer["attention"] = {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]}, "out": {"kernel": w["wo"]},
            }
        else:
            layer["mamba"] = {
                "in_proj": {"kernel": w["w_in"]},
                "conv_kernel": w["conv_w"], "conv_bias": w["conv_b"],
                "x_proj": {"kernel": w["w_x"]},
                "dt_norm": {"scale": w["dt_g"]},
                "b_norm": {"scale": w["b_g"]},
                "c_norm": {"scale": w["c_g"]},
                "dt_proj": {"kernel": w["w_dt"], "bias": w["b_dt"]},
                "A_log": w["a_log"], "D": w["d_skip"],
                "out_proj": {"kernel": w["w_out"]},
            }
        params[f"block_{i}"] = layer
    return model, params


_SERVE = "bench_drivers_serve_for_hybrid"


def _serve():
    """A private copy of ``drivers/serve.py``, loaded once."""
    module = sys.modules.get(_SERVE)
    if module is None:
        spec = importlib.util.spec_from_file_location(
            _SERVE, os.path.join(HERE, "serve.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[_SERVE] = module  # dataclasses look their module up there
        spec.loader.exec_module(module)
    return module


def reference_logits(cell, weights, r, sample, **kw):
    """``serve.py``'s, for ``control.py``'s ``serve_control``."""
    return _serve().reference_logits(cell, weights, r, sample, **kw)


def scan_states(cache, slot: int):
    """``[mamba layers, N, d_inner]``: slot ``slot``'s scan state in every
    Mamba layer of an engine's ``cache`` tree, in layer order."""
    import jax
    import numpy as np

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] == "scan_state":
            layer = next(int(k[6:]) for k in keys if k.startswith("block_"))
            found[layer] = np.asarray(leaf[slot], np.float32)
    return np.stack([found[i] for i in sorted(found)])


def probe_state(engine, cell, serve) -> dict:
    """One request through the engine as the window left it (module
    docstring): the tokens the recurrence consumed and the scan states it
    left in its slot. The window's requests are cancelled first, as
    ``close`` would, and what the probe writes to the engine's tracer is
    taken out again, so that the readers see the window alone."""
    from distributed_pytorch_tpu.serving import SamplingParams

    engine.finish_inflight()
    for rid, req in list(engine.requests.items()):
        if not req.done:
            engine.cancel(rid)
    events = getattr(engine.tracer, "events", [])  # none on the null tracer
    kept = len(events)
    prompt_len = max(p for p, _ in cell.traffic["deck"])
    want = cell.traffic["check"]["pad_tokens_to"] - prompt_len + 1
    prompt = serve.prompt_tokens(
        cell.seed, PROBE_INDEX, prompt_len, cell.config["vocab_size"])
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=want))
    engine.step()
    slot = engine.requests[rid].slot  # admitted at once: every slot is free
    engine.run()
    status = engine.poll(rid)
    if status.state != "finished" or len(status.generated) != want:
        raise RuntimeError(f"the state probe ended {status.state}")
    del events[kept:]
    # A request that stops at its length is not dispatched again (no wasted
    # step), and the last token it sampled was never fed: the slot holds the
    # state after the prompt and all but that token.
    return {"tokens": prompt + status.generated[:-1],
            "states": scan_states(engine.cache, slot)}


def state_gaps(cell, weights, probe: dict) -> list:
    """How far the program's scan states lie from the reference's over the
    same tokens: ``|h - h_ref| / |h_ref|`` (Frobenius) of every Mamba layer."""
    import numpy as np

    want = np.asarray(cell.reference.final_states(
        cell.config, weights, probe["tokens"]))  # [layers, d_inner, N]
    got = probe["states"].transpose(0, 2, 1)
    flat = lambda a: a.reshape(len(a), -1)  # noqa: E731
    return (np.linalg.norm(flat(got - want), axis=1)
            / np.linalg.norm(flat(want), axis=1)).tolist()


def run(cell):
    """Run one cell through ``serve.py``'s ``run`` (module docstring says
    what is put round it)."""
    from harness import hybrid, phases

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    if "layer_types" not in getattr(TransformerLM, "__dataclass_fields__", {}):
        # A program from before the block's options (this cell's parent):
        # fail before anything is built.
        raise SystemExit(
            "this program's TransformerLM has no layer_types: it cannot "
            "build a serve_hybrid configuration")
    serve = _serve()
    serve.TRACE_SECONDS = TRACE_SECONDS
    serve.build_program = cell.hooks.get("build_program", build_program)
    build_engine = cell.hooks.get("build_engine", serve.build_engine)
    after_check = cell.hooks.get("after_check")
    limit = cell.traffic["check"]["state_gap_limit"]
    held = {}

    def probing_build_engine(cfg, model, params, tracer=None):
        engine = build_engine(cfg, model, params, tracer)
        held["state_bytes"] = engine.state_bytes_per_slot * engine.max_slots
        close = engine.close

        def close_after_the_probe():
            if "probe" not in held:
                held["probe"] = probe_state(engine, cell, serve)
            close()

        engine.close = close_after_the_probe
        return engine

    def state_check(cell, weights, sample, check):
        gaps = state_gaps(cell, weights, held["probe"])
        held["state_gap"] = gaps[0]
        cell.say(
            f"correct: the first Mamba layer's scan state after the probe's "
            f"{len(held['probe']['tokens'])} tokens lies {gaps[0]:.6f} from "
            f"the reference's (limit {limit}); every layer's: "
            + " ".join(f"{g:.4f}" for g in gaps))
        if after_check is not None:  # control_hybrid.py and the tests
            after_check(cell, weights, sample, dict(
                check, state_gap=held["state_gap"],
                probe_tokens=held["probe"]["tokens"]))

    trace_once = hybrid.TraceOnce(cell.config)
    hooks, cell.hooks = cell.hooks, {
        **cell.hooks, "build_engine": probing_build_engine,
        "after_check": state_check}
    loaders = serve.load_xplane, phases.load_xplane
    serve.load_xplane = phases.load_xplane = trace_once
    try:
        out = serve.run(cell)
        ctx = out["context"]
        if ctx is not None:
            # The readers' pass over the trace, while TraceOnce holds it.
            phases.reduced(ctx)
    finally:
        cell.hooks = hooks
        serve.load_xplane, phases.load_xplane = loaders
    out["correct"] = (
        out["correct"] and "state_gap" in held and held["state_gap"] <= limit)
    if ctx is not None:
        ctx["ssm_ops"] = trace_once.window, trace_once.ssm
        # serve.py's schedule recorder saw the probe's steps too.
        del ctx["counters"]["plans"][len(ctx["step_rows"]):]
        steps = hybrid.traced_steps(ctx) or []
        cell.say(
            f"programs: {len(steps)} steps started in the traced window, "
            f"with {sum(p['prefill_chunks'] for p in steps)} prefill chunk "
            f"programs beside their decode programs; the trace was read "
            f"once, in {trace_once.read_s:.1f}s")
    device = out["device"]
    num_pages = cell.config["assumed"]["engine"]["num_pages"]
    both = device["memory_kv_pool_reserved_bytes"]
    # serve.py printed floor(both * peak_pages / num_pages); a page is more
    # than a byte, so that has exactly one peak_pages behind it.
    peak_pages = -(-device["memory_kv_pool_used_peak_bytes"] * num_pages // both)
    kv = both - held["state_bytes"]
    device["memory_state_pool_bytes"] = held["state_bytes"]
    device["memory_kv_pool_reserved_bytes"] = kv
    device["memory_kv_pool_used_peak_bytes"] = kv * peak_pages // num_pages
    cell.say(
        f"memory, split: weights {device['memory_weights_bytes']} B; the "
        f"state pool {held['state_bytes']} B (every slot's conv tail and scan "
        f"state, all of it written by the traffic); the KV pool {kv} B "
        f"reserved, of which the requests held at most {peak_pages} of "
        f"{num_pages} pages ({device['memory_kv_pool_used_peak_bytes']} B). "
        f"The line above counts the state pool into its KV pool.")
    return out

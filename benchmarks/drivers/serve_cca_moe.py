"""Driver for configurations of kind ``serve_cca_moe``: a decoder whose every
layer is compressed convolutional attention (K and V pages AND a two-token
slot state) and one expert of sixteen chosen by a router network that carries
its state from layer to layer (the ``zaya`` family), served through the
program's normal ``InferenceEngine`` with every expert and the whole tied
vocabulary held.

The closed loop, the counting rule, the window and the trace are
``drivers/serve.py``'s: ``run`` loads a private copy of that file (as
``drivers/serve_window_moe.py`` does), rebinds what differs and calls its
``run``. What differs:

* **``build_program``**: the program's model for such a configuration and its
  parameter tree, filled with the reference's weights; ``build_engine``: the
  engine from ``assumed.engine`` as it stands.
* **The field test first.** A program whose ``LAYER_TYPES`` has no ``"cca"``
  cannot build this model: ``run`` exits non-zero before anything is built
  (this cell's parent does).
* **The served tokens' comparison on the device.** A request's reference
  logits are up to ``[1024, 262272]`` float32, 1.07 GB: the two numbers
  ``serve.py`` reads of them (each served token's reference logit below the
  reference's best) are taken where they lie, not on the host.
* **The probe.** After the window and before the engine is closed, ONE more
  request goes through the same engine and the same compiled programs:
  ``check.probe_prompt`` tokens prefilled in pieces, then ``check.probe_output``
  decoded tokens. From it, each under its own limit of the traffic file's
  ``check``: ``routing_gap`` (the share of the (token, layer) pairs of the
  tokens a program carried alone whose expert, as the engine's programs report
  it, is not the reference's own), ``kv_gap`` (the FIRST layer's K and V
  pages, read back through the probe's block table, against the reference's
  mixed, normed, rotated ``k`` and shifted ``v``: bf16 rounding when sound; it
  crosses every piece border and the prefill-to-decode border, where the slot
  state is what carries the convolutions and the shift) and ``kv_gap_last``
  (the last layer's), each as ``|got - want| / |want|``. ``correct`` is the
  served tokens' two numbers and these three.
* **A shorter traced stretch** (``TRACE_SECONDS``) and **the layers'
  operations** read from the trace file for ``harness/cca.py``'s readers
  (``ctx["cca_ops"]``), and **the memory split** with the state pool apart.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces
#: The probe's numbers, each under ``check.<name>_limit``.
GAPS = ("routing_gap", "kv_gap", "kv_gap_last")
PROBE_INDEX = 10**6  # the probe's prompt in prompt_tokens' numbering


def model_options(cfg: dict) -> dict:
    """``TransformerLM``'s fields for the configuration (``control_cca_moe.py``
    changes one of them to plant a fault)."""
    import jax.numpy as jnp

    layers = cfg["num_hidden_layers"]
    if set(cfg["layer_types"][:layers]) != {"hybrid"} or cfg["sliding_window"]:
        raise ValueError("only 'hybrid' layers with no window are built")
    if tuple(cfg.get("experts_held") or (0, cfg["num_experts"])) != (
            0, cfg["num_experts"]):
        raise ValueError("every expert is held")
    rope = cfg["rope_parameters"]["hybrid"]
    head = cfg["head_dim"]
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=layers, n_heads=cfg["num_attention_heads"], head_dim=head,
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], dtype=jnp.dtype(cfg["torch_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"], norm="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], use_bias=False,
        rope_theta=float(rope["rope_theta"]), residual_scales=True,
        layer_types=("cca",) * layers,
        cca_options=(
            ("rotary_dim", int(round(head * rope["partial_rotary_factor"]))),
            ("time0", cfg["cca_time0"]), ("time1", cfg["cca_time1"])),
        ffn_types=("routed",) * layers, routed_experts=cfg["num_experts"],
        routed_top_k=cfg["num_experts_per_tok"],
        experts_held=(0, cfg["num_experts"]), routed_gating="softmax_biased",
        routed_router="mlp_carry",
        routed_router_hidden=cfg["router_hidden_size"],
    )


def build_program(cfg: dict, weights: dict, **changed):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(**{**model_options(cfg), **changed})
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
    }
    for i, w in enumerate(weights["layers"]):
        params[f"block_{i}"] = {
            "ln_attn": {"scale": w["ln1_g"]}, "ln_mlp": {"scale": w["ln2_g"]},
            "attn_skip_scale": w["res_attn_a"],
            "attn_branch_scale": w["res_attn_b"],
            "mlp_skip_scale": w["res_mlp_a"],
            "mlp_branch_scale": w["res_mlp_b"],
            "cca": {
                "q_proj": {"kernel": w["wq"]}, "k_proj": {"kernel": w["wk"]},
                "v_proj": {"kernel": w["wv"]}, "o_proj": {"kernel": w["wo"]},
                "conv0_kernel": w["conv0_w"], "conv0_bias": w["conv0_b"],
                "conv1_kernel": w["conv1_w"], "conv1_bias": w["conv1_b"],
                "temperature": w["tau"],
            },
            "experts": {
                "router": {"down": w["rd"], "carry_scale": w["gamma"],
                           "norm": {"scale": w["rn_g"]}, "w1": w["r1"],
                           "w2": w["r2"], "w3": w["r3"]},
                "router_bias": w["router_bias"],
                "in_kernel": w["we_in"], "out_kernel": w["we_out"],
            },
        }
    return model, params


def build_engine(cfg: dict, model, params, tracer=None):
    from distributed_pytorch_tpu.serving import InferenceEngine

    return InferenceEngine(
        model, params, tracer=tracer, **cfg["assumed"]["engine"])


def _private(name: str):
    """A private copy of ``drivers/<name>.py``, loaded once."""
    key = f"bench_drivers_{name}_for_cca_moe"
    module = sys.modules.get(key)
    if module is None:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(HERE, f"{name}.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module  # dataclasses look their module up there
        spec.loader.exec_module(module)
    return module


def _serve():
    return _private("serve")


def reference_logits(cell, weights, r, sample, **kw):
    """``serve.py``'s, for ``control.py``'s ``serve_control``."""
    return _serve().reference_logits(cell, weights, r, sample, **kw)


def check_against_reference(cell, weights, sample) -> dict:
    """``serve.py``'s, with a request's logits left on the device: how far
    each served (greedy) token's reference logit lies below the reference's
    best, over every served token of ``sample``."""
    import jax.numpy as jnp
    import numpy as np

    worst, total, compared, flips = 0.0, 0.0, 0, 0
    for r in sample:
        n = len(r.generated)
        logits = reference_logits(cell, weights, r, sample)
        served = jnp.asarray(r.generated, jnp.int32)
        gaps = np.asarray(
            logits.max(axis=-1) - logits[jnp.arange(n), served], np.float64)
        del logits
        worst = max(worst, float(gaps.max()))
        total += float(gaps.sum())
        flips += int((gaps > 0).sum())
        compared += n
    return {"logit_gap": worst, "mean_gap": total / compared, "flips": flips,
            "tokens": compared, "requests": len(sample)}


def probe(engine, cell, serve) -> dict:
    """One request through the engine as the window left it (module
    docstring): its tokens, the routing counts of every program the engine
    ran for it, and its pages of the first and the last layer. The window's
    requests are cancelled first, as ``close`` would, and what the probe
    writes to the engine's tracer is taken out again, so that the readers see
    the window alone."""
    import numpy as np

    from distributed_pytorch_tpu.serving import SamplingParams

    cfg, check = cell.config, cell.traffic["check"]
    engine.finish_inflight()
    for rid, req in list(engine.requests.items()):
        if not req.done:
            engine.cancel(rid)
    events = getattr(engine.tracer, "events", [])  # none on the null tracer
    kept = len(events)
    prompt = serve.prompt_tokens(
        cell.seed, PROBE_INDEX, check["probe_prompt"], cfg["vocab_size"])
    want = check["probe_output"]
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=want))
    req = engine.requests[rid]
    programs, pages = [], []
    while not engine.poll(rid).finished:
        engine.step()
        programs.extend(engine.routing_counts)
        if req.table.pages:
            pages = list(req.table.pages)
    status = engine.poll(rid)
    if status.state != "finished" or len(status.generated) != want:
        raise RuntimeError(f"the probe ended {status.state}")
    del events[kept:]
    # A request that stops at its length is not dispatched again, and the
    # last token it sampled was never fed: the pages hold the prompt and all
    # but that token.
    tokens = prompt + status.generated[:-1]
    layers = (0, cfg["num_hidden_layers"] - 1)
    kv_pages = _private("serve_window_moe").kv_pages
    return {
        "tokens": tokens, "cached": 0, "layers": layers,
        "routing": [np.asarray(counts) for counts in programs],
        "kv": np.stack([
            kv_pages(engine.cache, layer, pages)[:, : len(tokens)]
            for layer in layers]),
    }


def probe_gaps(cell, weights, probed: dict, **kw) -> dict:
    """``routing_gap`` and the two page readings of a probe, from ONE pass of
    the reference over its tokens (padded as the served tokens' comparison
    pads, so that the layers' compiled programs are shared)."""
    import numpy as np

    # The routing's comparison is ``serve_latent_moe.py``'s, as it stands.
    routing_gap = _private("serve_latent_moe").routing_gap
    check = cell.traffic["check"]
    n = len(probed["tokens"])
    tokens = list(probed["tokens"]) + [0] * (check["pad_tokens_to"] - n)
    kv, routed = cell.reference.probe_at(
        cell.config, weights, tokens, probed["layers"], **kw)
    want = np.asarray(kv, np.float32)[:, :, :n]
    flat = lambda a: a.reshape(len(a), -1)  # noqa: E731
    first, last = (np.linalg.norm(flat(probed["kv"] - want), axis=1)
                   / np.linalg.norm(flat(want), axis=1)).tolist()
    cell.say(
        f"correct: the probe's K and V pages lie {first:.6f} (layer "
        f"{probed['layers'][0]}) and {last:.6f} (layer {probed['layers'][1]}) "
        f"from the reference's k and v over its {n} positions (limits "
        f"{check['kv_gap_limit']} and {check['kv_gap_last_limit']})")
    return {"routing_gap": routing_gap(cell, routed[:, :n], probed),
            "kv_gap": first, "kv_gap_last": last}


def run(cell):
    """Run one cell through ``serve.py``'s ``run`` (module docstring says
    what is put round it)."""
    from distributed_pytorch_tpu.models import transformer

    if "cca" not in getattr(transformer, "LAYER_TYPES", ()):
        # A program from before the layer (this cell's parent): fail before
        # anything is built.
        raise SystemExit(
            "this program's LAYER_TYPES has no 'cca': it cannot build a "
            "serve_cca_moe configuration")
    serve = _serve()
    serve.TRACE_SECONDS = TRACE_SECONDS
    serve.build_program = cell.hooks.get("build_program", build_program)
    serve.build_engine = build_engine
    make_engine = cell.hooks.get("build_engine", build_engine)
    after_check = cell.hooks.get("after_check")
    check = cell.traffic["check"]
    held = {}

    def probing_build_engine(cfg, model, params, tracer=None):
        engine = make_engine(cfg, model, params, tracer)
        held["engine"] = engine
        close = engine.close

        def close_after_the_probe():
            if "probe" not in held:
                held["stats"] = engine.stats()
                t0 = time.perf_counter()
                held["probe"] = probe(engine, cell, serve)
                cell.say(f"the probe took {time.perf_counter() - t0:.1f}s")
            close()

        engine.close = close_after_the_probe
        return engine

    def probe_check(cell, weights, sample, numbers):
        held.update(probe_gaps(cell, weights, held["probe"]))
        if after_check is not None:  # control_cca_moe.py and the tests
            after_check(cell, weights, sample, dict(
                numbers, probe=held["probe"], **{k: held[k] for k in GAPS}))

    hooks, cell.hooks = cell.hooks, {
        **cell.hooks, "build_engine": probing_build_engine,
        "after_check": probe_check}
    serve_check = serve.check_against_reference
    serve.check_against_reference = check_against_reference
    try:
        out = serve.run(cell)
    finally:
        cell.hooks = hooks
        serve.check_against_reference = serve_check
    out["correct"] = bool(out["correct"] and all(
        held.get(key, float("inf")) <= check[f"{key}_limit"] for key in GAPS))
    stats = held.get("stats") or {}
    engine = held.get("engine")
    if engine is not None:
        device = out["device"]
        state = engine.state_bytes_per_slot * engine.max_slots
        device["memory_state_pool_bytes"] = state
        device["memory_kv_pool_reserved_bytes"] -= state
        cell.say(
            f"memory, split: weights {device['memory_weights_bytes']} B; the "
            f"state pool {state} B ({engine.state_bytes_per_slot} B a slot "
            f"over the layers); the K/V pools "
            f"{device['memory_kv_pool_reserved_bytes']} B. The line above "
            f"counts the state pool into its KV pool.")
    cell.say(
        f"engine: layer kinds {stats.get('layer_kinds')}, router "
        f"{stats.get('moe_router')}, products {stats.get('moe_product')}, K/V "
        f"block form {stats.get('kv_decode_block_form')}; "
        f"{stats.get('page_bytes_per_token_layer')} B a token and layer; "
        f"{stats.get('preemptions')} preemptions; "
        f"{stats.get('state_slots_updated')} (row, layer) states updated, "
        f"{stats.get('state_bytes_moved')} B of state moved (set-up included)")
    ctx = out["context"]
    if ctx is not None:
        from harness import cca

        # serve.py's schedule recorder saw the probe's steps too.
        del ctx["counters"]["plans"][len(ctx["step_rows"]):]
        ops = cca.read_ops(cell.scratch("trace"), cell.config)
        ctx["cca_ops"] = ops
        cell.say(
            f"operations: {len(ops['kernel'])} calls of the K/V decode "
            f"kernel, {len(ops['cca'])} other operations of the CCA "
            f"sublayers and {len(ops['moe'])} of the expert sublayers among "
            f"{ops['events']} device events, read in {ops['read_s']:.1f}s")
    return out

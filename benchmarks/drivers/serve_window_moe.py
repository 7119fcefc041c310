"""Driver for configurations of kind ``serve_window_moe``: a decoder whose K/V
attention layers are sliding (a window of 128 positions) three to every full
one, whose heads carry an RMSNorm of their own and whose feed-forwards are one
dense layer, then sigmoid-routed experts plus a shared one (the ``exaone_moe``
family), served through the program's normal ``InferenceEngine`` with its TWO
block-table groups (``serving/kv_cache.py`` ``WindowGroup``: the sliding
layers' pages behind a window go back to their group's allocator), holding
the chip's share of the experts and of the vocabulary.

The closed loop, the counting rule, the window, the trace and the comparison
of the served tokens are ``drivers/serve.py``'s: ``run`` loads a private copy
of that file (as ``drivers/serve_latent_moe.py`` does), rebinds what differs
and calls its ``run``. What differs:

* **``build_program``**: the program's model for such a configuration and its
  parameter tree, filled with the reference's weights; ``build_engine``: the
  engine with the window group's pool size (``window_pages``) among its
  options (``serve.py``'s refuses a ``max_seq_len`` above ``sliding_window``).
* **The field test first.** A program whose ``LAYER_TYPES`` has no
  ``"attention_window"`` cannot build this model: ``run`` exits non-zero
  before anything is built (this cell's parent does).
* **The probe.** After the window and before the engine is closed, ONE more
  request goes through the same engine and the same compiled programs:
  ``check.probe_prompt`` tokens prefilled in pieces, then ``check.probe_output``
  decoded tokens, far past the window. From it, each under its own limit of
  the traffic file's ``check``: ``kv_gap_window`` (the FIRST layer's K and V
  pages, read back through the probe's WINDOW-group table, the positions it
  still holds, against the reference's normed, rotated ``k`` and ``v``: bf16
  rounding when sound), ``kv_gap_full`` (the first FULL layer's pages through
  the full group's table, every position: the first reading downstream of
  three window layers), ``routing_gap`` (the share of the routed (token,
  layer, expert) triples of the tokens a program carried alone on which the
  program's routers and the reference's differ, as ``serve_hybrid_moe.py``
  reads it), each as ``|got - want| / |want|``; and a count that is an
  assertion and no limit: the probe's window-group table never held more
  pages than a piece's (41 at the published sizes), nor than a decode row's
  (9) once it decoded. ``correct`` is the served tokens' two numbers, these
  three and that count.
* **The experts go to the host before the reference runs** (the window is
  over and the engine closed): a 12,800-token pass needs room that 11.96 GB
  of resident weights do not leave.
* **A shorter traced stretch** (``TRACE_SECONDS``), **the window layers'
  operations** read from the trace file for ``harness/window.py``'s readers
  (``ctx["swa_kv_ops"]``), and **the memory split** a group.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces
#: The probe's numbers, each under ``check.<name>_limit``.
GAPS = ("routing_gap", "kv_gap_window", "kv_gap_full")
PROBE_INDEX = 10**6  # the probe's prompt in prompt_tokens' numbering


def layer_types(cfg: dict) -> tuple:
    """The program's name for each layer that is run."""
    return tuple(
        "attention_window" if window else "attention"
        for window in cfg["sliding_windows"][: cfg["num_hidden_layers"]])


def ffn_types(cfg: dict) -> tuple:
    return tuple(
        "dense" if kind == "dense" else "routed"
        for kind in cfg["mlp_layer_types"][: cfg["num_hidden_layers"]])


def model_options(cfg: dict) -> dict:
    """``TransformerLM``'s fields for the configuration (``control_window_moe.py``
    changes one of them to plant a fault)."""
    import jax.numpy as jnp

    windows = {w for w in cfg["sliding_windows"] if w}
    if windows != {cfg["sliding_window"]}:
        raise ValueError(f"the sliding layers' windows {windows} are not one")
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("only renormalised sigmoid scores are built")
    theta = float(cfg["rope_parameters"]["rope_theta"])
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        dtype=jnp.dtype(cfg["torch_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, qk_norm="head",
        # The full layers attend on the normed projections as they are; a
        # sliding layer rotates its own (``assumed.rope``).
        rope=False, rope_theta=theta,
        layer_types=layer_types(cfg),
        attention_variants=(("attention_window", (
            ("rope", True), ("rope_theta", theta),
            ("window", cfg["sliding_window"]))),),
        ffn_types=ffn_types(cfg),
        routed_experts=cfg.get("num_experts_published", cfg["num_experts"]),
        routed_top_k=cfg["num_experts_per_tok"],
        experts_held=tuple(
            cfg.get("experts_held") or (0, cfg["num_experts"])),
        shared_d_ff=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        routed_gating="sigmoid_biased",
        routed_scale=float(cfg["routed_scaling_factor"]),
    )


def build_program(cfg: dict, weights: dict, **changed):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(**{**model_options(cfg), **changed})
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
        "lm_head": {"kernel": weights["head"],
                    "bias": jnp.zeros((cfg["vocab_size"],), jnp.float32)},
    }
    for i, (kind, w) in enumerate(zip(ffn_types(cfg), weights["layers"])):
        layer = {
            "ln_attn": {"scale": w["ln1_g"]},
            "ln_mlp": {"scale": w["ln2_g"]},
            "attention": {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]}, "out": {"kernel": w["wo"]},
                "q_norm": {"scale": w["qn_g"]}, "k_norm": {"scale": w["kn_g"]},
            },
        }
        if kind == "dense":
            layer["mlp"] = {"gate": {"kernel": w["w_gate"]},
                            "up": {"kernel": w["w_up"]},
                            "down": {"kernel": w["w_down"]}}
        else:
            layer["experts"] = {"router_kernel": w["router"],
                                "router_bias": w["router_bias"],
                                "in_kernel": w["we_in"],
                                "out_kernel": w["we_out"]}
            layer["shared_mlp"] = {"gate": {"kernel": w["ws_gate"]},
                                   "up": {"kernel": w["ws_up"]},
                                   "down": {"kernel": w["ws_down"]}}
        params[f"block_{i}"] = layer
    return model, params


def build_engine(cfg: dict, model, params, tracer=None):
    from distributed_pytorch_tpu.serving import InferenceEngine

    return InferenceEngine(
        model, params, tracer=tracer, **cfg["assumed"]["engine"])


def _private(name: str):
    """A private copy of ``drivers/<name>.py``, loaded once."""
    key = f"bench_drivers_{name}_for_window_moe"
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


def kv_pages(cache, layer: int, pages):
    """Layer ``layer``'s K and V rows of physical ``pages``, in order:
    float32 ``[2, len(pages) * page, G, dh]``."""
    import jax
    import numpy as np

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if f"block_{layer}" in keys and keys[-1] in ("cached_key", "cached_value"):
            found[keys[-1]] = leaf
    ids = np.asarray(pages, np.int32)
    return np.stack([
        np.asarray(found[name][ids], np.float32).reshape(
            (-1,) + found[name].shape[2:])
        for name in ("cached_key", "cached_value")])


def probe(engine, cell, serve) -> dict:
    """One request through the engine as the window left it (module
    docstring): its tokens, the routing counts of every program the engine
    ran for it, what its window-group table held step by step, and its pages
    of the first (window) and the first full layer. The window's requests
    are cancelled first, as ``close`` would, and what the probe writes to the
    engine's tracer is taken out again, so that the readers see the window
    alone."""
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
    programs, full, window = [], [], (0, [])
    group = engine.scheduler.window_group
    # The scheduler notes the most pages ONE sequence's window table held
    # (at every ensure, before the trim that follows the program): the run's
    # so far, then the probe's own, apart for its pieces and its decode steps.
    held = {"run": group.pages_held_peak, "prefill": 0, "decode": 0}
    while not engine.poll(rid).finished:
        phase = "decode" if req.state.value == "decode" else "prefill"
        group.pages_held_peak = 0
        engine.step()
        held[phase] = max(held[phase], group.pages_held_peak)
        programs.extend(engine.routing_counts)
        if req.table.pages:
            full = list(req.table.pages)
            window = (req.window_table.first, list(req.window_table.pages))
    group.pages_held_peak = max(held.values())
    status = engine.poll(rid)
    if status.state != "finished" or len(status.generated) != want:
        raise RuntimeError(f"the probe ended {status.state}")
    del events[kept:]
    # A request that stops at its length is not dispatched again, and the
    # last token it sampled was never fed: the pages hold the prompt and all
    # but that token.
    tokens = prompt + status.generated[:-1]
    first_full = layer_types(cfg).index("attention")
    first_window = layer_types(cfg).index("attention_window")
    page = cfg["assumed"]["engine"]["page_size"]
    start = window[0] * page
    return {
        "tokens": tokens,
        "cached": 0,
        "routing": [np.asarray(counts) for counts in programs],
        "layers": (first_window, first_full),
        "window_start": start,
        "window_kv": kv_pages(engine.cache, first_window, window[1])[
            :, : len(tokens) - start],
        "full_kv": kv_pages(engine.cache, first_full, full)[:, : len(tokens)],
        "held": held,
        "bounds": (group.piece_pages, group.decode_pages),
    }


def kv_gaps(cell, want, probed: dict) -> tuple:
    """``|got - want| / |want|`` (Frobenius, K and V together) of the probe's
    pages against the reference's ``[k, v]`` ``[2 layers, 2, T, G, dh]``: the
    window layer's over the positions its table still holds, the full
    layer's over every position."""
    import numpy as np

    want = np.asarray(want, np.float32)
    start = probed["window_start"]
    gap = lambda got, ref: float(  # noqa: E731
        np.linalg.norm(got - ref) / np.linalg.norm(ref))
    window = gap(probed["window_kv"], want[0][:, start:])
    full = gap(probed["full_kv"], want[1])
    check = cell.traffic["check"]
    cell.say(
        f"correct: the probe's K and V pages lie {window:.6f} (layer "
        f"{probed['layers'][0]}, through its WINDOW-group table: positions "
        f"{start}-{want.shape[2] - 1} of {want.shape[2]}) and {full:.6f} "
        f"(layer {probed['layers'][1]}, through the full group's table: "
        f"every position) from the reference's k and v (limits "
        f"{check['kv_gap_window_limit']} and {check['kv_gap_full_limit']})")
    return window, full


def probe_gaps(cell, weights, probed: dict, **kw) -> dict:
    """``routing_gap`` and the two page readings of a probe, from ONE pass of
    the reference over its tokens (padded as the served tokens' comparison
    pads, so that the layers' compiled programs are shared)."""
    # The routing's comparison is ``serve_latent_moe.py``'s, as it stands.
    routing_gap = _private("serve_latent_moe").routing_gap
    pad_to = cell.traffic["check"]["pad_tokens_to"]
    n = len(probed["tokens"])
    tokens = list(probed["tokens"]) + [0] * (pad_to - n)
    kv, routed = cell.reference.probe_at(
        cell.config, weights, tokens, probed["layers"], **kw)
    window, full = kv_gaps(cell, kv[:, :, :n], probed)
    return {"routing_gap": routing_gap(cell, routed[:, :n], probed),
            "kv_gap_window": window, "kv_gap_full": full}


def held_within_bounds(cell, probed: dict) -> bool:
    """The assertion of the module docstring, said aloud."""
    piece, decode = probed["bounds"]
    held = probed["held"]
    ok = (held["run"] <= piece and held["prefill"] <= piece
          and held["decode"] <= decode)
    cell.say(
        f"correct: the probe's window-group table held at most "
        f"{held['prefill']} pages inside a prefill piece and "
        f"{held['decode']} while it decoded (bounds {piece} and {decode}); "
        f"the most any sequence of the run held at once: {held['run']} "
        f"(bound {piece}): {'within' if ok else 'OUTSIDE'} the bounds")
    return ok


def run(cell):
    """Run one cell through ``serve.py``'s ``run`` (module docstring says
    what is put round it)."""
    from distributed_pytorch_tpu.models import transformer

    if "attention_window" not in getattr(transformer, "LAYER_TYPES", ()):
        # A program from before the window group (this cell's parent): fail
        # before anything is built.
        raise SystemExit(
            "this program's LAYER_TYPES has no 'attention_window': it cannot "
            "build a serve_window_moe configuration")
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
                held["probe"] = probe(engine, cell, serve)
            close()

        engine.close = close_after_the_probe
        return engine

    def probe_check(cell, weights, sample, numbers):
        held.update(probe_gaps(cell, weights, held["probe"]))
        held["within"] = held_within_bounds(cell, held["probe"])
        if after_check is not None:  # control_window_moe.py and the tests
            after_check(cell, weights, sample, dict(
                numbers, probe=held["probe"], within=held["within"],
                **{k: held[k] for k in GAPS}))

    check_against_reference = serve.check_against_reference

    def check_with_room(cell, weights, sample):
        # A 12,800-token pass of the reference needs ~5 GB of its own beside
        # what is resident, and 11.96 GB of weights leave under 4: the routed
        # experts (8.46 GB) go to the host first, as
        # ``serve_sparse_latent_moe.py`` moves them; the reference's layer
        # programs take them from there, a layer at a time.
        _private("serve_sparse_latent_moe").experts_to_host(weights)
        return check_against_reference(cell, weights, sample)

    hooks, cell.hooks = cell.hooks, {
        **cell.hooks, "build_engine": probing_build_engine,
        "after_check": probe_check}
    serve.check_against_reference = check_with_room
    try:
        out = serve.run(cell)
    finally:
        cell.hooks = hooks
        serve.check_against_reference = check_against_reference
    out["correct"] = bool(out["correct"] and held.get("within") and all(
        held.get(key, float("inf")) <= check[f"{key}_limit"]
        for key in GAPS))
    stats = held.get("stats") or {}
    engine = held.get("engine")
    if engine is not None:
        split = engine.pool_bytes_by_group()
        device = out["device"]
        device["memory_kv_pool_full_bytes"] = split["full_bytes"]
        device["memory_kv_pool_window_bytes"] = split["window_bytes"]
        cell.say(
            f"memory, split: weights {device['memory_weights_bytes']} B; the "
            f"full group's pools {split['full_bytes']} B "
            f"({cell.config['assumed']['engine']['num_pages']} pages), the "
            f"window group's {split['window_bytes']} B "
            f"({cell.config['assumed']['engine']['window_pages']} pages, of "
            f"which sequences held at most "
            f"{stats.get('window_pages_held_peak')} each). The line above "
            f"counts both into its KV pool and reckons the share held from "
            f"the full group's pages.")
    cell.say(
        f"pages: {stats.get('page_bytes_per_token_layer')} B a token and "
        f"layer; the window group gave back "
        f"{stats.get('window_pages_freed')} pages behind its windows "
        f"(set-up included); {stats.get('preemptions')} preemptions; a "
        f"window layer saw {stats.get('decode_window_tokens_visible')} keys "
        f"inside its rows' windows and copied "
        f"{stats.get('decode_window_tokens_read')}")
    ctx = out["context"]
    if ctx is not None:
        from harness import window

        # serve.py's schedule recorder saw the probe's steps too.
        del ctx["counters"]["plans"][len(ctx["step_rows"]):]
        ops = window.read_ops(cell.scratch("trace"), cell.config)
        ctx["swa_kv_ops"] = ops
        cell.say(
            f"operations: {len(ops['decode'])} calls of the windowed K/V "
            f"decode kernel and {len(ops['rest'])} operations of the prefill "
            f"pieces' windowed reads among {ops['events']} device events, "
            f"read in {ops['read_s']:.1f}s")
    return out

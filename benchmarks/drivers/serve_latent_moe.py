"""Driver for configurations of kind ``serve_latent_moe``: a decoder whose
every layer is multi-head latent attention (one cached latent a token, no
head axis) and whose feed-forwards are one dense layer, then routed experts
plus shared ones (the ``deepseek_v2`` family), served through the program's
normal ``InferenceEngine`` with the prefix trie ON, holding the chip's share
of the experts.

The closed loop, the counting rule, the window, the trace and the comparison
of the served tokens are ``drivers/serve.py``'s: ``run`` loads a private copy
of that file (as ``drivers/serve_hybrid.py`` does), rebinds what differs and
calls its ``run``. What differs:

* **The traffic format ``closed_loop_documents``**
  (``benchmarks/README.serve_latent_moe.md``):
  a deck entry is ``[document, question tokens, output tokens]``. A request's
  prompt is its document's tokens (``document_tokens``: from ``--seed`` and
  the document's index, the same in every request that asks of it) followed
  by a question that is distinct every time (``serve.py``'s
  ``prompt_tokens``). ``DocumentLoop`` is ``serve.py``'s ``ClosedLoop`` with
  that ``submit``.
* **The document warm-up.** After the compile warm-up, set-up prefills every
  document once (one request a document, ``document_warm_output`` tokens:
  at 1 the document's last partial page is left in the trie with the
  document's tail and nothing else) and then asks TWO short questions of one
  document side by side, so that in the window every request finds its
  document in the trie, and the page-copy program of copy-on-write is
  compiled before the window opens.
* **``build_program``**: the program's model for such a configuration and
  its parameter tree, filled with the reference's weights.
* **The field test first.** A program whose ``LAYER_TYPES`` has no
  ``"latent"`` cannot build this model: ``run`` exits non-zero before
  anything is built (this cell's parent does).
* **The probe.** After the window and before the engine is closed, ONE more
  request goes through the same engine and the same compiled programs: the
  deck's median document (a prefix hit) and a question, then
  ``check.probe_output`` decoded tokens. From it, each under its own limit of
  the traffic file's ``check``: ``routing_gap`` (the share of the routed
  (token, layer, expert) triples of the probe's tokens that a program carried
  alone on which the program's routers, as the engine's programs report them,
  and the reference's, on its own activations, differ), ``latent_gap`` and
  ``latent_gap_last`` (the probe's latent pages of the first and of the last
  layer, read back through its block table, prefix-hit and copied-on-write
  pages among them, against the reference's ``[c | k_pe]``: ``|got - want| /
  |want|``). ``correct`` is the served tokens' two numbers and these three.
* **A shorter traced stretch**: 27 layers' operations of 45 steps a second
  are ~60,000 device events a traced second, and writing them out is what a
  traced run waits for: ``TRACE_SECONDS`` of the window's end, not
  ``serve.py``'s 6.
* **The latent attention's operations**, read from the trace file for
  ``harness/latent.py``'s readers (``ctx["mla_ops"]``).
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces
#: The probe's numbers, each under ``check.<name>_limit``.
GAPS = ("routing_gap", "latent_gap", "latent_gap_last")
PROBE_INDEX = 10**6  # the probe's question in prompt_tokens' numbering
DOCUMENT_INDEX = 2 * 10**6  # document k is prompt_tokens' entry this + k


def layer_kinds(cfg: dict) -> tuple:
    """``"dense"`` or ``"routed"`` for each layer's feed-forward."""
    return tuple(
        "dense" if i < cfg["first_k_dense_replace"] else "routed"
        for i in range(cfg["num_hidden_layers"]))


def build_program(cfg: dict, weights: dict):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    kinds = layer_kinds(cfg)
    n_router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))
    if cfg["scoring_func"] != "softmax" or cfg["routed_scaling_factor"] != 1:
        raise ValueError("only softmax scores at a scaling factor of 1 are built")
    yarn = cfg.get("rope_scaling")
    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        dtype=jnp.dtype(cfg["torch_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, rope_theta=cfg["rope_theta"],
        layer_types=("latent",) * len(kinds),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        rope_yarn=tuple(sorted(
            (k, v) for k, v in yarn.items() if k != "type")) if yarn else None,
        ffn_types=kinds, routed_experts=n_router,
        routed_top_k=cfg["num_experts_per_tok"], experts_held=held,
        shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        # Renormalised, the top k of a softmax over all scores ARE a softmax
        # over the chosen scores: the program's default rule.
        routed_gating=("softmax_of_top_k" if cfg["norm_topk_prob"]
                       else "top_k_of_softmax"),
    )
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
        "lm_head": {"kernel": weights["head"],
                    "bias": jnp.zeros((cfg["vocab_size"],), jnp.float32)},
    }
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        layer = {
            "ln_attn": {"scale": w["ln1_g"]},
            "ln_mlp": {"scale": w["ln2_g"]},
            "mla": {"query": {"kernel": w["wq"]}, "kv_a": {"kernel": w["wkva"]},
                    "kv_norm": {"scale": w["kvn_g"]}, "kv_b": w["wkvb"],
                    "out": {"kernel": w["wo"]}},
        }
        if kind == "dense":
            layer["mlp"] = {"gate": {"kernel": w["w_gate"]},
                            "up": {"kernel": w["w_up"]},
                            "down": {"kernel": w["w_down"]}}
        else:
            layer["experts"] = {"router_kernel": w["router"],
                                "in_kernel": w["we_in"],
                                "out_kernel": w["we_out"]}
            layer["shared_mlp"] = {"gate": {"kernel": w["ws_gate"]},
                                   "up": {"kernel": w["ws_up"]},
                                   "down": {"kernel": w["ws_down"]}}
        params[f"block_{i}"] = layer
    return model, params


_SERVE = "bench_drivers_serve_for_latent_moe"


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


def document_tokens(serve, seed: int, traffic: dict, doc: int, vocab: int):
    """Document ``doc``'s token ids: the same in every request of a run."""
    return serve.prompt_tokens(
        seed, DOCUMENT_INDEX + doc, traffic["documents"][doc], vocab)


def document_loop(serve):
    """``serve.py``'s ``ClosedLoop`` over a deck of ``[document, question
    tokens, output tokens]``: a two-number entry (the warm-up requests) is
    ``serve.py``'s own distinct prompt."""

    class DocumentLoop(serve.ClosedLoop):
        def __init__(self, *args):
            super().__init__(*args)
            self._documents = {}

        def document(self, doc: int):
            if doc not in self._documents:
                self._documents[doc] = document_tokens(
                    serve, self.seed, self.traffic, doc, self.cfg["vocab_size"])
            return self._documents[doc]

        def submit(self, deck_index: int, *entry) -> None:
            if len(entry) == 2:
                return super().submit(deck_index, *entry)
            doc, question, want = entry
            prompt = self.document(doc) + serve.prompt_tokens(
                self.seed, self.submitted, question, self.cfg["vocab_size"])
            self._submit_prompt(deck_index, prompt, want)

        def _submit_prompt(self, deck_index: int, prompt, want: int) -> None:
            now = time.perf_counter()
            rid = self.engine.submit(prompt, self._params(max_new_tokens=want))
            self.open[rid] = serve.Served(
                self.submitted, deck_index, prompt, want, now)
            self.submitted += 1

    return DocumentLoop


def warm_documents(serve, run_requests, engine, loop, requests) -> None:
    """``serve.py``'s compile warm-up, then every document prefilled once and
    two questions asked of one of them (module docstring)."""
    run_requests(engine, loop, requests)
    want = loop.traffic["document_warm_output"]
    for doc in range(len(loop.traffic["documents"])):
        loop._submit_prompt(-1, loop.document(doc), want)
    run_requests(engine, loop, [])
    page = loop.cfg["assumed"]["engine"]["page_size"]
    partial = [doc for doc, n in enumerate(loop.traffic["documents"]) if n % page]
    for _ in range(2 if partial else 0):
        # Side by side: while two requests hold a document's partial last
        # page, the first to write on copies it.
        loop.submit(-1, partial[0], 8, 2)
    run_requests(engine, loop, [])


def latent_pages(cache, pages, layers) -> list:
    """The latent pools' rows of physical ``pages``, in order, for each of
    ``layers`` (negative counts from the last): float32 ``[len(pages) * page,
    pool width]`` a layer."""
    import jax
    import numpy as np

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] == "cached_latent":
            layer = next(int(k[6:]) for k in keys if k.startswith("block_"))
            found[layer] = leaf
    order = sorted(found)
    ids = np.asarray(pages, np.int32)
    return [
        np.asarray(found[order[i]][ids], np.float32).reshape(
            len(ids) * found[order[i]].shape[1], -1)
        for i in layers]


def probe(engine, cell, serve) -> dict:
    """One request through the engine as the window left it (module
    docstring): its tokens, how many of them the trie served, the routing
    counts of every program the engine ran for it, and its latent pages of
    the first and last layer. The window's requests are cancelled first, as
    ``close`` would, and what the probe writes to the engine's tracer is
    taken out again, so that the readers see the window alone."""
    import numpy as np

    from distributed_pytorch_tpu.serving import SamplingParams

    cfg, traffic = cell.config, cell.traffic
    engine.finish_inflight()
    for rid, req in list(engine.requests.items()):
        if not req.done:
            engine.cancel(rid)
    events = getattr(engine.tracer, "events", [])  # none on the null tracer
    kept = len(events)
    lengths = traffic["documents"]
    doc = sorted(range(len(lengths)), key=lambda i: lengths[i])[len(lengths) // 2]
    check = traffic["check"]
    prompt = document_tokens(
        serve, cell.seed, traffic, doc, cfg["vocab_size"]
    ) + serve.prompt_tokens(
        cell.seed, PROBE_INDEX, check["probe_question"], cfg["vocab_size"])
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
    first, last = latent_pages(engine.cache, pages, (0, -1))
    return {
        "tokens": tokens, "document": doc,
        "cached": int(req.cached_prompt_tokens or 0),
        "routing": [np.asarray(counts) for counts in programs],
        "latents": np.stack([first[: len(tokens)], last[: len(tokens)]]),
    }


def routing_gap(cell, want, probed: dict) -> float:
    """The share of the routed (token, layer, expert) triples on which the
    program's routers and the reference's differ, over the probe's tokens
    that a program carried alone (the decoded ones: there a program's counts
    ARE its token's experts). ``want`` is the reference's own routing, bool
    ``[expert layers, T, n_router]``: it is not told what the program chose."""
    import numpy as np

    top_k = cell.config["num_experts_per_tok"]
    want = np.asarray(want).astype(np.int64)
    differ = np.zeros(len(want))
    start, alone = probed["cached"], 0
    for counts in probed["routing"]:  # [layers, E], in the order of the tokens
        tokens = int(counts[0].sum()) // top_k
        if tokens == 1:
            differ += np.abs(counts - want[:, start]).sum(axis=-1) / 2
            alone += 1
        start += tokens
    if start != want.shape[1] or not alone:
        raise RuntimeError(
            f"the probe's programs routed up to token {start}, {alone} of "
            f"them alone; the probe consumed {want.shape[1]}")
    layers = differ / (alone * top_k)
    cell.say(
        f"correct: of the routed (token, layer, expert) triples of the "
        f"probe's {alone} tokens that a program carried alone "
        f"{layers.mean():.6f} differ from the reference's own (limit "
        f"{cell.traffic['check']['routing_gap_limit']}); a layer: "
        + " ".join(f"{g:.4f}" for g in layers))
    return float(layers.mean())


def latent_gap(cell, want, probed: dict) -> tuple:
    """``|got - want| / |want|`` (Frobenius over every cached position) of
    the probe's latent pages of the first and the last layer against the
    reference's ``[c | k_pe]`` ``[2, T, r + dr]``: ``(first, last)``. The
    first layer's reads embeddings through one projection and one norm, so
    its distance is the pages' own precision and little else; the last
    layer's is mostly the noise of the activations that reached it, and
    holds whatever went wrong on the way."""
    import numpy as np

    want = np.asarray(want, np.float32)
    got = probed["latents"][..., : want.shape[-1]]
    flat = lambda a: a.reshape(len(a), -1)  # noqa: E731
    gaps = (np.linalg.norm(flat(got - want), axis=1)
            / np.linalg.norm(flat(want), axis=1))
    cell.say(
        f"correct: the probe's latent pages ({probed['cached']} of its "
        f"{want.shape[1]} positions served by the trie) lie "
        f"{gaps[0]:.6f} (first layer) and {gaps[1]:.6f} (last layer) from "
        f"the reference's [c | k_pe] (limits "
        f"{cell.traffic['check']['latent_gap_limit']} and "
        f"{cell.traffic['check']['latent_gap_last_limit']})")
    return float(gaps[0]), float(gaps[1])


def probe_gaps(cell, weights, probed: dict, **kw) -> dict:
    """``routing_gap`` and ``latent_gap`` of a probe, from ONE pass of the
    reference over its tokens (padded as the served tokens' comparison pads,
    so that the layers' compiled programs are shared)."""
    pad_to = cell.traffic["check"]["pad_tokens_to"]
    n = len(probed["tokens"])
    tokens = list(probed["tokens"]) + [0] * (pad_to - n)
    latents, routed = cell.reference.probe_at(
        cell.config, weights, tokens, (0, -1), **kw)
    first, last = latent_gap(cell, latents[:, :n], probed)
    return {"routing_gap": routing_gap(cell, routed[:, :n], probed),
            "latent_gap": first, "latent_gap_last": last}


def run(cell):
    """Run one cell through ``serve.py``'s ``run`` (module docstring says
    what is put round it)."""
    from distributed_pytorch_tpu.models import transformer

    if "latent" not in getattr(transformer, "LAYER_TYPES", ()):
        # A program from before the latent layer (this cell's parent): fail
        # before anything is built.
        raise SystemExit(
            "this program's LAYER_TYPES has no 'latent': it cannot build a "
            "serve_latent_moe configuration")
    serve = _serve()
    serve.TRACE_SECONDS = TRACE_SECONDS
    serve.build_program = cell.hooks.get("build_program", build_program)
    serve.ClosedLoop = document_loop(serve)
    run_requests = serve.run_requests
    build_engine = cell.hooks.get("build_engine", serve.build_engine)
    after_check = cell.hooks.get("after_check")
    check = cell.traffic["check"]
    held = {}

    def warming_run_requests(engine, loop, requests):
        if requests is cell.traffic["compile_warm"]:
            return warm_documents(serve, run_requests, engine, loop, requests)
        return run_requests(engine, loop, requests)

    def probing_build_engine(cfg, model, params, tracer=None):
        engine = build_engine(cfg, model, params, tracer)
        held["page_bytes"] = engine.stats().get("page_bytes_per_token_layer")
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
        if after_check is not None:  # control_latent_moe.py and the tests
            after_check(cell, weights, sample, dict(
                numbers, probe=held["probe"], **{k: held[k] for k in GAPS}))

    hooks, cell.hooks = cell.hooks, {
        **cell.hooks, "build_engine": probing_build_engine,
        "after_check": probe_check}
    serve.run_requests = warming_run_requests
    try:
        out = serve.run(cell)
    finally:
        cell.hooks = hooks
        serve.run_requests = run_requests
    out["correct"] = bool(out["correct"] and all(
        held.get(key, float("inf")) <= check[f"{key}_limit"]
        for key in GAPS))
    stats = held.get("stats") or {}
    cell.say(
        f"pages: {held.get('page_bytes')} B a token and layer as held; the "
        f"trie served {stats.get('prefix_tokens_hit')} of the prompt tokens "
        f"it was asked for and missed {stats.get('prefix_tokens_missed')} "
        f"(set-up included); {stats.get('cow_copies')} pages copied on "
        f"write, {stats.get('page_evictions')} evicted, "
        f"{stats.get('preemptions')} preemptions")
    ctx = out["context"]
    if ctx is not None:
        from harness import latent

        # serve.py's schedule recorder saw the probe's steps too.
        del ctx["counters"]["plans"][len(ctx["step_rows"]):]
        ops = latent.read_ops(cell.scratch("trace"), cell.config)
        ctx["mla_ops"] = ops
        cell.say(
            f"operations: {len(ops['decode'])} calls of the latent decode "
            f"kernel and {len(ops['rest'])} other operations of the latent "
            f"attention among {ops['events']} device events, read in "
            f"{ops['read_s']:.1f}s")
    return out

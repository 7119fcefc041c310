"""Plain reference for a hybrid decoder-only LM of the ``granitemoehybrid``
family (HF ``modeling_granitemoehybrid.py``): Mamba-2 layers with a few
attention layers between them, and in EVERY layer a feed-forward of routed
experts plus one shared expert.

With ``m_e = embedding_multiplier``, ``m_r = residual_multiplier``, ``m_a =
attention_multiplier``, ``m_l = logits_scaling``::

    x0     = m_e * E[tok]
    x      = x + m_r * mixer(rmsnorm(x))                 a layer
    x      = x + m_r * (routed(n) + shared(n)),  n = rmsnorm(x)
    logits = (rmsnorm(x_L) @ E^T) / m_l                  tied embedding

RMSNorm epsilon ``rms_norm_eps``; no biases but the conv's.

* attention (where ``layer_types[i] == "attention"``): grouped KV heads,
  causal, no window, **no positional term** (``position_embedding_type:
  nope``; ``rope_theta`` is dead), scores scaled by ``m_a`` and NOT by
  ``head_dim ** -0.5``.
* Mamba-2 mixer (``H = mamba_n_heads`` heads of ``P = mamba_d_head``, ``N =
  mamba_d_state``, ``G = mamba_n_groups``, ``K = mamba_d_conv``): ``[z, xBC,
  dt] = W_in u``; ``xBC_t = silu(b_c + sum_k w_c[k] xBC_{t-K+1+k})`` (depthwise
  causal conv over all ``H P + 2 G N`` channels); ``[x, B, C] = xBC``; ``dt =
  softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)`` one scalar a head;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t`` (``h [H, P, N]``);
  ``y_t = h_t C_t + D x_t``; ``out = W_out rmsnorm_w(y * silu(z))`` (the gate
  first, then ONE RMSNorm over all ``H P`` channels, as the family's
  ``RMSNormGated`` does with one group).
* routed experts: ``s = W_r n`` (``n_router`` outputs); the ``top_k`` largest;
  gates = softmax over THOSE scores; expert ``e``: ``W_out,e (silu(g) * u)``,
  ``[g, u] = W_in,e n``; the result is the gate-weighted sum. No capacity, no
  token dropped. The shared expert is the same gated form on every token,
  ungated, added.

**The chip's share.** The configuration may hold a range of the experts
(``experts_held = [lo, hi]`` of ``num_local_experts_published``): the router
keeps its published width and ``top_k``, pairs routed to absent experts are
left out, and the partial sum (plus the shared expert) goes on to the next
layer, here exactly as in the program. The experts are written plainly: every
held expert on every token, weighted by a gate that is zero where the token
was not routed to it.

**The recurrence twice.** ``ssd_scan`` is the definition, a ``lax.scan`` over
tokens. ``ssd_blocked`` is the blocked sum of the Mamba-2 paper's minimal
listing (Dao and Gu 2024, listing 1: ``segsum``, the diagonal blocks, the
blocks' states, the recurrence between blocks, the off-diagonal blocks).
``tests/test_mamba2.py`` holds the two together to float32 rounding. The
forward uses the blocked form (``mamba_chunk_size`` tokens a block, which
changes no value): the token scan carries ``H P N`` floats a token, ~100 GB
for one 1,408-token request of the configuration in the benchmark.

Departures from the published modelling code, all of them: the conv is
written as ``K`` shifted products, not a ``conv1d`` call; ``time_step_limit``
(0, inf) clamps nothing and is left out; weights are this file's own layout;
the experts' ``input_linear`` is ``[held, d, 2 f]`` with the gate half first.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no state carried between calls, no kernels, no batching. It imports nothing
of the program under test. Every projection, the router and the experts go
through the ``einsum`` it is handed (``control.py`` hands it the int8 one); the
recurrence itself and the attention's softmax do not. ``state_dtype`` is what
``h`` is rounded to at every block border (every token in ``ssd_scan``) and
``router_dtype`` what the router's operands and scores are rounded to: float32
is the model, the controls pass less.

Weight layout: ``embed [V, d]``, ``lnf_g [d]``; every layer ``ln1_g ln2_g
[d]``, ``router [d, n_router]``, ``we_in [held, d, 2 f]``, ``we_out [held, f,
d]``, ``ws_gate ws_up [d, fs]``, ``ws_down [fs, d]``; an attention layer ``wq
[d, Hq, D]``, ``wk wv [d, Hkv, D]``, ``wo [Hq, D, d]``; a Mamba-2 layer ``w_in
[d, 2 HP + 2GN + H]``, ``conv_w [K, HP + 2GN]``, ``conv_b [HP + 2GN]``,
``dt_bias a_log d_skip [H]``, ``norm_g [HP]``, ``w_out [HP, d]``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    n_router = cfg.get("num_local_experts_published", cfg["num_local_experts"])
    lo, hi = cfg.get("experts_held") or (0, cfg["num_local_experts"])
    if hi - lo != cfg["num_local_experts"] or not 0 <= lo < hi <= n_router:
        raise ValueError(
            f"experts_held {lo}..{hi} is not {cfg['num_local_experts']} of "
            f"{n_router} experts")
    return dict(
        d=d, hq=hq, hkv=cfg["num_key_value_heads"], hd=d // hq,
        v=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        heads=heads, p=p, g=g, n=n, k=cfg["mamba_d_conv"], di=heads * p,
        conv=heads * p + 2 * g * n, f=cfg["intermediate_size"],
        fs=cfg["shared_intermediate_size"], n_router=n_router, lo=lo, hi=hi,
        held=hi - lo, top_k=cfg["num_experts_per_tok"],
    )


def layer_types(cfg: dict) -> list:
    """``"attention"`` or ``"mamba"`` for each layer that is run: the first
    ``num_hidden_layers`` of the published list."""
    kinds = list(cfg["layer_types"][: cfg["num_hidden_layers"]])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {"attention", "mamba"}:
        raise ValueError(f"layer_types {kinds}")
    return kinds


def layer_shapes(cfg: dict, kind: str) -> dict:
    s = dims(cfg)
    d, f, fs = s["d"], s["f"], s["fs"]
    shapes = {
        "ln1_g": (d,), "ln2_g": (d,), "router": (d, s["n_router"]),
        "we_in": (s["held"], d, 2 * f), "we_out": (s["held"], f, d),
        "ws_gate": (d, fs), "ws_up": (d, fs), "ws_down": (fs, d)}
    if kind == "attention":
        shapes.update(
            wq=(d, s["hq"], s["hd"]), wk=(d, s["hkv"], s["hd"]),
            wv=(d, s["hkv"], s["hd"]), wo=(s["hq"], s["hd"], d))
    else:
        shapes.update(
            w_in=(d, s["di"] + s["conv"] + s["heads"]),
            conv_w=(s["k"], s["conv"]), conv_b=(s["conv"],),
            dt_bias=(s["heads"],), a_log=(s["heads"],),
            d_skip=(s["heads"],), norm_g=(s["di"],), w_out=(s["di"], d))
    return shapes


#: What ``_draw`` keeps in float32 whatever the served type: the recurrence
#: reads them as float32 quantities (the family keeps them so too).
FLOAT32_WEIGHTS = ("dt_bias", "a_log", "d_skip")


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    """Mamba-2's published initialisation where the recurrence needs it
    (``a_log = log(uniform[1, 16])`` a head, ``d_skip = 1``, ``dt_bias`` the
    inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]); norm
    scales 1 + 0.02 noise; everything else normal at ``std`` (the caller's:
    ``make_weights`` hands the tied embedding a smaller one)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            x = 1.0 + 0.02 * jax.random.normal(k, shape, F32)
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        elif name == "d_skip":
            x = jnp.ones(shape, F32)
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, F32, math.log(1e-3), math.log(1e-1)))
            x = step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
        else:
            x = std * jax.random.normal(k, shape, F32)
        out[name] = x if name in FLOAT32_WEIGHTS else x.astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program per kind
    of layer, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    s = dims(cfg)
    draw = {
        kind: jax.jit(functools.partial(
            _draw, shapes=layer_shapes(cfg, kind), std=std, dtype=dtype))
        for kind in ("attention", "mamba")
    }
    # The tied embedding at std / m_e, so that m_e E[tok] enters the stream at
    # ``std``. At ``std`` itself a token's own row wins the tied head by a wide
    # margin whatever the layers add (12 x 4096 x 0.02^2 = 19.7 against ~2 a
    # layer's output projects onto any row): every greedy token is then its
    # input token, and a comparison of served tokens reads 0 for any fault.
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "lnf_g": (s["d"],)},
        std=std / float(cfg.get("embedding_multiplier", 1.0)), dtype=dtype))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        draw[kind](jax.random.fold_in(key, 1 + i))
        for i, kind in enumerate(layer_types(cfg))
    ]
    return weights


# ------------------------------------------------------------------ forward


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(y, w, einsum, scale: float):
    """Causal grouped attention over ``y [T, d]`` with no positional term,
    scores scaled by ``scale``."""
    t = y.shape[0]
    q = einsum("td,dhk->thk", y, w["wq"])
    k = einsum("td,dhk->thk", y, w["wk"])
    v = einsum("td,dhk->thk", y, w["wv"])
    h, hkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(t, hkv, h // hkv, hd)
    scores = einsum("qgrk,sgk->grqs", qg, k) * scale
    pos = jnp.arange(t)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("grqs,sgk->qgrk", probs, v).reshape(t, h, hd)
    return einsum("thk,hkd->td", att, w["wo"])


def ssd_scan(x, dt, a, b, c, *, state_dtype=F32):
    """The recurrence as it is defined, from a zero state: ``x [T, H, P]``,
    ``dt [T, H]``, ``a [H]``, ``b, c [T, G, N]`` -> ``(y [T, H, P]`` without
    the ``D x`` term, ``h_T [H, P, N])``. ``h`` is rounded to ``state_dtype``
    after every token."""
    heads, p = x.shape[1], x.shape[2]
    rep = heads // b.shape[1]

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        h = (jnp.exp(dt_t * a)[:, None, None] * h.astype(F32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = h.astype(state_dtype)
        return h, jnp.sum(h.astype(F32) * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros((heads, p, b.shape[2]), state_dtype)
    h, ys = jax.lax.scan(step, h0, (x, dt, b, c))
    return ys, h.astype(F32)


def segsum(a):
    """``out[..., i, j] = a[..., j+1] + .. + a[..., i]`` for ``i >= j`` and
    ``-inf`` above the diagonal (the paper's listing)."""
    t = a.shape[-1]
    a = jnp.repeat(a[..., None], t, axis=-1)
    lower = jnp.tril(jnp.ones((t, t), bool), -1)
    out = jnp.cumsum(jnp.where(lower, a, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd_blocked(x, dt, a, b, c, block: int, *, state_dtype=F32):
    """``ssd_scan``'s results as the blocked sum of the Mamba-2 paper's
    minimal listing, blocks of ``block`` tokens (a shorter sequence is one
    block; a length ``block`` does not divide is padded with ``dt = 0``
    tokens, which move nothing). ``h`` is rounded to ``state_dtype`` where it
    crosses a block border."""
    t, heads, p = x.shape
    g, n = b.shape[1], b.shape[2]
    block = min(block, t)
    pad = -t % block
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                       for v in (x, dt, b, c))
    rep = heads // g
    b, c = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
    nb = (t + pad) // block
    x = (x * dt[..., None]).reshape(nb, block, heads, p)
    a = (dt * a).reshape(nb, block, heads).transpose(2, 0, 1)  # [H, nb, L]
    b, c = b.reshape(nb, block, heads, n), c.reshape(nb, block, heads, n)
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. inside the blocks
    y_diag = jnp.einsum(
        "clhn,cshn,hcls,cshp->clhp", c, b, jnp.exp(segsum(a)), x)
    # 2. the state each block adds
    decay_states = jnp.exp(a_cum[:, :, -1:] - a_cum)
    states = jnp.einsum("clhn,hcl,clhp->chpn", b, decay_states, x)
    # 3. the recurrence between the blocks' borders
    def border(h, xs):
        kept, added = xs
        h = (kept[:, None, None] * h.astype(F32) + added).astype(state_dtype)
        return h, h

    h0 = jnp.zeros((heads, p, n), state_dtype)
    h_last, after = jax.lax.scan(
        border, h0, (jnp.exp(a_cum[:, :, -1]).T, states))
    met = jnp.concatenate([h0[None], after[:-1]]).astype(F32)
    # 4. what the state a block met gives its outputs
    y_off = jnp.einsum("clhn,chpn,hcl->clhp", c, met, jnp.exp(a_cum))
    y = (y_diag + y_off).reshape(nb * block, heads, p)[:t]
    return y, h_last.astype(F32)


def mamba2(u, w, *, cfg: dict, eps: float, einsum, state_dtype=F32,
           recurrence=None):
    """The Mamba-2 mixer over ``u [T, d]`` from a zero state: its output and
    the state after the last token, ``h_T [H, P, N]``. ``recurrence`` is
    ``ssd_scan`` or (the default) ``ssd_blocked`` at ``mamba_chunk_size``."""
    s = dims(cfg)
    t, k = u.shape[0], s["k"]
    z, xbc, dt = jnp.split(
        einsum("td,de->te", u, w["w_in"]), [s["di"], s["di"] + s["conv"]],
        axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, s["conv"]), F32), xbc])
    xbc = silu(w["conv_b"] + sum(
        w["conv_w"][i] * padded[i:i + t] for i in range(k)))
    x, b, c = jnp.split(xbc, [s["di"], s["di"] + s["g"] * s["n"]], axis=-1)
    x = x.reshape(t, s["heads"], s["p"])
    b, c = b.reshape(t, s["g"], s["n"]), c.reshape(t, s["g"], s["n"])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = -jnp.exp(w["a_log"])
    if recurrence is None:
        recurrence = functools.partial(
            ssd_blocked, block=cfg.get("mamba_chunk_size", 256))
    y, h = recurrence(x, dt, a, b, c, state_dtype=state_dtype)
    y = (y + w["d_skip"][:, None] * x).reshape(t, s["di"])
    return einsum(
        "te,ed->td", rms_norm(y * silu(z), w["norm_g"], eps), w["w_out"]), h


def router_gates(n, w_router, *, top_k: int, einsum, router_dtype=F32):
    """``[T, n_router]`` gates: a softmax over each token's ``top_k`` largest
    scores at those experts, zero everywhere else."""
    scores = einsum(
        "td,de->te", n.astype(router_dtype).astype(F32),
        w_router.astype(router_dtype).astype(F32))
    scores = scores.astype(router_dtype).astype(F32)
    best, experts = jax.lax.top_k(scores, top_k)
    chosen = jax.nn.softmax(best, axis=-1)
    rows = jnp.arange(n.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, experts].set(chosen)


def routed_experts(n, w, *, cfg: dict, einsum, router_dtype=F32):
    """The held experts' part of the routed layer over ``n [T, d]``: every
    held expert on every token, weighted by its gate (zero where the token
    was not routed to it), an expert at a time. And ``[T, n_router]``: the
    experts, held or not, that each token was routed to."""
    s = dims(cfg)
    routed = router_gates(
        n, w["router"], top_k=s["top_k"], einsum=einsum,
        router_dtype=router_dtype)
    gates = routed[:, s["lo"]:s["hi"]]

    def one(total, xs):
        w_in, w_out, gate = xs
        g, u = jnp.split(einsum("td,df->tf", n, w_in.astype(F32)), 2, axis=-1)
        out = einsum("tf,fd->td", silu(g) * u, w_out.astype(F32))
        return total + gate[:, None] * out, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(n), (w["we_in"], w["we_out"], gates.T))
    return total, routed > 0


def shared_expert(n, w, einsum):
    gated = silu(einsum("td,df->tf", n, w["ws_gate"])) * einsum(
        "td,df->tf", n, w["ws_up"])
    return einsum("tf,fd->td", gated, w["ws_down"])


#: The expert matrices stay in the type they are stored in until
#: ``routed_experts`` upcasts one expert at a time (a layer's 36 in float32
#: at once would be 1.4 GB more).
_KEPT_AS_STORED = ("we_in", "we_out")


def block(x, w, *, kind: str, cfg: dict, einsum=jnp.einsum, state_dtype=F32,
          router_dtype=F32, recurrence=None):
    """One layer over ``x [T, d]`` (float32): its output, a Mamba-2 layer's
    final state (``None`` from an attention layer), and the experts each
    token was routed to (``routed_experts``)."""
    w = {k: v if k in _KEPT_AS_STORED else v.astype(F32) for k, v in w.items()}
    eps, m_r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    y = rms_norm(x, w["ln1_g"], eps)
    h_last = None
    if kind == "attention":
        mixed = attention(y, w, einsum, cfg["attention_multiplier"])
    else:
        mixed, h_last = mamba2(
            y, w, cfg=cfg, eps=eps, einsum=einsum, state_dtype=state_dtype,
            recurrence=recurrence)
    x = x + m_r * mixed
    n = rms_norm(x, w["ln2_g"], eps)
    fed, routed = routed_experts(
        n, w, cfg=cfg, einsum=einsum, router_dtype=router_dtype)
    return x + m_r * (fed + shared_expert(n, w, einsum)), h_last, routed


def _cfg_key(cfg: dict) -> str:
    return json.dumps(
        {k: v for k, v in cfg.items() if k != "assumed"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, einsum, state_dtype, router_dtype, recurrence):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return cfg["embedding_multiplier"] * table[tokens].astype(F32)

    def head(table, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,vd->rv", y, table.astype(F32)) / cfg["logits_scaling"]

    layers = {
        kind: jax.jit(functools.partial(
            block, kind=kind, cfg=cfg, einsum=einsum, state_dtype=state_dtype,
            router_dtype=router_dtype, recurrence=recurrence))
        for kind in ("attention", "mamba")
    }
    return jax.jit(embed), layers, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, state_dtype, router_dtype,
                    recurrence):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    Mamba-2 layers' final states, every layer's routing, and the head's
    program."""
    embed, layers, head = _programs(
        _cfg_key(cfg), einsum, jnp.dtype(state_dtype), jnp.dtype(router_dtype),
        recurrence)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    states, routing = [], []
    for kind, w in zip(layer_types(cfg), weights["layers"]):
        x, h_last, routed = layers[kind](x, w)
        routing.append(routed)
        if h_last is not None:
            states.append(h_last)
    return x, states, routing, head


def final_states(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum,
                 state_dtype=F32, router_dtype=F32, recurrence=None):
    """The state of every Mamba-2 layer after the whole of ``tokens``
    (unpadded: the recurrence runs over every position it is given), float32
    ``[mamba layers, H, P, N]``."""
    with jax.default_matmul_precision("highest"):
        _, states, _, _ = _through_layers(
            cfg, weights, list(tokens), einsum, state_dtype, router_dtype,
            recurrence)
    return jnp.stack(states)


def routing_at(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum,
               state_dtype=F32, router_dtype=F32, recurrence=None):
    """The experts this reference routes each of ``tokens`` to, layer by
    layer, on its own activations: bool ``[layers, T, n_router]``, ``top_k``
    true a token and layer, held or not."""
    with jax.default_matmul_precision("highest"):
        _, _, routing, _ = _through_layers(
            cfg, weights, list(tokens), einsum, state_dtype, router_dtype,
            recurrence)
    return jnp.stack(routing)


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0, state_dtype=F32,
              router_dtype=F32, recurrence=None):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of ONE token
    sequence ``tokens [T]`` (row ``p`` predicts token ``p + 1``). Layer by
    layer, each layer's weights upcast inside its own program. ``pad_*_to``
    pad the sequence (at its end: neither causal attention nor the
    recurrence carries anything backwards, and a token's experts do not look
    at other tokens) and the rows, so that one compiled program serves
    requests of every length."""
    tokens, rows = list(tokens), list(rows)
    if not rows:
        raise ValueError("no row to score")
    n = len(rows)
    tokens += [0] * (pad_tokens_to - len(tokens))
    rows += [rows[-1]] * (pad_rows_to - n)
    with jax.default_matmul_precision("highest"):
        x, _, _, head = _through_layers(
            cfg, weights, tokens, einsum, state_dtype, router_dtype,
            recurrence)
        return head(weights["embed"], weights["lnf_g"], x,
                    jnp.asarray(rows, jnp.int32))[:n]


# ------------------------------------------------------------------- counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against: per kind of mixer, in the
    shared expert and the router of a layer, in ONE routed expert, and in
    the output head."""
    s = dims(cfg)
    d = s["d"]
    return {
        "mamba": d * (s["di"] + s["conv"] + s["heads"]) + s["di"] * d,
        "attention": 2 * d * s["hq"] * s["hd"] + 2 * d * s["hkv"] * s["hd"],
        "shared": 3 * d * s["fs"], "router": d * s["n_router"],
        "expert": 3 * d * s["f"], "head": d * s["v"],
    }


def _layer_counts(cfg: dict) -> tuple:
    kinds = layer_types(cfg)
    return kinds.count("mamba"), kinds.count("attention")


def held_parameters(cfg: dict) -> int:
    """Every parameter this share holds: the matrices, the norms' scales,
    the conv and the recurrence's per-head vectors."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_mamba, n_attn = _layer_counts(cfg)
    every = p["shared"] + p["router"] + s["held"] * p["expert"] + 2 * s["d"]
    small = s["conv"] * (s["k"] + 1) + 3 * s["heads"] + s["di"]
    return (n_mamba * (p["mamba"] + small + every)
            + n_attn * (p["attention"] + every) + p["head"] + s["d"])


def state_bytes_per_slot(cfg: dict, conv_bytes: int = 2) -> int:
    """What one sequence keeps between tokens, over the Mamba-2 layers: the
    float32 state ``[H, P, N]`` and the conv tail in the served type."""
    s = dims(cfg)
    n_mamba, _ = _layer_counts(cfg)
    return n_mamba * (
        s["heads"] * s["p"] * s["n"] * 4 + (s["k"] - 1) * s["conv"] * conv_bytes)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = dims(cfg)
    _, n_attn = _layer_counts(cfg)
    return 2 * n_attn * s["hkv"] * s["hd"] * bytes_per_value


def scan_flops_per_token(cfg: dict) -> float:
    """The conv and the recurrence of ONE Mamba-2 layer for one token: K taps
    a channel; per state element the decay's product, the input's product,
    the update's multiply-add and the output's multiply-add (the decay's
    exponential is one a head). Vector work as the recurrence; the blocked
    evaluation trades some of it for matrix products."""
    s = dims(cfg)
    return 2.0 * s["k"] * s["conv"] + 6.0 * s["heads"] * s["p"] * s["n"]


def scan_io_bytes_per_token(cfg: dict) -> int:
    """What the recurrence reads and writes for one token besides the state,
    over the Mamba-2 layers: ``x`` and ``y`` over ``H P``, ``dt`` over ``H``,
    ``B`` and ``C`` over ``G N``, float32."""
    s = dims(cfg)
    n_mamba, _ = _layer_counts(cfg)
    return n_mamba * 4 * (2 * s["di"] + s["heads"] + 2 * s["g"] * s["n"])


def expert_flops(cfg: dict, pairs_held: float) -> float:
    """FLOPs of the held experts' two products for ``pairs_held`` routed
    (token, expert) pairs, over whatever layers they were counted in."""
    return 2.0 * matmul_params(cfg)["expert"] * pairs_held


def expert_min_bytes(cfg: dict, experts_hit: float, pairs_held: float,
                     bytes_per_param: int = 2) -> float:
    """Least bytes the held experts' products have to move: the weights of
    every expert a token reached, once (``experts_hit`` summed over layers
    and programs: a program that reaches an expert reads it), and each
    pair's input and output row in the served type."""
    s = dims(cfg)
    return (bytes_per_param * matmul_params(cfg)["expert"] * experts_hit
            + 2.0 * bytes_per_param * s["d"] * pairs_held)


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through this share of the
    model when the attention layers' queries attend to ``context_tokens``
    keys in all and ``logits_rows`` go through the head. The routed pairs on
    held experts are taken at their expectation under even routing,
    ``top_k held / n_router`` a token and layer."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_mamba, n_attn = _layer_counts(cfg)
    pairs = s["top_k"] * s["held"] / s["n_router"]
    every = p["shared"] + p["router"] + pairs * p["expert"]
    dense = 2.0 * new_tokens * (
        n_mamba * (p["mamba"] + every) + n_attn * (p["attention"] + every))
    attn = 4.0 * n_attn * s["hq"] * s["hd"] * context_tokens
    scan = n_mamba * scan_flops_per_token(cfg) * new_tokens
    return dense + attn + scan + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every weight held here once (the tied embedding is
    the head; at 64 rows a step reaches every held expert); the state of
    every decode row and of every prefill chunk read and written; the KV
    entries read, and one written per new token."""
    p = matmul_params(cfg)
    s = dims(cfg)
    n_mamba, n_attn = _layer_counts(cfg)
    every = p["shared"] + p["router"] + s["held"] * p["expert"]
    weights = (n_mamba * (p["mamba"] + every)
               + n_attn * (p["attention"] + every) + p["head"])
    states = decode_rows + prefill_chunks
    kv = kv_bytes_per_token(cfg) * (kv_tokens_read + decode_rows + prefill_tokens)
    return (bytes_per_param * weights
            + 2.0 * state_bytes_per_slot(cfg) * states + kv)

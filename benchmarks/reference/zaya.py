"""Plain reference for a decoder-only LM of the ``zaya`` family
(Zyphra/ZAYA1-8B ``config.json``; Compressed Convolutional Attention,
arXiv:2510.04476; the ZAYA1 technical report, arXiv:2511.17127): every layer a
CCA sublayer and a top-1 expert sublayer whose router is a small network that
carries its state from layer to layer.

With ``H`` query heads on ``G`` KV heads of ``D = head_dim`` (``r = H / G``;
``H D`` is NOT the hidden size), ``C = (H + G) D`` channels in ``H + G`` heads,
RMSNorm epsilon ``rms_norm_eps``, no biases on the projections, for layer
``l`` over positions ``t`` (everything before position 0 is zero)::

    x      = E[tok]
    n      = rmsnorm(x)
    u_t    = [W_q n_t ; W_k n_t]                                    d -> C
    a_t    = w0[0] * u_{t-1} + w0[1] * u_t + b0       depthwise, cca_time0 taps
    c_t^h  = W1[0]^h a_{t-1}^h + W1[1]^h a_t^h + b1^h  a [D, D] matrix a tap
                                                       and head, cca_time1 taps
    m_q    = (q~_h + k~_g) / 2,   m_k = (mean_{h in g} q~_h + k~_g) / 2
                                  q~, k~ the two parts of u_t; g = h // r
    q, k   = c[q] + m_q,  c[k] + m_k
    q, k   = sqrt(D) q / |q|_2,  sqrt(D) exp(tau_g) k / |k|_2       a head
    q, k   = R(q, t), R(k, t)      over the first partial_rotary_factor D
    v_t    = [W_v1 n_t ; W_v2 n_{t-1}]      concatenated, then split in G heads
    o_h(t) = sum_{u<=t} softmax_u(q_h(t) . k_g(u) / sqrt(D)) v_g(u)
    x      = a1 * x + b1 * (W_o o)                     learned [d] vectors
    n'     = rmsnorm(x)
    r_l    = W_d n' + gamma_l r_{l-1}                  router_hidden wide; the
                                                       carry is the MIXED r
    s      = W_3 gelu(W_2 gelu(W_1 rmsnorm(r_l)))      num_experts scores
    p      = softmax(s);  e = argmax(p + bias_l)       the bias for the CHOICE
    x      = a2 * x + b2 * p_e E_e(n')                 E_e a gated SiLU of
                                                       moe_intermediate_size
    logits = rmsnorm(x_L) E^T                          tied

What a serving system caches a token and layer is ``k`` after the rotation and
``v`` after the shift: ``2 G D`` numbers (1,024 B in bf16 at the published
sizes); what it keeps a SEQUENCE and layer is the last token's ``u``, ``a``
and ``W_v2 n`` (``2 C + G D / 2`` numbers). ``probe_at`` returns the former
for the comparison of the program's pages.

Everything the published ``config.json`` has no key for is listed under the
configuration file's ``assumed`` with the line here that it changes: the
convolutions' biases, the mean taken of ``u`` itself and added after the
convolutions, ``exp(tau)`` on ``k``, head 0 of ``v`` this token's and head 1
the previous one's, the FIRST dimensions rotated (paired by halves inside
them), the L2 norms' ``1e-6`` inside the root, the router's norm before its
first matrix, exact GELU, two hidden matrices, a scalar ``gamma``, the
element-wise residual scales, pre-norm blocks, no skip expert, no sliding
layer. Weights are this file's own layout and draw (``DRAWS``).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no kernels, no batching. It imports nothing of the program under test. Every
projection, the second convolution's matrices, the router's matrices, the
experts and the attention's two products go through the ``einsum`` it is
handed (``control.py`` hands it the int8 one); the depthwise taps, the
softmaxes, the GELU and the norms do not. Attention is computed a block of
``QUERY_BLOCK`` queries at a time and the head a block of ``ROW_BLOCK`` rows at
a time, which changes no value.

Weight layout: ``embed [V, d]``, ``lnf_g [d]``; every layer ``ln1_g ln2_g
[d]``, ``wq [d, H D]``, ``wk wv [d, G D]``, ``wo [H, D, d]``, ``conv0_w [K0,
C]``, ``conv0_b [C]``, ``conv1_w [K1, H + G, D, D]`` (``[tap, head, in,
out]``), ``conv1_b [C]``, ``tau [G]`` (float32), ``res_attn_a res_attn_b
res_mlp_a res_mlp_b [d]`` (float32), ``rd [d, R]``, ``gamma []`` (float32),
``rn_g [R]``, ``r1 r2 [R, R]``, ``r3 [R, E]``, ``router_bias [E]`` (float32),
``we_in [E, d, 2 f]`` (gate half first), ``we_out [E, f, d]``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 128  # queries a block of the attention: [H, 128, T] scores
ROW_BLOCK = 256  # rows a block of the head: [256, V] logits
L2_EPS = 1e-6  # inside the root of the L2 norms

#: How the weights that are no plain ``initializer_range`` matrix are drawn
#: (the configuration's ``assumed.weights`` says why): name -> (mean, std).
DRAWS = {
    "conv0_w": (0.0, 0.5), "conv0_b": (0.0, 0.1), "conv1_b": (0.0, 0.1),
    "tau": (0.0, 0.0), "gamma": (0.5, 0.1), "router_bias": (0.0, 0.02),
}
#: The router network's three matrices at ``ROUTER_GAINS / sqrt(router_hidden)``.
#: A trained router is balanced (that is what its bias is trained for); seeded
#: matrices are not: at gains of 1.6 the GELUs' positive means give some
#: experts a constant lead and the busiest expert of 16 takes 6 times the mean
#: load, the idlest nothing. With the two hidden matrices small the GELUs run
#: near their linear part (busiest 1.9 times the mean, idlest 0.5), and the
#: last matrix large spreads a token's scores by ~3, so that the tokens' own
#: scores decide the choice and the bias tips ~2% of them (NumPy at the
#: published widths, PR 49).
ROUTER_GAINS = {"r1": 0.25, "r2": 0.25, "r3": 200.0}
_FLOAT32 = ("tau", "gamma", "router_bias", "res_attn_a", "res_attn_b",
            "res_mlp_a", "res_mlp_b")


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    if (lo, hi) != (0, cfg["num_experts"]):
        raise ValueError("every expert is held: no share is written")
    if cfg["num_experts_per_tok"] != 1:
        raise ValueError("only the one best expert is written")
    if cfg.get("sliding_window"):
        raise ValueError("no sliding layer is written")
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) != {"hybrid"}:
        raise ValueError("layer_types names every layer 'hybrid'")
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    rope = cfg["rope_parameters"]["hybrid"]
    return dict(
        d=cfg["hidden_size"], h=h, g=g, dh=dh, heads=h + g, c=(h + g) * dh,
        half=g * dh // 2, v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], f=cfg["moe_intermediate_size"],
        e=cfg["num_experts"], rh=cfg["router_hidden_size"],
        k0=cfg["cca_time0"], k1=cfg["cca_time1"],
        rotary=int(round(dh * rope["partial_rotary_factor"])),
        theta=float(rope["rope_theta"]),
    )


def layer_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, dh, rh = s["d"], s["dh"], s["rh"]
    return {
        "ln1_g": (d,), "ln2_g": (d,), "wq": (d, s["h"] * dh),
        "wk": (d, s["g"] * dh), "wv": (d, s["g"] * dh),
        "wo": (s["h"], dh, d), "conv0_w": (s["k0"], s["c"]),
        "conv0_b": (s["c"],), "conv1_w": (s["k1"], s["heads"], dh, dh),
        "conv1_b": (s["c"],), "tau": (s["g"],), "res_attn_a": (d,),
        "res_attn_b": (d,), "res_mlp_a": (d,), "res_mlp_b": (d,),
        "rd": (d, rh), "gamma": (), "rn_g": (rh,), "r1": (rh, rh),
        "r2": (rh, rh), "r3": (rh, s["e"]), "router_bias": (s["e"],),
        "we_in": (s["e"], d, 2 * s["f"]), "we_out": (s["e"], s["f"], d),
    }


def _draw(key, shapes: dict, std: float, dtype, own: dict) -> dict:
    """Norm and residual scales 1 + 0.02 noise; ``DRAWS`` as it says; the
    matrices in ``own`` at the std it gives; everything else normal at
    ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        noise = jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if name.endswith("_g") or name.startswith("res_"):
            x = 1.0 + 0.02 * noise
        elif name in DRAWS:
            x = DRAWS[name][0] + DRAWS[name][1] * noise
        else:
            x = own.get(name, std) * noise
        out[name] = x if name in _FLOAT32 else x.astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program for the
    layers, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    s = dims(cfg)
    # Each head's matrices a tap keep ``a``'s size: 1 / sqrt(K1 D).
    own = {"conv1_w": (s["k1"] * s["dh"]) ** -0.5}
    own.update({k: g * s["rh"] ** -0.5 for k, g in ROUTER_GAINS.items()})
    draw = jax.jit(functools.partial(
        _draw, shapes=layer_shapes(cfg), std=std, dtype=dtype, own=own))
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "lnf_g": (s["d"],)},
        std=float(cfg["assumed"].get("embedding_std", std)), dtype=dtype,
        own={}))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        draw(jax.random.fold_in(key, 1 + i)) for i in range(s["layers"])]
    return weights


# ------------------------------------------------------------------ the model


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta: float, rotary: int):
    """``R`` over ``x [T, heads, D]`` at positions ``0..T-1``: the first
    ``rotary`` dimensions of a head, paired by halves inside them."""
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = (jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def delayed(x, by: int):
    """``x [T, ..]`` ``by`` positions later, zeros before position 0."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]], axis=0)


def cca(n, w, *, cfg: dict, einsum):
    """The CCA sublayer over ``n [T, d]``: ``W_o o``, and what a cache would
    hold of every position, ``[k, v]`` ``[2, T, G, D]``."""
    s = dims(cfg)
    t, h, g, dh, heads = n.shape[0], s["h"], s["g"], s["dh"], s["heads"]
    u = jnp.concatenate(
        [einsum("td,dc->tc", n, w["wq"]), einsum("td,dc->tc", n, w["wk"])], -1)
    a = w["conv0_b"] + sum(
        w["conv0_w"][i] * delayed(u, s["k0"] - 1 - i) for i in range(s["k0"]))
    a_heads = a.reshape(t, heads, dh)
    c = w["conv1_b"].reshape(heads, dh) + sum(
        einsum("thk,hkj->thj", delayed(a_heads, s["k1"] - 1 - i),
               w["conv1_w"][i])
        for i in range(s["k1"]))
    q_raw = u[:, : h * dh].reshape(t, g, h // g, dh)
    k_raw = u[:, h * dh:].reshape(t, g, 1, dh)
    q = c[:, :h] + ((q_raw + k_raw) / 2).reshape(t, h, dh)
    k = c[:, h:] + (
        (jnp.mean(q_raw, axis=2, keepdims=True) + k_raw) / 2).reshape(t, g, dh)
    q = l2_norm(q) * dh**0.5
    k = l2_norm(k) * dh**0.5 * jnp.exp(w["tau"])[:, None]
    q, k = rope(q, s["theta"], s["rotary"]), rope(k, s["theta"], s["rotary"])
    vv = einsum("td,dc->tc", n, w["wv"])
    v = jnp.concatenate(
        [vv[:, : s["half"]], delayed(vv[:, s["half"]:], 1)], -1
    ).reshape(t, g, dh)

    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    qs = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, qb, g, h // g, dh)
    keys = jnp.arange(t)

    def one(xs):
        qx, start = xs  # [qb, G, r, D]
        scores = einsum("qgrk,ugk->grqu", qx, k) * dh**-0.5
        seen = keys[None, :] <= (start + jnp.arange(qb))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("grqu,ugk->qgrk", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (qs, jnp.arange(0, t + pad, qb)))
    out = out.reshape(t + pad, h, dh)[:t]
    return einsum("thk,hkd->td", out, w["wo"]), jnp.stack([k, v])


def router(n, w, carry, *, cfg: dict, einsum):
    """``(p [T, E], the chosen expert [T], the carry r_l [T, R])``."""
    r = einsum("td,dr->tr", n, w["rd"]) + w["gamma"] * carry
    y = rms_norm(r, w["rn_g"], cfg["rms_norm_eps"])
    for name in ("r1", "r2"):
        y = jax.nn.gelu(einsum("tr,rs->ts", y, w[name]), approximate=False)
    p = jax.nn.softmax(einsum("tr,re->te", y, w["r3"]), axis=-1)
    return p, jnp.argmax(p + w["router_bias"], axis=-1), r


def experts(n, w, carry, *, cfg: dict, einsum):
    """The expert sublayer over ``n [T, d]``: every expert on every token,
    weighted by its gate (zero where the token did not choose it), an expert
    at a time. And bool ``[T, E]``, the expert each token chose, and the
    router's carry."""
    p, chosen, carry = router(n, w, carry, cfg=cfg, einsum=einsum)
    routed = jax.nn.one_hot(chosen, p.shape[-1], dtype=bool)
    gates = jnp.where(routed, p, 0.0)

    def one(total, xs):
        w_in, w_out, gate = xs
        g, u = jnp.split(einsum("td,df->tf", n, w_in.astype(F32)), 2, axis=-1)
        out = einsum("tf,fd->td", silu(g) * u, w_out.astype(F32))
        return total + gate[:, None] * out, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(n), (w["we_in"], w["we_out"], gates.T))
    return total, routed, carry


_KEPT_AS_STORED = ("we_in", "we_out")


def block(x, carry, w, *, cfg: dict, einsum=jnp.einsum):
    """One layer over ``x [T, d]`` (float32) and the previous layer's router
    carry: its output, its carry, ``[k, v]`` ``[2, T, G, D]`` and the expert
    each token chose ``[T, E]``."""
    w = {k: v if k in _KEPT_AS_STORED else v.astype(F32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    mixed, kv = cca(rms_norm(x, w["ln1_g"], eps), w, cfg=cfg, einsum=einsum)
    x = w["res_attn_a"] * x + w["res_attn_b"] * mixed
    fed, routed, carry = experts(
        rms_norm(x, w["ln2_g"], eps), w, carry, cfg=cfg, einsum=einsum)
    return w["res_mlp_a"] * x + w["res_mlp_b"] * fed, carry, kv, routed


def _cfg_key(cfg: dict) -> str:
    return json.dumps(
        {k: v for k, v in cfg.items() if k != "assumed"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, einsum):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(table, g, x, rows):
        """``[len(rows), V]``, ``ROW_BLOCK`` rows at a time (the rows are
        padded to whole blocks and cut again)."""
        y = rms_norm(x[rows], g.astype(F32), eps)
        n = y.shape[0]
        rb = min(ROW_BLOCK, n)
        blocks = jnp.pad(y, [(0, -n % rb), (0, 0)]).reshape(-1, rb, y.shape[1])
        table = table.astype(F32)
        out = jax.lax.map(lambda b: einsum("rd,vd->rv", b, table), blocks)
        return out.reshape(-1, table.shape[0])[:n]

    layer = jax.jit(functools.partial(block, cfg=cfg, einsum=einsum))
    return jax.jit(embed), layer, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, keep_kv=()):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    ``[k, v]`` of the layers in ``keep_kv``, every layer's routing, and the
    head's program."""
    embed, layer, head = _programs(_cfg_key(cfg), einsum)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    carry = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), F32)
    n = len(weights["layers"])
    keep = {i % n for i in keep_kv}
    kept, routing = {}, []
    for i, w in enumerate(weights["layers"]):
        x, carry, kv, routed = layer(x, carry, w)
        if i in keep:
            kept[i] = kv
        routing.append(routed)
    return x, [kept[i % n] for i in keep_kv], routing, head


def routing_at(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """The expert this reference routes each of ``tokens`` to, layer by layer,
    on its own activations: bool ``[layers, T, E]``, one true a token and
    layer."""
    with jax.default_matmul_precision("highest"):
        _, _, routing, _ = _through_layers(cfg, weights, list(tokens), einsum)
    return jnp.stack(routing)


def probe_at(cfg: dict, weights: dict, tokens, layers=(0, -1), *,
             einsum=jnp.einsum):
    """What a cache would hold of every position of ``tokens`` in the given
    layers (``[k, v]``, float32 ``[len(layers), 2, T, G, D]``), and
    ``routing_at``'s ``[layers, T, E]``, from ONE pass."""
    with jax.default_matmul_precision("highest"):
        _, kept, routing, _ = _through_layers(
            cfg, weights, list(tokens), einsum, keep_kv=tuple(layers))
    return jnp.stack(kept), jnp.stack(routing)


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of ONE token
    sequence ``tokens [T]`` (row ``p`` predicts token ``p + 1``). Layer by
    layer, each layer's weights upcast inside its own program; the head a
    block of rows at a time (3,072 positions x 262,272 float32 logits are 3.2
    GB whole). ``pad_*_to`` pad the sequence (at its end: everything here is
    causal, and a token's expert does not look at other tokens) and the rows,
    so that one compiled program serves requests of every length."""
    tokens, rows = list(tokens), list(rows)
    if not rows:
        raise ValueError("no row to score")
    n = len(rows)
    tokens += [0] * (pad_tokens_to - len(tokens))
    rows += [rows[-1]] * (pad_rows_to - n)
    with jax.default_matmul_precision("highest"):
        x, _, _, head = _through_layers(cfg, weights, tokens, einsum)
        return head(weights["embed"], weights["lnf_g"], x,
                    jnp.asarray(rows, jnp.int32))[:n]


def forward(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """Float32 logits ``[T, V]`` at every position: the CPU tests' full
    forward."""
    return logits_at(cfg, weights, tokens, range(len(tokens)), einsum=einsum)


# ------------------------------------------------------------------ the counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against: in a layer's CCA sublayer (the
    four projections and the second convolution's matrices), in its router,
    in ONE expert, and in the tied head."""
    s = dims(cfg)
    d, dh, rh = s["d"], s["dh"], s["rh"]
    return {
        "cca": (2 * d * s["h"] * dh + 2 * d * s["g"] * dh
                + s["k1"] * s["heads"] * dh * dh),
        "router": d * rh + 2 * rh * rh + rh * s["e"],
        "expert": 3 * d * s["f"],
        "head": d * s["v"],
    }


def held_parameters(cfg: dict) -> int:
    """Every parameter held: the matrices, the depthwise taps, the biases, the
    norms' and residuals' scales, ``tau``, ``gamma``, the routers' biases and
    the tied embedding with the final norm."""
    s = dims(cfg)
    p = matmul_params(cfg)
    small = (s["k0"] * s["c"] + 2 * s["c"] + s["g"] + 6 * s["d"] + 1
             + s["rh"] + s["e"])
    return (s["layers"] * (p["cca"] + p["router"] + s["e"] * p["expert"]
                           + small)
            + p["head"] + s["d"])


def kv_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """What the architecture caches a token and layer: ``k`` and ``v`` on
    ``G`` heads of ``D`` (1,024 B in bf16 at the published sizes)."""
    s = dims(cfg)
    return 2 * s["g"] * s["dh"] * bytes_per_value


def state_bytes_per_slot_layer(cfg: dict, bytes_per_value: int = 4) -> int:
    """What a sequence keeps a layer beside its pages: the last ``K0 - 1``
    ``u``, the last ``K1 - 1`` ``a`` and the last ``W_v2 n`` (10,752 B in
    float32 at the published sizes)."""
    s = dims(cfg)
    return ((s["k0"] - 1 + s["k1"] - 1) * s["c"] + s["half"]) * bytes_per_value


def experts_reached(cfg: dict, tokens: float) -> float:
    """Experts of a layer that ``tokens`` tokens reach, at their expectation
    under even routing."""
    s = dims(cfg)
    return s["e"] * (1.0 - (1.0 - 1.0 / s["e"]) ** tokens)


def expert_flops(cfg: dict, pairs_held: float) -> float:
    """FLOPs of the experts' two products for ``pairs_held`` routed (token,
    expert) pairs, over whatever layers they were counted in."""
    return 2.0 * matmul_params(cfg)["expert"] * pairs_held


def expert_min_bytes(cfg: dict, experts_hit: float, pairs_held: float,
                     bytes_per_param: int = 2) -> float:
    """Least bytes the experts' products have to move: the weights of every
    expert a token reached, once (``experts_hit`` summed over layers and
    programs: a program that reaches an expert reads it), and each pair's
    input and output row in the served type."""
    s = dims(cfg)
    return (bytes_per_param * matmul_params(cfg)["expert"] * experts_hit
            + 2.0 * bytes_per_param * s["d"] * pairs_held)


def decode_kv_min_bytes(cfg: dict, visible_tokens: float,
                        bytes_per_value: int = 2) -> float:
    """Least bytes the layers' decode attention has to read, ALL of them:
    ``k`` and ``v`` of the tokens its rows see (``visible_tokens``: a layer's
    sum over the rows of ``pos + 1``), once a layer."""
    return (float(visible_tokens) * kv_bytes_per_token_layer(
        cfg, bytes_per_value) * dims(cfg)["layers"])


def decode_kv_flops(cfg: dict, visible_tokens: float) -> float:
    """FLOPs of the layers' decode attention, all of them, over
    ``visible_tokens`` (query, key) pairs a layer."""
    s = dims(cfg)
    return 4.0 * s["h"] * s["dh"] * float(visible_tokens) * s["layers"]


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through the model when their
    queries attend to ``context_tokens`` keys in all and ``logits_rows`` go
    through the head: one expert a token."""
    s = dims(cfg)
    p = matmul_params(cfg)
    dense = 2.0 * new_tokens * s["layers"] * (
        p["cca"] + p["router"] + p["expert"])
    attn = 4.0 * s["h"] * s["dh"] * s["layers"] * context_tokens
    return dense + attn + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every weight a token of the step reaches, ONCE (the
    experts at ``experts_reached``; the head only where a row is decoded: a
    prefill piece samples nothing; the embedding's rows); ``k`` and ``v`` of
    the cached tokens read (``kv_tokens_read``, the SUM of the rows'
    contexts) and the new tokens' writes, at 1,024 B a token and layer; and
    the slot state of every decoded row and of every prefill piece, in and
    out, in every layer."""
    s = dims(cfg)
    p = matmul_params(cfg)
    new = decode_rows + prefill_tokens
    weights = (
        s["layers"] * (p["cca"] + p["router"]
                       + experts_reached(cfg, new) * p["expert"])
        + (p["head"] if decode_rows else 0) + new * s["d"])
    states = 2.0 * state_bytes_per_slot_layer(cfg) * s["layers"] * (
        decode_rows + prefill_chunks)
    return (bytes_per_param * weights + states
            + kv_bytes_per_token_layer(cfg) * s["layers"]
            * (float(kv_tokens_read) + new))

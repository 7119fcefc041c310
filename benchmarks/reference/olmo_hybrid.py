"""Plain reference for a hybrid decoder-only LM of the ``olmo_hybrid`` family:
gated delta-rule linear-attention layers (Yang, Kautz, Hatamizadeh, "Gated
Delta Networks", arXiv:2412.06464) with full-attention layers between them,
``layer_types`` naming each layer ``linear_attention`` or ``full_attention``.

Every layer, with the Olmo 2 / Olmo 3 block (the norm on each sublayer's
OUTPUT, none on its input): ``x = x + rmsnorm(mixer(x))``; ``x = x +
rmsnorm(W_down(silu(W_gate x) * (W_up x)))``. Final RMSNorm; logits from an
untied head.

* ``full_attention``: no biases, ``num_key_value_heads`` KV heads, causal, an
  RMSNorm over the WHOLE query and the whole key projection before the heads
  are split (QK-norm), **no rotary or other positional term** (the published
  ``rope_parameters.rope_theta`` is null), no window.
* ``linear_attention``, a token ``x`` at a time, head ``h`` of ``H``::

      q~, k~, v~ = W_q x, W_k x, W_v x
      [q', k', v'] = silu(conv_K([q~, k~, v~]))   depthwise causal, no bias
      q = q' / sqrt(|q'|^2 + 1e-6) * d_k^-1/2,  k = k' / sqrt(|k'|^2 + 1e-6)
      beta  = 2 sigmoid(W_b x)            (2: linear_allow_neg_eigval)
      alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))
      S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
      y   = rmsnorm_{d_v}(o_t) * silu(W_g x)_h,   out = W_o [y_1 .. y_H]

  **The recurrence is run exactly as written**, a ``lax.scan`` over tokens
  from a zero state: ``S`` is decayed, ``S^T k`` is read from the decayed
  state, the rank-one term is added, and ``o`` is read from the new state. No
  block form, no regrouping: it shares nothing with the program's
  ``ops/linear_attention.py``.

Departures from the published description, each an assumption the
configuration file lists (``assumed``): (a) no positional term; (b) the
block's norm placement and QK-norm by the Olmo 2 / Olmo 3 convention, which
``config.json`` does not state; (c) the convolution without bias, SiLU after
it, over q, k and v separately (one depthwise convolution over their
concatenation is the same thing); (d) the output gate as ``rmsnorm(o) *
silu(W_g x)`` a head with one scale vector of ``d_v``; (e) the initialisation
below; (f) the float32 state.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no state carried between calls, no kernels, no batching. It imports nothing
of the program under test. Every projection goes through the ``einsum`` it is
handed (``control_linear_hybrid.py`` hands it ``control.py``'s int8 one); the
recurrence itself does not.

It also makes the weights from the seed, on the device, in the type they are
served in, and holds the counts the roofline readers use (``serve_flops``,
``serve_min_bytes``, ``state_bytes_per_slot``, ``gdn_step_state_bytes``,
``gdn_blocks_flops``, ``gdn_blocks_min_bytes``). ``final_states`` gives the
linear layers' states after a sequence, for the comparison of the program's.

Weight layout (this file's own): ``embed [V, d]``, ``w_head [d, V]``, ``lnf_g
[d]``; every layer ``ln1_g ln2_g [d]`` (the norms of the mixer's and of the
MLP's output), ``w_gate w_up [d, F]``, ``w_down [F, d]``; a full layer ``wq [d,
H, D]``, ``wk wv [d, Hkv, D]``, ``wo [H, D, d]``, ``qn_g [H, D]``, ``kn_g [Hkv,
D]``; a linear layer ``w_q w_k [d, H d_k]``, ``w_v w_g [d, H d_v]``, ``conv_w
[K, 2 H d_k + H d_v]``, ``w_a w_b [d, H]``, ``a_log dt_bias [H]``, ``on_g
[d_v]``, ``w_o [H d_v, d]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
KINDS = ("linear_attention", "full_attention")
L2_EPS = 1e-6


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
        f=cfg["intermediate_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], lh=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        k=cfg["linear_conv_kernel_dim"],
    )


def layer_types(cfg: dict) -> list:
    """The published name of each layer that is run."""
    kinds = list(cfg["layer_types"][: cfg["num_hidden_layers"]])
    if set(kinds) - set(KINDS):
        raise ValueError(f"unknown layer types {set(kinds) - set(KINDS)}")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("key heads shared by value heads are not written here")
    return kinds


def layer_shapes(cfg: dict, kind: str) -> dict:
    s = dims(cfg)
    d, f, lh, dk, dv = s["d"], s["f"], s["lh"], s["dk"], s["dv"]
    shapes = {"ln1_g": (d,), "ln2_g": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    if kind == "full_attention":
        shapes.update(
            wq=(d, s["h"], s["hd"]), wk=(d, s["hkv"], s["hd"]),
            wv=(d, s["hkv"], s["hd"]), wo=(s["h"], s["hd"], d),
            qn_g=(s["h"], s["hd"]), kn_g=(s["hkv"], s["hd"]))
    else:
        shapes.update(
            w_q=(d, lh * dk), w_k=(d, lh * dk), w_v=(d, lh * dv),
            w_g=(d, lh * dv), conv_w=(s["k"], lh * (2 * dk + dv)),
            w_a=(d, lh), w_b=(d, lh), a_log=(lh,), dt_bias=(lh,),
            on_g=(dv,), w_o=(lh * dv, d))
    return shapes


#: What stays float32 in the served weights (the recurrence's own scalars).
FLOAT32_NAMES = ("a_log", "dt_bias")


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    """The published initialisation where the recurrence needs it (``a_log =
    log(A)`` with ``A`` uniform in (0, 16) a head; ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in [1e-3, 1e-1]; both kept float32);
    norm scales 1 + 0.02 noise; everything else normal at ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g") and name != "w_g":
            x = 1.0 + 0.02 * jax.random.normal(k, shape, F32)
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, F32, 1e-4, 16.0))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, F32, math.log(1e-3), math.log(1e-1)))
            x = step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
        else:
            x = std * jax.random.normal(k, shape, F32)
        out[name] = x.astype(F32 if name in FLOAT32_NAMES else dtype)
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
        for kind in KINDS
    }
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "w_head": (s["d"], s["v"]),
                       "lnf_g": (s["d"],)},
        std=std, dtype=dtype))
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


def l2_normalised(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def attention(y, w, *, eps: float, einsum):
    """Causal attention over ``y [T, d]`` with QK-norm and no positional
    term."""
    t = y.shape[0]
    q = einsum("td,dhk->thk", y, w["wq"])
    k = einsum("td,dhk->thk", y, w["wk"])
    v = einsum("td,dhk->thk", y, w["wv"])
    h, hkv, hd = q.shape[1], k.shape[1], q.shape[2]
    whole = lambda x, g: rms_norm(  # noqa: E731  over all heads together
        x.reshape(t, -1), g.reshape(-1), eps).reshape(x.shape)
    q, k = whole(q, w["qn_g"]), whole(k, w["kn_g"])
    qg = q.reshape(t, hkv, h // hkv, hd)
    scores = einsum("qgrk,sgk->grqs", qg, k) * hd**-0.5
    pos = jnp.arange(t)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("grqs,sgk->qgrk", probs, v).reshape(t, h, hd)
    return einsum("thk,hkd->td", att, w["wo"])


def gated_delta(y, w, *, cfg: dict, eps: float, einsum, state_dtype=F32,
                beta_scale=None):
    """The linear-attention mixer over ``y [T, d]`` from a zero state: its
    output and the state after the last token, ``S_T [H, d_k, d_v]``.
    ``state_dtype`` is what ``S`` is rounded to after every token: float32 is
    the model; the controls pass less. ``beta_scale`` (``None``: 2 where
    ``linear_allow_neg_eigval``, else 1) is the controls' too."""
    s = dims(cfg)
    t, taps, lh, dk, dv = y.shape[0], s["k"], s["lh"], s["dk"], s["dv"]
    if beta_scale is None:
        beta_scale = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    qkv = jnp.concatenate(
        [einsum("td,de->te", y, w[name]) for name in ("w_q", "w_k", "w_v")],
        axis=-1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), F32), qkv])
    qkv = silu(sum(w["conv_w"][i] * padded[i:i + t] for i in range(taps)))
    q, k, v = jnp.split(qkv, [lh * dk, 2 * lh * dk], axis=-1)
    q = l2_normalised(q.reshape(t, lh, dk)) * dk**-0.5
    k = l2_normalised(k.reshape(t, lh, dk))
    v = v.reshape(t, lh, dv)
    beta = beta_scale * jax.nn.sigmoid(einsum("td,dh->th", y, w["w_b"]))
    alpha = jnp.exp(-jnp.exp(w["a_log"]) * jax.nn.softplus(
        einsum("td,dh->th", y, w["w_a"]) + w["dt_bias"]))

    def step(state, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs  # [H, d_k], .., [H], [H]
        state = alpha_t[:, None, None] * state.astype(F32)
        read = jnp.sum(state * k_t[:, :, None], axis=1)  # S^T k: [H, d_v]
        state = state + (beta_t[:, None] * k_t)[:, :, None] * (
            v_t - read)[:, None, :]
        state = state.astype(state_dtype)
        return state, jnp.sum(state.astype(F32) * q_t[:, :, None], axis=1)

    s0 = jnp.zeros((lh, dk, dv), state_dtype)
    s_last, o = jax.lax.scan(step, s0, (q, k, v, alpha, beta))
    gate = silu(einsum("td,de->te", y, w["w_g"])).reshape(t, lh, dv)
    out = (rms_norm(o, w["on_g"], eps) * gate).reshape(t, lh * dv)
    return einsum("te,ed->td", out, w["w_o"]), s_last.astype(F32)


def block(x, w, *, kind: str, cfg: dict, einsum=jnp.einsum, state_dtype=F32,
          beta_scale=None):
    """One layer over ``x [T, d]`` (float32): its output, and a linear
    layer's final state (``None`` from a full layer). Every projection goes
    through ``einsum``."""
    w = {k: v.astype(F32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    s_last = None
    if kind == "full_attention":
        mixed = attention(x, w, eps=eps, einsum=einsum)
    else:
        mixed, s_last = gated_delta(
            x, w, cfg=cfg, eps=eps, einsum=einsum, state_dtype=state_dtype,
            beta_scale=beta_scale)
    x = x + rms_norm(mixed, w["ln1_g"], eps)
    gated = silu(einsum("td,df->tf", x, w["w_gate"])) * einsum(
        "td,df->tf", x, w["w_up"])
    fed = einsum("tf,fd->td", gated, w["w_down"])
    return x + rms_norm(fed, w["ln2_g"], eps), s_last


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, einsum, state_dtype, beta_scale):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(w_head, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,dv->rv", y, w_head.astype(F32))

    layers = {
        kind: jax.jit(functools.partial(
            block, kind=kind, cfg=cfg, einsum=einsum, state_dtype=state_dtype,
            beta_scale=beta_scale))
        for kind in KINDS
    }
    return jax.jit(embed), layers, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, state_dtype, beta_scale):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    linear layers' final states, and the head's program."""
    scalars = tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool))))
    embed, layers, head = _programs(
        scalars, einsum, jnp.dtype(state_dtype), beta_scale)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    states = []
    for kind, w in zip(layer_types(cfg), weights["layers"]):
        x, s_last = layers[kind](x, w)
        if s_last is not None:
            states.append(s_last)
    return x, states, head


def final_states(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum,
                 state_dtype=F32, beta_scale=None):
    """The state of every linear layer after the whole of ``tokens``
    (unpadded: the recurrence runs over every position it is given), float32
    ``[linear layers, H, d_k, d_v]``."""
    with jax.default_matmul_precision("highest"):
        _, states, _ = _through_layers(
            cfg, weights, list(tokens), einsum, state_dtype, beta_scale)
    return jnp.stack(states)


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0,
              state_dtype=F32, beta_scale=None):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of ONE token
    sequence ``tokens [T]`` (row ``p`` predicts token ``p + 1``). Layer by
    layer, each layer's weights upcast inside its own program. ``pad_*_to``
    pad the sequence (at its end: neither causal attention nor the
    recurrence carries anything backwards) and the rows, so that one compiled
    program serves requests of every length."""
    tokens, rows = list(tokens), list(rows)
    if not rows:
        raise ValueError("no row to score")
    n = len(rows)
    tokens += [0] * (pad_tokens_to - len(tokens))
    rows += [rows[-1]] * (pad_rows_to - n)
    with jax.default_matmul_precision("highest"):
        x, _, head = _through_layers(
            cfg, weights, tokens, einsum, state_dtype, beta_scale)
        return head(weights["w_head"], weights["lnf_g"], x,
                    jnp.asarray(rows, jnp.int32))[:n]


# ------------------------------------------------------------------- counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against, per kind of layer (the mixer
    and the feed-forward), and in the output head."""
    s = dims(cfg)
    d, lh, dk, dv = s["d"], s["lh"], s["dk"], s["dv"]
    mlp = 3 * d * s["f"]
    return {
        "linear_attention": (
            d * lh * (2 * dk + 2 * dv + 2) + lh * dv * d + mlp),
        "full_attention": (
            2 * d * s["h"] * s["hd"] + 2 * d * s["hkv"] * s["hd"] + mlp),
        "head": d * s["v"],
    }


def _layer_counts(cfg: dict) -> tuple:
    kinds = layer_types(cfg)
    return kinds.count("linear_attention"), kinds.count("full_attention")


def gdn_step_state_bytes(cfg: dict, rows: int) -> int:
    """Least bytes the one-token update moves for ``rows`` (row, linear
    layer) pairs: each float32 state once in and once out."""
    s = dims(cfg)
    return 2 * rows * s["lh"] * s["dk"] * s["dv"] * 4


def state_bytes_per_slot(cfg: dict, conv_bytes: int = 2) -> int:
    """What one sequence keeps between tokens, over the linear layers: the
    float32 state and the conv tail in the served type."""
    s = dims(cfg)
    n_linear, _ = _layer_counts(cfg)
    tail = (s["k"] - 1) * s["lh"] * (2 * s["dk"] + s["dv"])
    return n_linear * (s["lh"] * s["dk"] * s["dv"] * 4 + tail * conv_bytes)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = dims(cfg)
    _, n_full = _layer_counts(cfg)
    return 2 * n_full * s["hkv"] * s["hd"] * bytes_per_value


def scan_flops_per_token(cfg: dict) -> float:
    """The conv and the recurrence of ONE linear layer for one token, as the
    recurrence is written: K taps a channel; per state element the decay's
    product, the multiply-add of ``S^T k``, the rank-one update's and the
    multiply-add of ``S^T q``. Vector work as written; a blocked evaluation
    turns it into matrix products (``gdn_blocks_flops``)."""
    s = dims(cfg)
    conv = 2.0 * s["k"] * s["lh"] * (2 * s["dk"] + s["dv"])
    return conv + 7.0 * s["lh"] * s["dk"] * s["dv"]


def scan_io_bytes_per_token(cfg: dict) -> int:
    """What the recurrence reads and writes for one token besides the state,
    over the linear layers: ``q``, ``k`` over ``d_k``, ``v`` and ``o`` over
    ``d_v``, ``alpha`` and ``beta``, a head, float32."""
    s = dims(cfg)
    n_linear, _ = _layer_counts(cfg)
    return n_linear * 4 * s["lh"] * (2 * s["dk"] + 2 * s["dv"] + 2)


def gdn_blocks_flops(cfg: dict, tokens: int, block: int = 64) -> float:
    """Matrix-product FLOPs of the blocked evaluation of ``tokens`` tokens
    (whole blocks of ``block``) in ONE linear layer: a block and head the two
    ``L x L`` Gram products over ``d_k``, the unit-triangular solve with ``d_k
    + d_v`` right-hand sides (``L^2`` multiply-adds a column), and the four
    products with the state (``W_k S``, ``Q S``, ``A U``, ``K^T U``)."""
    s = dims(cfg)
    dk, dv, ell = s["dk"], s["dv"], block
    blocks = -(-tokens // ell)
    a_block = (
        2 * 2.0 * ell * ell * dk + 1.0 * ell * ell * (dk + dv)
        + 3 * 2.0 * ell * dk * dv + 2.0 * ell * ell * dv)
    return blocks * s["lh"] * a_block


def gdn_blocks_min_bytes(cfg: dict, tokens: int, pieces: int) -> float:
    """Least bytes the blocked evaluation moves in ONE linear layer for
    ``tokens`` tokens in ``pieces`` prefill pieces: a piece's state once in
    and once out, and a token's ``q, k, v, o, alpha, beta`` (float32)."""
    s = dims(cfg)
    state = 2.0 * pieces * s["lh"] * s["dk"] * s["dv"] * 4
    return state + tokens * 4.0 * s["lh"] * (2 * s["dk"] + 2 * s["dv"] + 2)


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through the model when the full
    layers' queries attend to ``context_tokens`` keys in all (summed over the
    new positions) and ``logits_rows`` go through the head."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_linear, n_full = _layer_counts(cfg)
    dense = 2.0 * (n_linear * p["linear_attention"]
                   + n_full * p["full_attention"]) * new_tokens
    attn = 4.0 * n_full * s["h"] * s["hd"] * context_tokens
    scan = n_linear * scan_flops_per_token(cfg) * new_tokens
    return dense + attn + scan + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every weight once (the embedding is a gather: a row a
    token; the head is whole); the state of every decode row and of every
    prefill chunk (a chunk is a stretch of one request) read and written; the
    KV entries read, and one written per new token. Weights read again by a
    second program of the same step are the engine's doing and not in a
    floor."""
    p = matmul_params(cfg)
    n_linear, n_full = _layer_counts(cfg)
    weights = (n_linear * p["linear_attention"] + n_full * p["full_attention"]
               + p["head"])
    states = decode_rows + prefill_chunks
    kv = kv_bytes_per_token(cfg) * (kv_tokens_read + decode_rows + prefill_tokens)
    return (bytes_per_param * weights
            + 2.0 * state_bytes_per_slot(cfg) * states + kv)

"""Plain reference for a hybrid decoder-only LM of the ``jamba`` family
(HF ``modeling_jamba.py``), dense feed-forwards (``num_experts`` 1).

Every layer: ``x = x + mixer(rmsnorm(x))``; ``x = x + W_down(silu(W_gate h) *
(W_up h))`` with ``h = rmsnorm(x)``. Final RMSNorm; logits from the tied
embedding. The mixer of layer ``i`` is attention where ``i %
attn_layer_period == attn_layer_offset`` and Mamba everywhere else.

* attention: no biases, grouped KV heads, causal, **no rotary or other
  positional term** (the config carries no rope key), no window.
* Mamba: ``[u, z] = W_in h``; ``u_t = silu(b_c + sum_k w_c[k] u_{t-K+1+k})``
  (depthwise causal conv); ``[dt, B, C] = W_x u_t``; the family's own
  ``dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)``; ``delta =
  softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(delta_t A)
  h_{t-1} + (delta_t B_t) u_t``; ``y_t = h_t C_t + D u_t``; ``out = W_out (y_t
  * silu(z_t))``. The recurrence is a plain ``lax.scan`` over time from a zero
  state.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no state carried between calls, no kernels, no batching. It imports nothing
of the program under test. Every projection goes through the ``einsum`` it is
handed (``control.py`` hands it the int8 one); the recurrence itself does not.

It also makes the weights from the seed, on the device, in the type they are
served in, and holds the counts the roofline readers use (``serve_flops``,
``serve_min_bytes``, ``state_bytes_per_slot``). ``final_states`` gives the
Mamba layers' scan states after a sequence, for the comparison of the
program's.

Weight layout (this file's own): ``embed [V, d]``, ``lnf_g [d]``; every layer
``ln1_g ln2_g [d]``, ``w_gate w_up [d, F]``, ``w_down [F, d]``; an attention
layer ``wq [d, H, D]``, ``wk wv [d, Hkv, D]``, ``wo [H, D, d]``; a Mamba layer
``w_in [d, 2 d_inner]``, ``conv_w [K, d_inner]``, ``conv_b [d_inner]``, ``w_x
[d_inner, R + 2N]``, ``dt_g [R]``, ``b_g c_g [N]``, ``w_dt [R, d_inner]``,
``b_dt [d_inner]``, ``a_log [d_inner, N]``, ``d_skip [d_inner]``, ``w_out
[d_inner, d]``.
"""

from __future__ import annotations

import functools
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
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
        f=cfg["intermediate_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], di=cfg["mamba_expand"] * d,
        n=cfg["mamba_d_state"], r=cfg["mamba_dt_rank"], k=cfg["mamba_d_conv"],
    )


def layer_types(cfg: dict) -> list:
    """``"attention"`` or ``"mamba"`` per layer, as the ``jamba`` modelling
    code lays them out from the period and the offset."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def layer_shapes(cfg: dict, kind: str) -> dict:
    s = dims(cfg)
    d, f, di, n, r = s["d"], s["f"], s["di"], s["n"], s["r"]
    shapes = {"ln1_g": (d,), "ln2_g": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    if kind == "attention":
        shapes.update(
            wq=(d, s["h"], s["hd"]), wk=(d, s["hkv"], s["hd"]),
            wv=(d, s["hkv"], s["hd"]), wo=(s["h"], s["hd"], d))
    else:
        shapes.update(
            w_in=(d, 2 * di), conv_w=(s["k"], di), conv_b=(di,),
            w_x=(di, r + 2 * n), dt_g=(r,), b_g=(n,), c_g=(n,),
            w_dt=(r, di), b_dt=(di,), a_log=(di, n), d_skip=(di,),
            w_out=(di, d))
    return shapes


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    """Mamba's published initialisation where the recurrence needs it
    (``a_log = log(1..N)`` on every channel, ``d_skip = 1``, ``b_dt`` the
    inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]); norm
    scales 1 + 0.02 noise; everything else normal at ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            x = 1.0 + 0.02 * jax.random.normal(k, shape, F32)
        elif name == "a_log":
            x = jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape)
        elif name == "d_skip":
            x = jnp.ones(shape, F32)
        elif name == "b_dt":
            step = jnp.exp(jax.random.uniform(
                k, shape, F32, math.log(1e-3), math.log(1e-1)))
            x = step + jnp.log(-jnp.expm1(-step))  # softplus^-1(step)
        else:
            x = std * jax.random.normal(k, shape, F32)
        out[name] = x.astype(dtype)
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
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "lnf_g": (s["d"],)},
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


def attention(y, w, einsum):
    """Causal grouped attention over ``y [T, d]`` with no positional term."""
    t = y.shape[0]
    q = einsum("td,dhk->thk", y, w["wq"])
    k = einsum("td,dhk->thk", y, w["wk"])
    v = einsum("td,dhk->thk", y, w["wv"])
    h, hkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(t, hkv, h // hkv, hd)
    scores = einsum("qgrk,sgk->grqs", qg, k) * hd**-0.5
    pos = jnp.arange(t)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("grqs,sgk->qgrk", probs, v).reshape(t, h, hd)
    return einsum("thk,hkd->td", att, w["wo"])


def mamba(y, w, *, cfg: dict, eps: float, einsum, state_dtype=F32):
    """The Mamba mixer over ``y [T, d]`` from a zero state: its output and
    the state after the last token, ``h_T [d_inner, N]``. ``state_dtype`` is
    what ``h`` is rounded to after every token: float32 is the model; the
    controls pass less."""
    s = dims(cfg)
    t, k, n, r = y.shape[0], s["k"], s["n"], s["r"]
    u, z = jnp.split(einsum("td,de->te", y, w["w_in"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    u = silu(w["conv_b"] + sum(
        w["conv_w"][i] * padded[i:i + t] for i in range(k)))
    dt, b, c = jnp.split(einsum("te,ep->tp", u, w["w_x"]), [r, r + n], axis=-1)
    dt = rms_norm(dt, w["dt_g"], eps)
    b, c = rms_norm(b, w["b_g"], eps), rms_norm(c, w["c_g"], eps)
    delta = jax.nn.softplus(einsum("tr,re->te", dt, w["w_dt"]) + w["b_dt"])
    a = -jnp.exp(w["a_log"])  # [d_inner, N]

    def step(h, xs):
        u_t, delta_t, b_t, c_t = xs
        h = (jnp.exp(delta_t[:, None] * a) * h.astype(F32)
             + (delta_t * u_t)[:, None] * b_t[None, :])
        h = h.astype(state_dtype)
        return h, jnp.sum(h.astype(F32) * c_t[None, :], axis=-1)

    h0 = jnp.zeros(a.shape, state_dtype)
    h, ys = jax.lax.scan(step, h0, (u, delta, b, c))
    ys = ys + w["d_skip"] * u
    return einsum("te,ed->td", ys * silu(z), w["w_out"]), h.astype(F32)


def block(x, w, *, kind: str, cfg: dict, einsum=jnp.einsum, state_dtype=F32):
    """One layer over ``x [T, d]`` (float32): its output, and a Mamba
    layer's final scan state (``None`` from an attention layer). Every
    projection goes through ``einsum``."""
    w = {k: v.astype(F32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    y = rms_norm(x, w["ln1_g"], eps)
    h_last = None
    if kind == "attention":
        x = x + attention(y, w, einsum)
    else:
        mixed, h_last = mamba(y, w, cfg=cfg, eps=eps, einsum=einsum,
                              state_dtype=state_dtype)
        x = x + mixed
    y = rms_norm(x, w["ln2_g"], eps)
    gated = silu(einsum("td,df->tf", y, w["w_gate"])) * einsum(
        "td,df->tf", y, w["w_up"])
    return x + einsum("tf,fd->td", gated, w["w_down"]), h_last


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, einsum, state_dtype):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(table, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,vd->rv", y, table.astype(F32))

    layers = {
        kind: jax.jit(functools.partial(
            block, kind=kind, cfg=cfg, einsum=einsum, state_dtype=state_dtype))
        for kind in ("attention", "mamba")
    }
    return jax.jit(embed), layers, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, state_dtype):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    Mamba layers' final scan states, and the head's program."""
    scalars = tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool))))
    embed, layers, head = _programs(scalars, einsum, jnp.dtype(state_dtype))
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    states = []
    for kind, w in zip(layer_types(cfg), weights["layers"]):
        x, h_last = layers[kind](x, w)
        if h_last is not None:
            states.append(h_last)
    return x, states, head


def final_states(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum,
                 state_dtype=F32):
    """The scan state of every Mamba layer after the whole of ``tokens``
    (unpadded: the recurrence runs over every position it is given), float32
    ``[mamba layers, d_inner, N]``."""
    with jax.default_matmul_precision("highest"):
        _, states, _ = _through_layers(
            cfg, weights, list(tokens), einsum, state_dtype)
    return jnp.stack(states)


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0,
              state_dtype=F32):
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
        x, _, head = _through_layers(cfg, weights, tokens, einsum, state_dtype)
        return head(weights["embed"], weights["lnf_g"], x,
                    jnp.asarray(rows, jnp.int32))[:n]


# ------------------------------------------------------------------- counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against, per kind of layer (the mixer
    and the feed-forward), and in the output head."""
    s = dims(cfg)
    d, di, n, r = s["d"], s["di"], s["n"], s["r"]
    mlp = 3 * d * s["f"]
    return {
        "mamba": d * 2 * di + di * (r + 2 * n) + r * di + di * d + mlp,
        "attention": 2 * d * s["h"] * s["hd"] + 2 * d * s["hkv"] * s["hd"] + mlp,
        "head": d * s["v"],
    }


def _layer_counts(cfg: dict) -> tuple:
    kinds = layer_types(cfg)
    return kinds.count("mamba"), kinds.count("attention")


def state_bytes_per_slot(cfg: dict, conv_bytes: int = 2) -> int:
    """What one sequence keeps between tokens, over the Mamba layers: the
    float32 scan state and the conv tail in the served type."""
    s = dims(cfg)
    n_mamba, _ = _layer_counts(cfg)
    return n_mamba * (s["di"] * s["n"] * 4 + s["di"] * (s["k"] - 1) * conv_bytes)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = dims(cfg)
    _, n_attn = _layer_counts(cfg)
    return 2 * n_attn * s["hkv"] * s["hd"] * bytes_per_value


def scan_flops_per_token(cfg: dict) -> float:
    """The conv and the recurrence of ONE Mamba layer for one token: K taps a
    channel; per (channel, state) the decay's product and exponential, the
    input's product, the update's multiply-add and the output's
    multiply-add. Vector work, not matmuls."""
    s = dims(cfg)
    return 2.0 * s["k"] * s["di"] + 7.0 * s["di"] * s["n"]


def scan_io_bytes_per_token(cfg: dict) -> int:
    """What the scan reads and writes for one token besides the state, over
    the Mamba layers: ``u``, ``delta`` and ``y`` over ``d_inner``, ``B`` and
    ``C`` over ``N``, float32."""
    s = dims(cfg)
    n_mamba, _ = _layer_counts(cfg)
    return n_mamba * 4 * (3 * s["di"] + 2 * s["n"])


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through the model when the
    attention layers' queries attend to ``context_tokens`` keys in all
    (summed over the new positions) and ``logits_rows`` go through the head."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_mamba, n_attn = _layer_counts(cfg)
    dense = 2.0 * (n_mamba * p["mamba"] + n_attn * p["attention"]) * new_tokens
    attn = 4.0 * n_attn * s["h"] * s["hd"] * context_tokens
    scan = n_mamba * scan_flops_per_token(cfg) * new_tokens
    return dense + attn + scan + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every weight once (the tied embedding is the head);
    the state of every decode row and of every prefill chunk (a chunk is a
    stretch of one request) read and written; the KV entries read, and one
    written per new token. Weights read again by a second program of the
    same step are the engine's doing and not in a floor."""
    p = matmul_params(cfg)
    n_mamba, n_attn = _layer_counts(cfg)
    weights = n_mamba * p["mamba"] + n_attn * p["attention"] + p["head"]
    states = decode_rows + prefill_chunks
    kv = kv_bytes_per_token(cfg) * (kv_tokens_read + decode_rows + prefill_tokens)
    return (bytes_per_param * weights
            + 2.0 * state_bytes_per_slot(cfg) * states + kv)

"""Mixture-of-Experts MLP with expert parallelism over the mesh's ``expert``
axis.

No reference analog (SURVEY.md §2b: EP absent from the reference) — this is a
beyond-parity capability, built the TPU way:

* **Routing** is deterministic top-1 (Switch-Transformer) or top-2
  (GShard-style, ``router_top_k=2``): the router picks the k best experts
  per token, gates renormalized over the kept choices; each expert
  processes at most ``capacity = ceil(k * tokens_per_group *
  capacity_factor / n_experts)`` tokens per group (group = one batch row),
  secondary assignments queue behind primaries for slots; overflow tokens
  fall through the residual connection (their MoE output is zero).
* **Dispatch/combine are einsums** against a one-hot ``[B, T, E, C]`` tensor —
  dense, static-shaped, MXU-friendly; no gather/scatter, no dynamic shapes,
  exactly what XLA tiles well.
* **Expert parallelism is a sharding annotation**: the stacked expert kernels
  ``[E, d_model, d_ff]`` carry ``P("expert", ...)`` specs
  (:data:`MOE_EP_RULES`), and the dispatched activations ``[E, B, C, M]`` are
  constrained to ``P("expert", "data")`` — XLA inserts the token all-to-all
  (data-sharded tokens -> expert-sharded slots) and back, riding ICI, the
  same role NCCL all-to-all plays in GPU MoE stacks.
* The **load-balance auxiliary loss** (mean expert load x mean router prob,
  scaled by ``aux_weight``) is sown into the ``"losses"`` collection; the
  train step adds every term in that collection to the task loss.

**Two expert layers live here until training moves** (ROADMAP queue D).
:class:`MoEMLP` above is the training layer: capacity, dropped tokens, a dense
one-hot. :class:`RoutedExperts` below is the serving layer, and the one an
expert-parallel deployment needs: **dropless top-k over a held range**. It is
told ``n_experts`` (the router's width, as published) and ``held = (lo, hi)``,
the experts whose weights THIS chip has; it routes every token over all
``n_experts`` (scores, top-k and gates in float32, ``ROUTER_DTYPE``; gates a
softmax over the chosen ``top_k`` scores alone), and computes its own
experts' part of the result for the (token, expert) pairs routed to them.
Pairs on absent experts are left out: the partial sum is the layer's result
here, and the parts of all the shares add up to the whole layer's. On one
chip the layer runs without its exchange, and nothing stands in for it.

Work is proportional to the routed pairs, not to tokens x experts: the pairs
are sorted by expert (held ones first, absent ones and those of rows that
carry nothing behind them), the rows gathered in that order, and the two
projections are grouped matrix products over the held experts
(``ops/grouped_matmul.py``). How they are computed follows the block's
``paged_kernel`` as the attention kernels do (:func:`product_mode`; no option
of the layer's own): on a TPU a weight-stationary Pallas kernel that streams
each REACHED expert's weights once, in wide column blocks double-buffered
behind the matmuls, against that expert's own rows in tiles of 16, and never
visits a row of no group (the device trace names it
``ragged-dot-stationary``; for it the rows are gathered into a power of two's
worth of tokens, so that a model's prefill widths share a few traces of the
kernel); elsewhere, and as the plain form the tests hold
the kernel to, ``jax.lax.ragged_dot`` (on a TPU the compiler's own grouped
matmul, ``ragged-dot``, whose time grows with the rows it is handed: PERF.md
section 6). No capacity, so no token is dropped at any load: the products'
row count is ``tokens x top_k`` (the kernel's: that of the next power of
two's tokens) whatever the routing.
The layer also counts the tokens routed to each of the ``n_experts``
(``[n_experts] int32``, sown into the ``"routing"`` collection when the
caller makes it mutable): the serving engine's routing counters.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_pytorch_tpu.ops.grouped_matmul import gated_experts
from distributed_pytorch_tpu.ops.paged_attention import resolve_kernel
from distributed_pytorch_tpu.parallel.partitioning import Rules

F32 = jnp.float32
#: The type of the router's scores, top-k and gates in :class:`RoutedExperts`.
#: Not an option: the tests and the benchmark's control that show what
#: catches a lower one patch it.
ROUTER_DTYPE = F32

#: Expert-parallel specs for :class:`MoEMLP` params (stacked over dim 0 = E).
#: Compose with ``TRANSFORMER_TP_RULES`` for the dense layers: EP rules first,
#: first match wins.
MOE_EP_RULES: Rules = (
    (r".*/moe/up_kernel$", P("expert", None, None)),
    (r".*/moe/up_bias$", P("expert", None)),
    (r".*/moe/down_kernel$", P("expert", None, None)),
    (r".*/moe/down_bias$", P("expert", None)),
    (r".*/moe/router/kernel$", P()),
    (r".*/moe/router/bias$", P()),
)


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense transformer MLP block.

    ``[B, T, d_model] -> [B, T, d_model]`` with top-1 (Switch) or top-2
    (GShard-style, ``router_top_k=2``) routing over ``n_experts`` expert
    MLPs of width ``d_ff``.
    """

    n_experts: int
    d_ff: int
    d_model: int
    dtype: Any = jnp.float32
    # 1 = Switch top-1; 2 = GShard-style deterministic top-2 (gates
    # renormalized over the two chosen experts, primary assignments take
    # capacity slots before secondaries).
    router_top_k: int = 1
    capacity_factor: float = 1.25
    aux_weight: float = 1e-2
    mesh: Optional[Mesh] = None
    expert_axis: Optional[str] = "expert"
    data_axis: Optional[str] = "data"

    def _constrain(self, x: jnp.ndarray) -> jnp.ndarray:
        """Pin dispatched activations [E, B, C, ...] to expert x data sharding
        so XLA materializes the all-to-all at this seam."""
        if self.mesh is None:
            return x
        e_ax = self.expert_axis if self.expert_axis in self.mesh.shape else None
        d_ax = self.data_axis if self.data_axis in self.mesh.shape else None
        if e_ax is None and d_ax is None:
            return x
        spec = P(e_ax, d_ax, *([None] * (x.ndim - 2)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        n_batch, n_tokens, d_model = x.shape
        n_exp = self.n_experts
        k = self.router_top_k
        if k not in (1, 2):
            raise ValueError(f"router_top_k must be 1 or 2, got {k}")
        if k > n_exp:
            # With the primary masked out, a second choice doesn't exist:
            # argmax over all-zero probs would silently re-pick the primary
            # at half weight.
            raise ValueError(
                f"router_top_k={k} needs at least {k} experts, got {n_exp}"
            )
        capacity = max(
            1, math.ceil(k * n_tokens * self.capacity_factor / n_exp)
        )

        # --- route: deterministic top-k per token ------------------------
        router_logits = nn.Dense(n_exp, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )
        probs = jax.nn.softmax(router_logits, axis=-1)  # [B, T, E]
        idx1 = jnp.argmax(probs, axis=-1)  # [B, T]
        oh1 = jax.nn.one_hot(idx1, n_exp, dtype=jnp.float32)

        # Load-balance aux loss (Switch eq. 4 over the PRIMARY assignment):
        # E * mean_load . mean_prob.
        load = jnp.mean(oh1, axis=(0, 1))  # fraction routed per expert
        importance = jnp.mean(probs, axis=(0, 1))  # mean router prob
        aux = n_exp * jnp.sum(load * importance)
        self.sow("losses", "moe_aux", self.aux_weight * aux)

        def slots(position, keep):
            # [B, T, E, C] one-hot over capacity slots; position is 0 for
            # unrouted (token, expert) pairs -> index -1 -> all-zero row,
            # exactly the "no slot" encoding we want.
            return jax.nn.one_hot(
                position.astype(jnp.int32) - 1, capacity, dtype=jnp.float32
            ) * jnp.where(keep, 1.0, 0.0)[..., None]

        # Primary choice: position within each expert's capacity (1-based).
        pos1 = jnp.cumsum(oh1, axis=1) * oh1  # [B, T, E]
        keep1 = (pos1 > 0) & (pos1 <= capacity)
        disp1 = slots(pos1, keep1)
        gate1 = jnp.sum(probs * oh1, axis=-1)  # [B, T]

        if k == 2:
            # Secondary = best expert with the primary masked out; its
            # tokens queue BEHIND every primary assignment of that expert
            # (GShard priority), sharing one capacity budget.
            probs2 = probs * (1.0 - oh1)
            idx2 = jnp.argmax(probs2, axis=-1)
            oh2 = jax.nn.one_hot(idx2, n_exp, dtype=jnp.float32)
            count1 = jnp.sum(oh1, axis=1, keepdims=True)  # [B, 1, E]
            pos2 = (jnp.cumsum(oh2, axis=1) + count1) * oh2
            keep2 = (pos2 > 0) & (pos2 <= capacity)
            disp2 = slots(pos2, keep2)
            gate2 = jnp.sum(probs * oh2, axis=-1)
            # Renormalize over the two chosen experts, then zero dropped
            # assignments (kept one keeps its renormalized share).
            denom = gate1 + gate2 + 1e-9
            g1 = gate1 / denom
            g2 = gate2 / denom
            dispatch_t = disp1 + disp2  # disjoint slots by construction
            combine_t = disp1 * g1[..., None, None] + disp2 * g2[..., None, None]
        else:
            dispatch_t = disp1
            combine_t = disp1 * gate1[..., None, None]

        # --- dispatch -> experts -> combine ------------------------------
        w_up = self.param(
            "up_kernel",
            nn.initializers.lecun_normal(),
            (n_exp, d_model, self.d_ff),
        )
        b_up = self.param("up_bias", nn.initializers.zeros, (n_exp, self.d_ff))
        w_down = self.param(
            "down_kernel",
            nn.initializers.lecun_normal(),
            (n_exp, self.d_ff, d_model),
        )
        b_down = self.param("down_bias", nn.initializers.zeros, (n_exp, d_model))

        compute = self.dtype
        # Tokens -> expert slots: the EP all-to-all happens here.
        expert_in = jnp.einsum(
            "btec,btm->ebcm", dispatch_t.astype(compute), x.astype(compute)
        )
        expert_in = self._constrain(expert_in)
        h = jnp.einsum("ebcm,emf->ebcf", expert_in, w_up.astype(compute))
        h = nn.gelu(h + b_up[:, None, None, :].astype(compute))
        out = jnp.einsum("ebcf,efm->ebcm", h, w_down.astype(compute))
        out = out + b_down[:, None, None, :].astype(compute)
        out = self._constrain(out)
        # Expert slots -> tokens: the reverse all-to-all.
        y = jnp.einsum("btec,ebcm->btm", combine_t.astype(compute), out)
        return y.astype(x.dtype)


def route_top_k(scores: jnp.ndarray, top_k: int):
    """The published router: the ``top_k`` largest of a token's scores and
    gates that are a softmax over THOSE scores. ``scores [..., E]`` ->
    ``(gates [..., top_k]`` in the scores' type, ``experts [..., top_k])``."""
    best, experts = jax.lax.top_k(scores, top_k)
    return jax.nn.softmax(best, axis=-1), experts


#: The published gating rules. ``"softmax_of_top_k"``: :func:`route_top_k`.
#: ``"top_k_of_softmax"``: a softmax over ALL the scores, then the ``top_k``
#: largest probabilities as they are, NOT renormalised (a configuration's
#: ``scoring_func: softmax`` with ``norm_topk_prob: false``).
#: ``"sigmoid_biased"``: ``s = sigmoid(scores)``, the ``top_k`` experts of
#: largest ``s + bias`` (a correction bias an expert, used for the CHOICE
#: alone), gates ``s_e / sum of the chosen s`` (``scoring_func: sigmoid``,
#: ``topk_method: noaux_tc`` with one group, ``norm_topk_prob: true``).
#: ``"softmax_biased"``: ``p = softmax(scores)`` over ALL the scores, the
#: ``top_k`` experts of largest ``p + bias`` (a balancing bias an expert, used
#: for the CHOICE alone), gates ``p_e`` as they are, NOT renormalised (with
#: ``top_k`` 1: the one best expert at its own probability).
GATINGS = (
    "softmax_of_top_k", "top_k_of_softmax", "sigmoid_biased", "softmax_biased",
)
#: The rules that choose by ``score + bias`` (``router_bias [E]``, float32).
BIASED_GATINGS = ("sigmoid_biased", "softmax_biased")


def route(
    scores: jnp.ndarray, top_k: int, gating: str = GATINGS[0], bias=None
):
    """``(gates, experts)`` as :func:`route_top_k` gives them, under any of
    :data:`GATINGS`. ``bias [E]`` is the :data:`BIASED_GATINGS`' correction
    bias (``None``: zeros); the other rules take none."""
    if gating == "softmax_of_top_k":
        return route_top_k(scores, top_k)
    if gating == "top_k_of_softmax":
        return jax.lax.top_k(jax.nn.softmax(scores, axis=-1), top_k)
    if gating == "sigmoid_biased":
        s = jax.nn.sigmoid(scores)
        _, experts = jax.lax.top_k(s if bias is None else s + bias, top_k)
        chosen = jnp.take_along_axis(s, experts, axis=-1)
        return chosen / jnp.sum(chosen, axis=-1, keepdims=True), experts
    if gating == "softmax_biased":
        p = jax.nn.softmax(scores, axis=-1)
        _, experts = jax.lax.top_k(p if bias is None else p + bias, top_k)
        return jnp.take_along_axis(p, experts, axis=-1), experts
    raise ValueError(
        f"unknown gating rule {gating!r} (expected one of {GATINGS})"
    )


def product_mode(paged_kernel, d_model: int, d_ff: int) -> str:
    """How :class:`RoutedExperts` computes its grouped products under a
    block's ``paged_kernel``: ``ops/paged_attention.resolve_kernel``'s answer
    (``"pallas"`` on a TPU, ``"interpret"`` for tests, ``"xla"``), and
    ``"xla"``, ``jax.lax.ragged_dot``, where the block names no kernel. The
    ONE place where the call's static shapes decide: the compiled kernel
    copies rows and weight blocks in whole lanes (Mosaic refuses a slice of
    32 of 128), so widths that are no multiples of 128 take the plain form."""
    mode = resolve_kernel(paged_kernel) if paged_kernel else "xla"
    if mode == "pallas" and (d_model % 128 or d_ff % 128):
        return "xla"
    return mode


#: What :class:`RoutedExperts` can be given as its router. ``"linear"``: one
#: matrix ``router_kernel [d_model, n_experts]``. ``"mlp_carry"``:
#: :class:`CarryRouter`, a small network with a carry between layers.
ROUTERS = ("linear", "mlp_carry")
#: The routers that take the previous routed layer's carry and return theirs.
CARRY_ROUTERS = ("mlp_carry",)


def _router_dot(x, kernel):
    return jnp.dot(
        x.astype(ROUTER_DTYPE), kernel.astype(ROUTER_DTYPE),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=ROUTER_DTYPE,
    )


class CarryRouter(nn.Module):
    """A router that is a small network and hands a state from layer to layer
    (the ``zaya`` family's): over a token's normed input ``n [tokens, d]`` and
    the previous routed layer's carry ``r' [tokens, hidden]`` (``None``: the
    first, zeros)::

        r = W_d n + gamma r'                 the carry it returns, MIXED
        s = W_3 gelu(W_2 gelu(W_1 rmsnorm(r))) ``[tokens, n_experts]``

    ``gamma`` one learned scalar (``carry_scale``), the GELU exact, all of it
    in ``ROUTER_DTYPE``. Returns ``(s, r)``."""

    n_experts: int
    hidden: int
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, n, carry=None):
        kernel = lambda name, shape: self.param(  # noqa: E731
            name, nn.initializers.lecun_normal(), shape, F32
        )
        d, hid = n.shape[-1], self.hidden
        r = _router_dot(n, kernel("down", (d, hid)))
        gamma = self.param("carry_scale", nn.initializers.ones_init(), (), F32)
        if carry is not None:
            r = r + gamma.astype(ROUTER_DTYPE) * carry.astype(ROUTER_DTYPE)
        y = nn.RMSNorm(epsilon=self.norm_eps, dtype=F32, name="norm")(
            r
        ).astype(ROUTER_DTYPE)
        for name in ("w1", "w2"):
            y = jax.nn.gelu(
                _router_dot(y, kernel(name, (hid, hid))), approximate=False
            )
        return _router_dot(y, kernel("w3", (hid, self.n_experts))), r


class RoutedExperts(nn.Module):
    """Dropless top-k routed gated-SiLU experts over a held range (module
    docstring). ``[B, T, d_model] -> [B, T, d_model]``; with a router of
    :data:`CARRY_ROUTERS`, ``(that, the router's carry [B, T, hidden])``, and
    the call takes the previous routed layer's ``carry``.

    Parameters: ``router_kernel [d_model, n_experts]`` (no bias; with
    ``router="mlp_carry"`` :class:`CarryRouter`'s under ``router`` instead;
    under the :data:`BIASED_GATINGS` also ``router_bias [n_experts]``, float32),
    ``in_kernel [held, d_model, 2 d_ff]`` (``[gate, up]``) and ``out_kernel
    [held, d_ff, d_model]``. ``live`` (optional) marks what carries a
    request: ``[B]`` whole batch rows (the batched decode step's rows inside
    its group) or ``[B, T]`` single tokens (a prefill piece's own, not its
    padding). Everything else's pairs are computed by nobody, stand in no
    group of either product and are counted nowhere."""

    n_experts: int
    top_k: int
    d_ff: int
    d_model: int
    held: Optional[tuple] = None  # (lo, hi): experts lo..hi-1; None = all
    dtype: Any = F32
    gating: str = GATINGS[0]  # one of GATINGS
    paged_kernel: str = ""  # the block's (see Attention): the products' mode
    # What the gates are multiplied by after the rule has made them (a
    # configuration's ``routed_scaling_factor``), in float32; 1.0 is none.
    scale: float = 1.0
    router: str = ROUTERS[0]  # one of ROUTERS
    router_hidden: int = 0  # the width of a router that is a network
    norm_eps: float = 1e-5  # of such a router's norm

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, *, live: Optional[jnp.ndarray] = None,
        carry: Optional[jnp.ndarray] = None,
    ):
        batch, t, d = x.shape
        lo, hi = self.held or (0, self.n_experts)
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(
                f"held experts {self.held} outside 0..{self.n_experts}"
            )
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(
                f"top_k {self.top_k} of {self.n_experts} experts"
            )
        n_held, k = hi - lo, self.top_k
        tokens = batch * t
        flat = x.reshape(tokens, d)

        if self.router not in ROUTERS:
            raise ValueError(
                f"unknown router {self.router!r} (expected one of {ROUTERS})"
            )
        with jax.named_scope("moe.route"):
            if self.router == "mlp_carry":
                scores, carry = CarryRouter(
                    self.n_experts, self.router_hidden, self.norm_eps,
                    name="router",
                )(flat, None if carry is None else carry.reshape(tokens, -1))
                carry = carry.reshape(batch, t, -1)
            else:
                router = self.param(
                    "router_kernel", nn.initializers.normal(0.02),
                    (d, self.n_experts), F32,
                )
                scores = _router_dot(flat, router)
            bias = None
            if self.gating in BIASED_GATINGS:
                bias = self.param(
                    "router_bias", nn.initializers.zeros, (self.n_experts,),
                    F32,
                ).astype(ROUTER_DTYPE)
            gates, experts = route(scores, k, self.gating, bias)  # [tokens, k]
            if self.scale != 1.0:
                gates = gates * jnp.asarray(self.scale, gates.dtype)
            if live is None:
                alive = jnp.ones((tokens,), bool)
            elif live.ndim == 1:
                alive = jnp.repeat(live, t)
            else:
                alive = live.reshape(tokens)
            # Held pairs by expert, everything else behind them.
            local = experts - lo
            mine = (local >= 0) & (local < n_held) & alive[:, None]
            group = jnp.where(mine, local, n_held).reshape(-1)
            order = jnp.argsort(group, stable=True)
            sizes = jnp.bincount(group, length=n_held + 1)[:n_held]
            counts = jnp.bincount(
                jnp.where(alive[:, None], experts, self.n_experts).reshape(-1),
                length=self.n_experts + 1,
            )[: self.n_experts]
            self.sow("routing", "counts", counts.astype(jnp.int32))

        w_in = self.param(
            "in_kernel", nn.initializers.lecun_normal(),
            (n_held, d, 2 * self.d_ff),
        )
        w_out = self.param(
            "out_kernel", nn.initializers.lecun_normal(),
            (n_held, self.d_ff, d),
        )
        with jax.named_scope("moe.experts"):
            # Initialising only asks for shapes: the plain form gives them
            # without a trace of the kernel.
            mode = "xla" if self.is_initializing() else product_mode(
                self.paged_kernel, d, self.d_ff
            )
            take, room = order // k, tokens
            if mode != "xla":
                # Every program that calls the kernel traces and lowers it
                # for its own row count (set-up time: PERF.md section 6), so
                # the rows are gathered into a power of two's worth of
                # tokens: a model's prefill widths share a few kernels. The
                # rows past the pairs stand in no group and are never read.
                room = 1 << (tokens - 1).bit_length()
                take = jax.lax.pad(take, 0, [(0, (room - tokens) * k, 0)])
            rows = flat.astype(self.dtype)[take]  # [tokens k or more, d]
            # A token stands in a group once: no group outgrows the tokens.
            out = gated_experts(
                rows, w_in.astype(self.dtype), w_out.astype(self.dtype),
                sizes, mode=mode, max_group=room,
            )[: tokens * k]
        with jax.named_scope("moe.combine"):
            # Rows past the held pairs belong to no group: whatever the
            # product left there is not a result.
            in_group = jnp.arange(tokens * k) < jnp.sum(sizes)
            weight = gates.reshape(-1)[order].astype(F32)
            out = jnp.where(in_group[:, None], out * weight[:, None], 0.0)
            back = jnp.argsort(order)  # pair (token, choice) -> its row
            y = out[back].reshape(tokens, k, d).sum(axis=1)
        y = y.reshape(batch, t, d).astype(x.dtype)
        return (y, carry) if self.router in CARRY_ROUTERS else y

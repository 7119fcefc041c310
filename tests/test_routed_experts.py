"""``models/moe.py``'s ``RoutedExperts`` (dropless top-k over a held range)
against ``benchmarks/reference/granite.py``'s routed layer, which computes
every held expert on every token and weights it by its gate. float32
throughout: differences are the order of the sums (a grouped product over
sorted pairs against a sum over experts), measured at 1e-7 on outputs of
order 0.1; 1e-5 leaves room and is a hundredth of one dropped pair."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from granite_toy import reference

from distributed_pytorch_tpu.models import moe
from distributed_pytorch_tpu.models.moe import RoutedExperts, route_top_k

D, F, E, K = 32, 16, 8, 3
TOL = 1e-5
CFG = dict(
    hidden_size=D, intermediate_size=F, shared_intermediate_size=24,
    num_attention_heads=2, num_key_value_heads=1, vocab_size=16,
    num_hidden_layers=1, layer_types=["mamba"], mamba_n_heads=2,
    mamba_d_head=4, mamba_d_state=4, mamba_n_groups=1, mamba_d_conv=2,
    num_local_experts=E, num_experts_per_tok=K,
)


def share(held):
    lo, hi = held
    return dict(CFG, num_local_experts=hi - lo, experts_held=[lo, hi],
                num_local_experts_published=E)


def weights(seed=0, router=None):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    return {"router": f(D, E) if router is None else jnp.asarray(router),
            "we_in": f(E, D, 2 * F), "we_out": f(E, F, D)}


def tokens_in(n, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, D)), jnp.float32)


def layer_out(w, x, held=None, live=None, counts=False):
    lo, hi = held or (0, E)
    layer = RoutedExperts(E, K, F, D, held=held)
    params = {"router_kernel": w["router"], "in_kernel": w["we_in"][lo:hi],
              "out_kernel": w["we_out"][lo:hi]}
    out, sown = layer.apply(
        {"params": params}, x[None], live=live, mutable=["routing"])
    out = np.asarray(out[0])
    return (out, np.asarray(sown["routing"]["counts"][0])) if counts else out


def reference_out(w, x, held=None):
    lo, hi = held or (0, E)
    cut = dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
    return np.asarray(reference.routed_experts(
        x, cut, cfg=share((lo, hi)), einsum=jnp.einsum)[0])


def test_all_experts_held_matches_the_reference():
    w, x = weights(), tokens_in(40)
    want = reference_out(w, x)
    assert np.abs(want).max() > 0.1
    assert np.abs(layer_out(w, x) - want).max() < TOL


@pytest.mark.parametrize("cut", [4, 1, 7])
def test_the_shares_add_up(cut):
    """The parts computed with experts ``0..cut-1`` and ``cut..E-1`` held,
    each against the reference given the same share, sum to the uncut
    reference's layer (the shared expert is no part of this layer: the
    block adds it once, ``test_granite_serving.py`` holds that)."""
    w, x = weights(2), tokens_in(33, seed=3)
    parts = []
    for held in ((0, cut), (cut, E)):
        got = layer_out(w, x, held)
        assert np.abs(got - reference_out(w, x, held)).max() < TOL
        parts.append(got)
    whole = reference_out(w, x)
    assert np.abs(parts[0] + parts[1] - whole).max() < TOL
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01


def test_the_shares_and_the_shared_expert_counted_once_are_the_whole_layer():
    """What two chips sharing a layer compute: each its experts' part, and
    both the shared expert alike. The parts and ONE shared expert's output
    add up to the uncut reference's feed-forward."""
    from distributed_pytorch_tpu.models.transformer import MLPBlock

    w, x = weights(14), tokens_in(20, seed=15)
    rng = np.random.default_rng(16)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    shared_w = {"ws_gate": f(D, 24), "ws_up": f(D, 24), "ws_down": f(24, D)}
    shared = np.asarray(MLPBlock(24, D, kind="gated_silu", use_bias=False).apply(
        {"params": {"gate": {"kernel": shared_w["ws_gate"]},
                    "up": {"kernel": shared_w["ws_up"]},
                    "down": {"kernel": shared_w["ws_down"]}}}, x))
    parts = layer_out(w, x, (0, 5)) + layer_out(w, x, (5, E))
    whole = reference_out(w, x) + np.asarray(
        reference.shared_expert(x, shared_w, jnp.einsum))
    assert np.abs(parts + shared - whole).max() < TOL
    assert np.abs(shared).max() > 0.01


def pointing_router(expert_order):
    """A router under which every token with a positive first coordinate
    prefers the experts in ``expert_order``, most first."""
    router = np.zeros((D, E), np.float32)
    router[0, list(expert_order)] = np.arange(E, 0, -1)
    return router


def test_no_token_is_dropped_when_every_token_goes_to_one_expert():
    """The load a capacity would drop at: all 64 tokens choose expert 5
    first (and 6, 2 behind it). Every one of them gets expert 5's part."""
    w = weights(4, router=pointing_router([5, 6, 2, 0, 1, 3, 4, 7]))
    x = jnp.abs(tokens_in(64, seed=5))
    got, counts = layer_out(w, x, counts=True)
    assert counts.tolist() == [0, 0, 64, 0, 0, 64, 64, 0]
    assert np.abs(got - reference_out(w, x)).max() < TOL
    only = layer_out(w, x, held=(5, 6))
    assert (np.abs(only).max(axis=-1) > 1e-4).all()  # no row left out


def test_a_capacity_that_drops_is_caught(monkeypatch):
    """The planted fault: the grouped product told that no expert has more
    than 8 rows (a capacity), so the rest of a crowded expert's tokens fall
    through. The uneven load above shows it; even loads would not."""
    w = weights(4, router=pointing_router([5, 6, 2, 0, 1, 3, 4, 7]))
    x = jnp.abs(tokens_in(64, seed=5))
    ragged_dot = jax.lax.ragged_dot
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda a, b, sizes, **kw: ragged_dot(a, b, jnp.minimum(sizes, 8), **kw))
    assert np.abs(layer_out(w, x) - reference_out(w, x)).max() > 100 * TOL


def test_no_token_to_a_held_expert_gives_zeros():
    w = weights(6, router=pointing_router([0, 1, 2, 3, 4, 5, 6, 7]))
    x = jnp.abs(tokens_in(16, seed=7))
    got, counts = layer_out(w, x, held=(4, 8), counts=True)
    assert counts.tolist() == [16, 16, 16, 0, 0, 0, 0, 0]
    assert (got == 0).all()
    assert (reference_out(w, x, held=(4, 8)) == 0).all()


@pytest.mark.parametrize("held", [None, (0, 4), (6, 8)])
def test_gates_sum_to_one_over_the_chosen_whatever_is_held(held):
    """The gates are a softmax over the chosen ``K`` scores: what is held
    changes which of them are used here, never their values. With every
    expert's output forced to its input's first coordinate, a token's
    result is that coordinate times the sum of its HELD gates."""
    w, x = weights(8), tokens_in(24, seed=9)
    gates, experts = route_top_k(x @ w["router"], K)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    lo, hi = held or (0, E)
    held_mask = (np.asarray(experts) >= lo) & (np.asarray(experts) < hi)
    want = (np.asarray(gates) * held_mask).sum(-1)
    ref = np.asarray(reference.router_gates(
        x, w["router"], top_k=K, einsum=jnp.einsum))
    np.testing.assert_allclose(ref[:, lo:hi].sum(-1), want, atol=1e-6)
    np.testing.assert_allclose(ref.sum(-1), 1.0, atol=1e-6)
    assert ((ref > 0).sum(-1) == K).all()


def test_rows_that_carry_no_request_are_computed_by_nobody():
    w, x = weights(10), tokens_in(6, seed=11)
    layer = RoutedExperts(E, K, F, D)
    params = {"router_kernel": w["router"], "in_kernel": w["we_in"],
              "out_kernel": w["we_out"]}
    live = jnp.asarray([True, False, True, True, False, True])
    out, sown = layer.apply(
        {"params": params}, x[:, None], live=live, mutable=["routing"])
    out = np.asarray(out[:, 0])
    want = reference_out(w, x)
    assert np.abs(out[np.asarray(live)] - want[np.asarray(live)]).max() < TOL
    assert (out[~np.asarray(live)] == 0).all()
    assert int(sown["routing"]["counts"][0].sum()) == 4 * K


def test_router_scores_in_bfloat16_are_caught(monkeypatch):
    """The planted fault ``control_hybrid_moe.py --router bfloat16`` plants at
    the cell's size. bfloat16 scores move the gates by 1e-3 and, where two
    scores lie within a rounding of each other, swap the last chosen expert
    for another: the first alone is 100 times the tolerance."""
    w, x = weights(12), tokens_in(256, seed=13)
    want = reference_out(w, x)
    assert np.abs(layer_out(w, x) - want).max() < TOL
    monkeypatch.setattr(moe, "ROUTER_DTYPE", jnp.bfloat16)
    assert np.abs(layer_out(w, x) - want).max() > 100 * TOL


@pytest.mark.parametrize("held, top_k, message", [
    ((4, 9), K, "held experts"), ((3, 3), K, "held experts"),
    (None, E + 1, "top_k"),
])
def test_a_share_or_a_top_k_the_router_does_not_have_is_refused(
        held, top_k, message):
    layer = RoutedExperts(E, top_k, F, D, held=held)
    with pytest.raises(ValueError, match=message):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, D)))


# ------------------------------------- the second gating rule (deepseek_v2)
#
# ``gating="top_k_of_softmax"``: a softmax over ALL the scores, then the top
# k probabilities as they are, against ``benchmarks/reference/deepseek_v2.py``.

import deepseek_toy  # noqa: E402

DS_CFG = dict(
    deepseek_toy.TOY, hidden_size=D, moe_intermediate_size=F,
    n_routed_experts=E, num_experts_per_tok=K)


def ds_share(held):
    lo, hi = held
    return dict(DS_CFG, n_routed_experts=hi - lo, experts_held=[lo, hi],
                n_routed_experts_published=E)


def ds_layer_out(w, x, held=None):
    lo, hi = held or (0, E)
    layer = RoutedExperts(E, K, F, D, held=held, gating="top_k_of_softmax")
    params = {"router_kernel": w["router"], "in_kernel": w["we_in"][lo:hi],
              "out_kernel": w["we_out"][lo:hi]}
    return np.asarray(layer.apply({"params": params}, x[None])[0])


def ds_reference_out(w, x, held=None):
    lo, hi = held or (0, E)
    cut = dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
    return np.asarray(deepseek_toy.reference.routed_experts(
        x, cut, cfg=ds_share((lo, hi)), einsum=jnp.einsum)[0])


def test_the_default_gating_is_the_rule_it_always_was():
    scores = jnp.asarray(np.random.default_rng(0).standard_normal((5, E)))
    for got, want in zip(moe.route(scores, K), route_top_k(scores, K)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert RoutedExperts(E, K, F, D).gating == "softmax_of_top_k"


def test_unrenormalised_gates_are_the_softmax_over_all_scores():
    scores = jnp.asarray(
        np.random.default_rng(1).standard_normal((7, E)), jnp.float32)
    gates, experts = moe.route(scores, K, "top_k_of_softmax")
    probs = np.asarray(jax.nn.softmax(scores, axis=-1))
    np.testing.assert_allclose(
        np.asarray(gates), np.take_along_axis(probs, np.asarray(experts), -1),
        rtol=1e-6)
    assert (np.asarray(gates).sum(-1) < 0.999).all()  # NOT renormalised
    # The same experts as the default rule chooses: softmax is monotone.
    np.testing.assert_array_equal(
        np.asarray(experts), np.asarray(route_top_k(scores, K)[1]))


def test_an_unknown_gating_rule_is_refused():
    with pytest.raises(ValueError, match="unknown gating rule"):
        ds = RoutedExperts(E, K, F, D, gating="sigmoid")
        ds.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, D)))


def test_unrenormalised_layer_matches_the_reference():
    w, x = weights(21), tokens_in(40, seed=22)
    assert np.abs(ds_layer_out(w, x) - ds_reference_out(w, x)).max() < TOL
    # ... and is not the default rule's layer.
    assert np.abs(ds_layer_out(w, x) - layer_out(w, x)).max() > 0.01


def test_the_eight_shares_and_the_shared_expert_counted_once_are_the_whole_layer():
    """What the eight chips of the deployment compute of one layer: each its
    one expert's part (the toy's eighth), and all of them the shared experts
    alike. The eight parts and ONE shared output add up to the uncut
    reference's feed-forward; each part is also the reference's, given the
    same share."""
    from distributed_pytorch_tpu.models.transformer import MLPBlock

    w, x = weights(23), tokens_in(24, seed=24)
    rng = np.random.default_rng(25)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    ws = {"ws_gate": f(D, 2 * F), "ws_up": f(D, 2 * F), "ws_down": f(2 * F, D)}
    shared = np.asarray(
        MLPBlock(2 * F, D, kind="gated_silu", use_bias=False).apply(
            {"params": {"gate": {"kernel": ws["ws_gate"]},
                        "up": {"kernel": ws["ws_up"]},
                        "down": {"kernel": ws["ws_down"]}}}, x))
    parts = []
    for e in range(E):
        got = ds_layer_out(w, x, (e, e + 1))
        assert np.abs(got - ds_reference_out(w, x, (e, e + 1))).max() < TOL
        parts.append(got)
    ref = deepseek_toy.reference
    whole = ds_reference_out(w, x) + np.asarray(ref.gated_mlp(
        x, ws["ws_gate"], ws["ws_up"], ws["ws_down"], jnp.einsum))
    assert np.abs(sum(parts) + shared - whole).max() < TOL
    assert sum(np.abs(p).max() > 1e-3 for p in parts) == E

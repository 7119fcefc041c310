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


@pytest.fixture(params=["xla", "interpret"])
def mode(request):
    """How the layer computes its grouped products: ``jax.lax.ragged_dot``,
    or ``ops/grouped_matmul.py``'s kernel through the Pallas interpreter."""
    return request.param


def layer_out(w, x, held=None, live=None, counts=False, mode="xla"):
    lo, hi = held or (0, E)
    layer = RoutedExperts(E, K, F, D, held=held, paged_kernel=mode)
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


def test_all_experts_held_matches_the_reference(mode):
    w, x = weights(), tokens_in(40)
    want = reference_out(w, x)
    assert np.abs(want).max() > 0.1
    assert np.abs(layer_out(w, x, mode=mode) - want).max() < TOL


@pytest.mark.parametrize("cut", [4, 1, 7])
def test_the_shares_add_up(cut, mode):
    """The parts computed with experts ``0..cut-1`` and ``cut..E-1`` held,
    each against the reference given the same share, sum to the uncut
    reference's layer (the shared expert is no part of this layer: the
    block adds it once, ``test_granite_serving.py`` holds that)."""
    w, x = weights(2), tokens_in(33, seed=3)
    parts = []
    for held in ((0, cut), (cut, E)):
        got = layer_out(w, x, held, mode=mode)
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


def test_no_token_is_dropped_when_every_token_goes_to_one_expert(mode):
    """The load a capacity would drop at: all 64 tokens choose expert 5
    first (and 6, 2 behind it). Every one of them gets expert 5's part
    (the kernel: groups of four row tiles, and experts no row reached)."""
    w = weights(4, router=pointing_router([5, 6, 2, 0, 1, 3, 4, 7]))
    x = jnp.abs(tokens_in(64, seed=5))
    got, counts = layer_out(w, x, counts=True, mode=mode)
    assert counts.tolist() == [0, 0, 64, 0, 0, 64, 64, 0]
    assert np.abs(got - reference_out(w, x)).max() < TOL
    only = layer_out(w, x, held=(5, 6), mode=mode)
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


def test_no_token_to_a_held_expert_gives_zeros(mode):
    w = weights(6, router=pointing_router([0, 1, 2, 3, 4, 5, 6, 7]))
    x = jnp.abs(tokens_in(16, seed=7))
    got, counts = layer_out(w, x, held=(4, 8), counts=True, mode=mode)
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


def test_rows_that_carry_no_request_are_computed_by_nobody(mode):
    w, x = weights(10), tokens_in(6, seed=11)
    layer = RoutedExperts(E, K, F, D, paged_kernel=mode)
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


def ds_layer_out(w, x, held=None, mode="xla"):
    lo, hi = held or (0, E)
    layer = RoutedExperts(E, K, F, D, held=held, gating="top_k_of_softmax",
                          paged_kernel=mode)
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


def test_unrenormalised_layer_matches_the_reference(mode):
    w, x = weights(21), tokens_in(40, seed=22)
    assert np.abs(
        ds_layer_out(w, x, mode=mode) - ds_reference_out(w, x)).max() < TOL
    # ... and is not the default rule's layer.
    assert np.abs(ds_layer_out(w, x) - layer_out(w, x)).max() > 0.01


def test_the_eight_shares_and_the_shared_expert_counted_once_are_the_whole_layer(
        mode):
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
        got = ds_layer_out(w, x, (e, e + 1), mode=mode)
        assert np.abs(got - ds_reference_out(w, x, (e, e + 1))).max() < TOL
        parts.append(got)
    ref = deepseek_toy.reference
    whole = ds_reference_out(w, x) + np.asarray(ref.gated_mlp(
        x, ws["ws_gate"], ws["ws_up"], ws["ws_down"], jnp.einsum))
    assert np.abs(sum(parts) + shared - whole).max() < TOL
    assert sum(np.abs(p).max() > 1e-3 for p in parts) == E


# ------------------------- the grouped products' kernel (ops/grouped_matmul.py)
#
# Through the Pallas interpreter against ``jax.lax.ragged_dot`` on the same
# sorted rows, at toy sizes of the two configurations' products (a first
# product wider than it is deep, a second one deeper, a width that is whole
# lanes and one that is not). float32 operands: the differences are the order
# of the sums.

from distributed_pytorch_tpu.ops import grouped_matmul as gm  # noqa: E402

TILE = gm.ROW_TILE
GROUPS = {
    "an-expert-no-row-reached": [5, 0, 9, 0, 3],
    "the-first-experts-unreached": [0, 0, 7, 11, 0],
    "all-rows-on-absent-experts": [0, 0, 0, 0, 0],
    "a-group-larger-than-a-tile": [3, 2 * TILE + 5, 4, 0, 1],
    "sizes-no-multiple-of-the-tile": [TILE + 1, TILE - 1, 2 * TILE + 3, 1, 0],
    "groups-that-end-on-a-tile": [TILE, 2 * TILE, 0, TILE, 0],
    "one-group-is-all-the-rows": [0, 0, 6 * TILE, 0, 0],
}
WIDTHS = {"in-32x48": (32, 48), "out-24x32": (24, 32), "lanes-128x256": (128, 256)}


def sorted_rows(sizes, k, n, rows=6 * TILE, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((rows, k)), jnp.float32),
            jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.float32),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_the_kernel_is_ragged_dot_on_the_rows_of_a_group(groups, widths):
    sizes = GROUPS[groups]
    x, w, s = sorted_rows(sizes, *WIDTHS[widths])
    want = np.asarray(jax.lax.ragged_dot(x, w, s))
    got = np.asarray(gm.grouped_matmul(x, w, s, mode="interpret"))
    held = sum(sizes)
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got[:held], want[:held], atol=2e-5)
    if held:
        assert np.abs(want[:held]).max() > 1.0


@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_the_hosts_rule_counts_the_tiles_the_kernel_walks(groups):
    """``moe_rows_computed`` is ``rows_computed`` of the routing counts: the
    tiles the kernel says it multiplied, a reached expert, are that rule's."""
    sizes = GROUPS[groups]
    x, w, s = sorted_rows(sizes, 32, 48)
    _, walked = gm._stationary(x, w, s, interpret=True)
    walked = np.asarray(walked)
    assert int(walked.sum()) * TILE == gm.rows_computed(sizes)
    assert ((walked > 0) == (np.asarray(sizes) > 0)).all()
    # No tile for less than a row, none twice over.
    assert (walked * TILE >= np.asarray(sizes)).all()
    assert (walked <= -(-np.asarray(sizes) // TILE) + 1).all()
    # A batch of programs and layers is summed over.
    assert gm.rows_computed([[sizes, sizes]]) == 2 * gm.rows_computed(sizes)


def test_rows_that_are_no_whole_tiles_are_padded_and_cut_again():
    x, w, s = sorted_rows([3, 0, 4], 32, 48, rows=TILE + 5)
    want = np.asarray(jax.lax.ragged_dot(x, w, s))
    got = np.asarray(gm.grouped_matmul(x, w, s, mode="interpret"))
    assert got.shape == want.shape
    np.testing.assert_allclose(got[:7], want[:7], atol=2e-5)


def test_bfloat16_operands_accumulate_in_float32():
    """The configurations' arithmetic: bf16 operands, float32 sums and
    results. Against the float32 product of the SAME bf16 numbers."""
    x, w, s = sorted_rows([TILE + 3, 0, 9], 128, 256)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    got = np.asarray(gm.grouped_matmul(xb, wb, s, mode="interpret"))
    want = np.asarray(jax.lax.ragged_dot(
        xb.astype(jnp.float32), wb.astype(jnp.float32), s))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got[: TILE + 12], want[: TILE + 12], atol=1e-4)


@pytest.mark.parametrize("gating", moe.GATINGS)
def test_masked_tokens_of_a_piece_reach_no_expert_under_any_gating(
        gating, mode):
    """A prefill piece's padding (``live [B, T]``): the same result for the
    tokens that count, zeros for the rest, and the same counts, whichever
    product computes them."""
    w, x = weights(30), tokens_in(24, seed=31)
    live = jnp.asarray(np.arange(24) < 17)[None]
    params = {"router_kernel": w["router"], "in_kernel": w["we_in"][2:7],
              "out_kernel": w["we_out"][2:7]}
    if gating in moe.BIASED_GATINGS:  # the rules that have a correction bias
        params["router_bias"] = jnp.asarray(
            0.2 * np.random.default_rng(32).standard_normal(E), jnp.float32)

    def run(kernel):
        layer = RoutedExperts(E, K, F, D, held=(2, 7), gating=gating,
                              paged_kernel=kernel)
        out, sown = layer.apply(
            {"params": params}, x[None], live=live, mutable=["routing"])
        return np.asarray(out[0]), np.asarray(sown["routing"]["counts"][0])

    (got, counts), (want, want_counts) = run(mode), run("xla")
    assert np.abs(got - want).max() < TOL
    assert (got[17:] == 0).all() and np.abs(got[:17]).max() > 0.01
    assert counts.tolist() == want_counts.tolist()
    assert int(counts.sum()) == 17 * K


def test_the_products_mode_follows_the_blocks_kernel(monkeypatch):
    from distributed_pytorch_tpu.ops import paged_attention

    assert moe.product_mode("", 256, 128) == "xla"
    assert moe.product_mode("xla", 256, 128) == "xla"
    assert moe.product_mode("interpret", D, F) == "interpret"
    assert moe.product_mode("auto", 256, 128) == "xla"  # no TPU here
    with pytest.raises(ValueError, match="unknown paged-attention kernel"):
        moe.product_mode("mosaic", 256, 128)
    # On a TPU: the kernel, but for widths that are no whole lanes (the
    # compiled kernel copies whole lanes; test_chip_compile.py compiles it).
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    assert moe.product_mode("auto", 4096, 768) == "pallas"
    assert moe.product_mode("auto", 2048, 1408) == "pallas"
    assert moe.product_mode("auto", D, F) == "xla"
    assert moe.product_mode("pallas", 256, 64) == "xla"


# --------------------------- the exaone_moe rule: sigmoid, a bias, gates x 2.5


def test_the_eight_shares_of_scaled_sigmoid_gates_are_the_whole_layer(mode):
    """``k-exaone-236b-a23b``'s routed layer on the deployment's eight chips:
    sigmoid scores, a correction bias for the CHOICE alone, gates renormalised
    and multiplied by 2.5 (``RoutedExperts.scale``). Each chip's part is the
    reference's given the same share; the eight parts and ONE shared expert
    add up to the uncut reference's feed-forward; and the scale is in it."""
    import exaone_toy

    ref = exaone_toy.reference
    cfg = dict(exaone_toy.TOY, hidden_size=D, moe_intermediate_size=F,
               num_experts=E, num_experts_per_tok=K)
    rng = np.random.default_rng(31)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    w = {"router": f(D, E), "router_bias": f(E) / 3, "we_in": f(E, D, 2 * F),
         "we_out": f(E, F, D), "ws_gate": f(D, F), "ws_up": f(D, F),
         "ws_down": f(F, D)}
    x = tokens_in(24, seed=32)

    def part(held, scale=2.5):
        lo, hi = held
        layer = RoutedExperts(
            E, K, F, D, held=held, paged_kernel=mode, gating="sigmoid_biased",
            scale=scale)
        return np.asarray(layer.apply({"params": {
            "router_kernel": w["router"], "router_bias": w["router_bias"],
            "in_kernel": w["we_in"][lo:hi],
            "out_kernel": w["we_out"][lo:hi]}}, x[None])[0])

    def wanted(held):
        lo, hi = held
        cut = dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
        return np.asarray(ref.routed_experts(x, cut, cfg=dict(
            cfg, num_experts=hi - lo, experts_held=[lo, hi],
            num_experts_published=E), einsum=jnp.einsum)[0])

    parts = [part((e, e + 1)) for e in range(E)]
    for e, got in enumerate(parts):
        assert np.abs(got - wanted((e, e + 1))).max() < TOL
    shared = np.asarray(ref.gated_mlp(
        x, w["ws_gate"], w["ws_up"], w["ws_down"], jnp.einsum))
    whole = wanted((0, E)) + shared
    assert np.abs(sum(parts) + shared - whole).max() < 2 * TOL
    assert np.abs(part((0, E)) - 2.5 * part((0, E), scale=1.0)).max() < 2 * TOL
    assert np.abs(part((0, E), scale=1.0)).max() > 0.01


# ------------- the zaya rule: the ONE best of softmax + bias, a router network


def test_the_best_of_softmax_plus_bias_at_its_own_probability():
    """``GATINGS``' fourth rule: a softmax over ALL the scores, the ``top_k``
    experts of largest ``p + bias`` (the bias for the CHOICE alone), gates
    their own probabilities, not renormalised."""
    rng = np.random.default_rng(41)
    scores = jnp.asarray(rng.standard_normal((9, E)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(E), jnp.float32)
    p = np.asarray(jax.nn.softmax(scores, axis=-1))
    for k in (1, 3):
        gates, experts = moe.route(scores, k, "softmax_biased", bias)
        want = np.argsort(-(p + np.asarray(bias)), axis=-1)[:, :k]
        assert np.array_equal(np.asarray(experts), want)
        assert np.allclose(np.asarray(gates),
                           np.take_along_axis(p, want, axis=-1), atol=1e-7)
        assert np.all(np.asarray(gates).sum(-1) < 1.0)
    plain, _ = moe.route(scores, 1, "softmax_biased")
    assert np.allclose(np.asarray(plain)[:, 0], p.max(axis=-1), atol=1e-7)
    # The bias moves choices, never a gate of the same expert.
    _, unbiased = moe.route(scores, 1, "softmax_biased")
    _, biased = moe.route(scores, 1, "softmax_biased", bias)
    assert (np.asarray(unbiased) != np.asarray(biased)).any()


#: sha256 (first 16) of the layer's float32 output bytes on seeded inputs,
#: recorded at commit 640088f (PR 48), before the router became an option.
LINEAR_ROUTER_BITS = {
    "softmax_of_top_k": "45af69df8239f168",
    "top_k_of_softmax": "ef0c61535040f9a1",
    "sigmoid_biased": "5dc6d65f707a6f53",
}


@pytest.mark.parametrize("gating", sorted(LINEAR_ROUTER_BITS))
def test_a_model_on_the_linear_router_gives_the_bits_it_gave(gating):
    """The default router keeps its parameter names and its arithmetic: the
    nine configurations before the router network see the same layer."""
    import hashlib

    rng = np.random.default_rng(49)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    params = {"router_kernel": f(D, E), "in_kernel": f(5, D, 2 * F),
              "out_kernel": f(5, F, D), "router_bias": f(E) / 3}
    x = f(2, 12, D)
    biased = gating == "sigmoid_biased"
    if not biased:
        params.pop("router_bias")
    layer = RoutedExperts(E, K, F, D, held=(2, 7), gating=gating,
                          scale=2.5 if biased else 1.0)
    out = layer.apply({"params": params}, x)
    assert hashlib.sha256(np.asarray(out).tobytes()).hexdigest()[:16] == (
        LINEAR_ROUTER_BITS[gating])
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    assert sorted(shapes) == sorted(params)


@pytest.mark.parametrize("carried", [False, True])
def test_the_router_network_with_its_carry_matches_the_reference(mode, carried):
    """``router="mlp_carry"`` under the fourth rule at ONE expert a token,
    every expert held: the layer's output, its counts and the carry it hands
    on are ``benchmarks/reference/zaya.py``'s, with and without a carry from
    the layer before."""
    import zaya_toy

    ref, cfg = zaya_toy.reference, zaya_toy.TOY
    d, f_, e, r = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                   cfg["num_experts"], cfg["router_hidden_size"])
    w = {k: v.astype(jnp.float32) for k, v in ref.make_weights(
        cfg, zaya_toy.SEED)["layers"][1].items()}
    rng = np.random.default_rng(51)
    x = jnp.asarray(rng.standard_normal((20, d)), jnp.float32)
    carry = jnp.asarray(rng.standard_normal((20, r)), jnp.float32) * carried
    want, routed, want_carry = ref.experts(
        x, w, carry, cfg=cfg, einsum=jnp.einsum)
    layer = RoutedExperts(
        e, 1, f_, d, held=(0, e), gating="softmax_biased", router="mlp_carry",
        router_hidden=r, paged_kernel=mode)
    params = {
        "router": {"down": w["rd"], "carry_scale": w["gamma"],
                   "norm": {"scale": w["rn_g"]}, "w1": w["r1"],
                   "w2": w["r2"], "w3": w["r3"]},
        "router_bias": w["router_bias"], "in_kernel": w["we_in"],
        "out_kernel": w["we_out"]}
    (got, got_carry), sown = layer.apply(
        {"params": params}, x[None], carry=carry[None] if carried else None,
        mutable=["routing"])
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(got_carry[0]) - np.asarray(want_carry)).max() < TOL
    assert np.array_equal(np.asarray(sown["routing"]["counts"][0]),
                          np.asarray(routed).sum(axis=0))
    assert np.asarray(routed).sum(axis=0).max() < 20  # more than one expert
    with pytest.raises(ValueError, match="unknown router"):
        RoutedExperts(e, 1, f_, d, router="tree").apply(
            {"params": params}, x[None])

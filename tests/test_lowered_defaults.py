"""Every default is the block it was: the serving programs of the language
models the benchmark serves lower to the bytes they lowered to at the parent
commit (``tests/lowered_defaults.py`` says what is lowered and how to record
it again).

The three older models' twelve were recorded for PR 35 from commit 28941f2
(PR 32) and from PR 35's tree, which gave the same digests; the three newer
families' twelve (``latent_routed``, ``sparse_window_latent``,
``hybrid_gated_delta``) for PR 45 from commit b5b6f72 (PR 44), before the
engine handed the counting of a dispatch's reads to ``serving/decode_reads.py``.

PR 47 recorded twelve again, from its own tree against commit 58a529b (PR 46):
the eight ``prefill8`` digests of the four toys with K/V attention layers
(``default_block_gqa``, ``hybrid_gated_delta``, ``hybrid_mamba2_routed``,
``hybrid_s6``; gather and kernel), whose chunk read became a walk over the
blocks a row holds (``ops/paged_attention.py`` ``_paged_walk``), and those
toys' four ``.gather.decode`` digests, which moved ONTO their ``.kernel.decode``
digests: with the kernel off a decode step now goes through
``paged_attention(kernel="xla")`` too, so the program is byte for byte the one
the parent lowered for ``paged_kernel="xla"`` (it works the rows' positions
out a second time where the inline lines reused the model's, and the SSA
numbers after that shift: nothing else differs from the parent's
``.gather.decode`` text). The other twelve, every ``latent_routed.*``,
``sparse_window_latent.*`` and ``.kernel.decode``, and the two interpreted
latent decode digests the script prints, came out equal on both sides.
All under jax 0.9.0 / flax 0.12.3."""

import pytest

import lowered_defaults

PINNED = {
    "default_block_gqa.gather.decode": "4faaa8afe500a85d",
    "default_block_gqa.gather.prefill8": "88a546fc6e9d87e4",
    "default_block_gqa.kernel.decode": "4faaa8afe500a85d",
    "default_block_gqa.kernel.prefill8": "88a546fc6e9d87e4",
    "hybrid_gated_delta.gather.decode": "4fb3b5d3920e2fc2",
    "hybrid_gated_delta.gather.prefill8": "2906b7d300f860ba",
    "hybrid_gated_delta.kernel.decode": "4fb3b5d3920e2fc2",
    "hybrid_gated_delta.kernel.prefill8": "2906b7d300f860ba",
    "hybrid_mamba2_routed.gather.decode": "1c6edfab0cd0ef14",
    "hybrid_mamba2_routed.gather.prefill8": "a6972b5dc6cc907b",
    "hybrid_mamba2_routed.kernel.decode": "1c6edfab0cd0ef14",
    "hybrid_mamba2_routed.kernel.prefill8": "a6972b5dc6cc907b",
    "hybrid_s6.gather.decode": "fa5894e99101e658",
    "hybrid_s6.gather.prefill8": "2cd2d2598016e8e4",
    "hybrid_s6.kernel.decode": "fa5894e99101e658",
    "hybrid_s6.kernel.prefill8": "2cd2d2598016e8e4",
    "latent_routed.gather.decode": "015a039241a87f9a",
    "latent_routed.gather.prefill8": "c1217038b934d3ee",
    "latent_routed.kernel.decode": "746cc00fac6bb03e",
    "latent_routed.kernel.prefill8": "c1217038b934d3ee",
    "sparse_window_latent.gather.decode": "ac4598d00e54f25b",
    "sparse_window_latent.gather.prefill8": "dbdfddea9a340f0d",
    "sparse_window_latent.kernel.decode": "639456a483ebd333",
    "sparse_window_latent.kernel.prefill8": "dbdfddea9a340f0d",
}


@pytest.fixture(scope="module")
def digests():
    return lowered_defaults.digests()


def test_nothing_is_lowered_that_is_not_pinned(digests):
    assert sorted(digests) == sorted(PINNED)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_a_default_program_lowers_to_the_bytes_it_did(digests, name):
    assert digests[name] == PINNED[name], (
        f"{name} lowers to other StableHLO than at the recorded commit: a "
        f"default changed (or jax did: see tests/lowered_defaults.py)")

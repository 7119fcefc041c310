"""Every default is the block it was: the serving programs of the language
models the benchmark serves lower to the bytes they lowered to at the parent
commit (``tests/lowered_defaults.py`` says what is lowered and how to record
it again).

The three older models' twelve were recorded for PR 35 from commit 28941f2
(PR 32) and from PR 35's tree, which gave the same digests; the three newer
families' twelve (``latent_routed``, ``sparse_window_latent``,
``hybrid_gated_delta``) for PR 45 from commit b5b6f72 (PR 44), before the
engine handed the counting of a dispatch's reads to ``serving/decode_reads.py``.
All under jax 0.9.0 / flax 0.12.3."""

import pytest

import lowered_defaults

PINNED = {
    "default_block_gqa.gather.decode": "4a4d42ef0fa46d87",
    "default_block_gqa.gather.prefill8": "2f950e1896218678",
    "default_block_gqa.kernel.decode": "4faaa8afe500a85d",
    "default_block_gqa.kernel.prefill8": "2f950e1896218678",
    "hybrid_gated_delta.gather.decode": "4499298c598b149f",
    "hybrid_gated_delta.gather.prefill8": "3c03650ba553d999",
    "hybrid_gated_delta.kernel.decode": "4fb3b5d3920e2fc2",
    "hybrid_gated_delta.kernel.prefill8": "3c03650ba553d999",
    "hybrid_mamba2_routed.gather.decode": "52031c5f9f1488fb",
    "hybrid_mamba2_routed.gather.prefill8": "e5295ccf53c628d9",
    "hybrid_mamba2_routed.kernel.decode": "1c6edfab0cd0ef14",
    "hybrid_mamba2_routed.kernel.prefill8": "e5295ccf53c628d9",
    "hybrid_s6.gather.decode": "c85d5fff30dc4ff5",
    "hybrid_s6.gather.prefill8": "e066e27c0ca500f9",
    "hybrid_s6.kernel.decode": "fa5894e99101e658",
    "hybrid_s6.kernel.prefill8": "e066e27c0ca500f9",
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

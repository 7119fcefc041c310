"""Every default is the block it was: the serving programs of the three
language models the benchmark served before latent attention lower to the
bytes they lowered to at the parent commit (``tests/lowered_defaults.py`` says
what is lowered and how to record it again).

Recorded for PR 35 from commit 28941f2 (PR 32) and from PR 35's tree, which
gave the same twelve digests, under jax 0.9.0 / flax 0.12.3."""

import pytest

import lowered_defaults

PINNED = {
    "default_block_gqa.gather.decode": "4a4d42ef0fa46d87",
    "default_block_gqa.gather.prefill8": "2f950e1896218678",
    "default_block_gqa.kernel.decode": "4faaa8afe500a85d",
    "default_block_gqa.kernel.prefill8": "2f950e1896218678",
    "hybrid_mamba2_routed.gather.decode": "52031c5f9f1488fb",
    "hybrid_mamba2_routed.gather.prefill8": "e5295ccf53c628d9",
    "hybrid_mamba2_routed.kernel.decode": "1c6edfab0cd0ef14",
    "hybrid_mamba2_routed.kernel.prefill8": "e5295ccf53c628d9",
    "hybrid_s6.gather.decode": "c85d5fff30dc4ff5",
    "hybrid_s6.gather.prefill8": "e066e27c0ca500f9",
    "hybrid_s6.kernel.decode": "fa5894e99101e658",
    "hybrid_s6.kernel.prefill8": "e066e27c0ca500f9"
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

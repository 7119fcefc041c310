"""What the engine counts of its decode dispatches' reads is what it counted at
the recorded commit, whoever counts it: every ``step`` slice's ``decode_*`` and
``state_*`` args, every ``dsa.select`` and ``prefill.chunk`` event's args and
the same keys of ``stats()``, key for key and value for value, on the scripted
runs of ``tests/decode_reads_recorded.py`` (which says what is run and how to
record it again).

Recorded for PR 45 from commit b5b6f72 (PR 44), where the engine counted a
kind of layer at a time in six methods of its own, twice a traced dispatch;
``serving/decode_reads.py`` counts them since. Recorded again for PR 47 from
its own tree: the ``prefill.chunk`` events of the three families with K/V
attention layers carry two more args, ``keys_walked`` and ``keys_table`` (64
and 64: a toy's table is one block of the chunk walk); with those two keys
taken out the file is the one recorded for PR 45, value for value."""

import json

import pytest

import decode_reads_recorded as runs

#: A tracer's runs' only (the count allocates ``num_pages`` numbers).
TRACED_ONLY = "decode_index_tokens_scored_distinct"


@pytest.fixture(scope="module")
def recorded():
    with open(runs.RECORDED) as f:
        return json.load(f)


def test_every_family_is_recorded_in_each_of_its_modes(recorded):
    assert {family: sorted(modes) for family, modes in recorded.items()} == {
        family: sorted(runs.modes(family)) for family in runs.FAMILIES}


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
@pytest.mark.parametrize("family", sorted(runs.FAMILIES))
def test_a_family_counts_what_it_counted(recorded, family, traced):
    got = json.loads(json.dumps(runs.record(family, traced)))
    assert sorted(got) == sorted(runs.modes(family)[:None if traced else 2])
    for mode in got:
        want = recorded[family][mode]
        if traced:
            assert got[mode] == want, (family, mode)
            continue
        # No tracer: no slice and no event, and ``stats()`` all the same.
        assert sorted(got[mode]) == ["stats"]
        stats = dict(want["stats"])
        if TRACED_ONLY in stats:
            assert stats[TRACED_ONLY] > 0
            stats[TRACED_ONLY] = 0
        assert got[mode]["stats"] == stats, (family, mode)

"""Flash block-size selection: candidate legality and lookup tiers (the
measured sweep itself needs real hardware; its results ship in
DEFAULT_TABLE)."""

import json

import pytest

from distributed_pytorch_tpu.ops import flash_autotune as fa


@pytest.fixture(autouse=True)
def _isolated_caches(tmp_path, monkeypatch):
    """Every test sees an empty disk cache (a dev box where a real sweep ran
    must not leak measured winners in) and a clean in-process cache."""
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    monkeypatch.setattr(fa, "_runtime_cache", {})


def test_candidates_are_legal():
    for t in (2048, 8192, 16384):
        for d in (64, 128):
            cands = list(fa.candidates(t, d))
            assert cands, (t, d)
            for bq, bk in cands:
                assert t % bq == 0 and t % bk == 0
                assert bk % 128 == 0  # lane alignment
                # VMEM bound honored
                assert bq * bk * 4 + 2 * bk * d * 4 <= 12 * 2**20


def test_lookup_uses_shipped_table_nearest_bucket():
    # Exact bucket.
    assert fa.lookup(16384, 64, device_kind="TPU v5 lite") == (1024, 1024)
    # Nearest bucket: T=12288 sits nearer 16384 than 8192... check stability
    # for an off-table T and d.
    blocks = fa.lookup(4096, 96, device_kind="TPU v5 lite")
    assert blocks in set(fa.DEFAULT_TABLE["tpu v5 lite"].values())


def test_lookup_on_unknown_device_uses_analytic_default():
    # Round-3 VERDICT: unknown chips were pinned to the bare (512, 1024)
    # guess; now they get the VMEM-reasoned largest legal tile.
    blocks = fa.lookup(8192, 64, device_kind="TPU v99")
    assert blocks == fa.analytic_default(8192, 64)
    assert blocks in set(fa.candidates(8192, 64))


def test_analytic_default_legality_and_preference():
    for t in (2048, 4096, 8192, 16384, 32768):
        for d in (64, 128, 256):
            bq, bk = fa.analytic_default(t, d)
            assert t % bq == 0 and t % bk == 0, (t, d)
            assert bq * bk * 4 + 2 * bk * d * 4 <= 12 * 2**20, (t, d)
    # At long T / d=64 every large candidate is legal: picks the largest
    # area, square-preferred — matching the measured v5e winner.
    assert fa.analytic_default(16384, 64) == (1024, 1024)
    # Odd T with no standard divisor degrades to the legacy fallback.
    assert fa.analytic_default(1000, 64) == fa._FALLBACK


def test_disk_cache_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    fa._save_disk_cache({("tpu v5 lite", 1024, 64, "bfloat16", True): (256, 512)})
    got = fa._load_disk_cache()
    assert got[("tpu v5 lite", 1024, 64, "bfloat16", True)] == (256, 512)
    # Cache file is valid JSON on disk.
    with open(fa._cache_path()) as f:
        json.load(f)


def test_runtime_cache_wins_over_table(monkeypatch):
    key = fa._key("TPU v5 lite", 16384, 64, "bfloat16", True)
    monkeypatch.setitem(fa._runtime_cache, key, (256, 256))
    assert fa.lookup(16384, 64, device_kind="TPU v5 lite") == (256, 256)


class TestShippedTableFile:
    """FLASH_BLOCKS_TABLE: the pod workflow — an exported table outranks the
    host's private disk cache, so all hosts pick identical blocks."""

    def test_explicit_table_wins(self, tmp_path, monkeypatch):
        import json

        from distributed_pytorch_tpu.ops import flash_autotune as fa

        key = fa._key("tpu v99", 4096, 64, "bfloat16", True)
        table = tmp_path / "blocks.json"
        table.write_text(json.dumps({json.dumps(list(key)): [256, 512]}))
        monkeypatch.setenv("FLASH_BLOCKS_TABLE", str(table))
        monkeypatch.setattr(fa, "_runtime_cache", {})
        fa._load_table_file.cache_clear()
        assert fa.lookup(4096, 64, "bfloat16", True, device_kind="tpu v99") == (
            256,
            512,
        )

    def test_missing_table_fails_loudly(self, tmp_path, monkeypatch):
        import pytest

        from distributed_pytorch_tpu.ops import flash_autotune as fa

        monkeypatch.setenv("FLASH_BLOCKS_TABLE", str(tmp_path / "absent.json"))
        monkeypatch.setattr(fa, "_runtime_cache", {})
        fa._load_table_file.cache_clear()
        with pytest.raises(FileNotFoundError):
            fa.lookup(4096, 64, "bfloat16", True, device_kind="tpu v99")

    def test_shape_not_in_table_falls_through(self, tmp_path, monkeypatch):
        import json

        from distributed_pytorch_tpu.ops import flash_autotune as fa

        table = tmp_path / "blocks.json"
        table.write_text(json.dumps({}))
        monkeypatch.setenv("FLASH_BLOCKS_TABLE", str(table))
        monkeypatch.setattr(fa, "_runtime_cache", {})
        fa._load_table_file.cache_clear()
        # Unknown device, empty table -> analytic VMEM-reasoned default.
        assert fa.lookup(
            4096, 64, "bfloat16", True, device_kind="tpu v99"
        ) == fa.analytic_default(4096, 64)


@pytest.mark.parametrize(
    "kind, seeded, tier",
    [
        ("TPU v5 lite", False, "shipped_table"),
        ("TPU v99", False, "analytic"),
        # A winner in this machine's per-user file outranks the committed
        # table — and says so, which is how chip_smoke.py notices.
        ("TPU v5 lite", True, "disk_cache"),
    ],
)
def test_lookup_names_its_tier(kind, seeded, tier):
    if seeded:
        key = fa._key(kind, 8192, 128, "bfloat16", True)
        paged = fa._paged_key(kind, 2048, 16, 128, "bfloat16")
        fa._save_disk_cache({key: (256, 512), paged: (4, 64)})
    blocks, got = fa.lookup_with_tier(8192, 128, device_kind=kind)
    assert got == tier
    assert blocks == fa.lookup(8192, 128, device_kind=kind)
    npb, got = fa.lookup_paged_with_tier(
        2048, 16, 128, "bfloat16", device_kind=kind
    )
    assert got == {"analytic": "fallback"}.get(tier, tier)
    assert npb == fa.lookup_paged(2048, 16, 128, "bfloat16", device_kind=kind)
    assert (blocks, npb) == ((256, 512), 4) if seeded else npb in (
        fa._PAGED_FALLBACK, fa.PAGED_DEFAULT_TABLE["tpu v5 lite"]
    )

"""bench.py's failure path: one parseable JSON line naming the metric that
was not measured (``main`` then exits non-zero), and the peak table's
refusal of a TPU it does not know.
"""

import json
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import bench  # noqa: E402


class TestEmitFailure:
    def _capture(self, capsys, **kw):
        bench.emit_failure(**kw)
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 1, out
        return json.loads(out[0])

    def test_single_parseable_line_with_cause(self, capsys):
        row = self._capture(
            capsys,
            error="backend_unavailable",
            detail="RuntimeError: no backend\nmore context",
            stage="init",
        )
        assert row["error"] == "backend_unavailable"
        assert row["stage"] == "init"
        assert row["value"] is None
        assert row["vs_baseline"] is None
        assert "more context" in row["detail"]  # last line of the detail

    def test_metric_name_follows_mode(self, capsys):
        row = self._capture(
            capsys,
            error="bench_failed",
            detail="x",
            stage="measure",
            metric="dp_weak_scaling_efficiency",
            unit="ratio_vs_1dev",
        )
        assert row["metric"] == "dp_weak_scaling_efficiency"
        assert row["unit"] == "ratio_vs_1dev"

    def test_detail_truncated(self, capsys):
        row = self._capture(
            capsys, error="e", detail="y" * 10_000, stage="measure"
        )
        assert len(row["detail"]) <= 400


def test_peak_flops_table():
    class FakeDev:
        def __init__(self, kind):
            self.device_kind = kind

    assert bench.peak_flops_per_chip(FakeDev("TPU v5 lite")) == 197e12
    assert bench.peak_flops_per_chip(FakeDev("TPU v4")) == 275e12
    # A TPU the table does not know is an error, never another chip's peak.
    with pytest.raises(ValueError, match="TPU v99"):
        bench.peak_flops_per_chip(FakeDev("TPU v99"))


@pytest.mark.parametrize("stage", ["init", "measure"])
def test_main_exits_nonzero_on_failure(stage, monkeypatch, capsys, tmp_path):
    """No backend, or a measurement that raises: one failure line and a
    non-zero exit — never a run that looks like it succeeded."""
    import jax

    def boom(*a, **k):
        raise RuntimeError(f"no {stage}")

    # With the variable set the cache helper sets nothing in this process.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    if stage == "init":
        monkeypatch.setattr(jax, "devices", boom)
    else:
        monkeypatch.setattr(bench, "run_benches", boom)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["stage"] == stage and row["value"] is None
    assert f"no {stage}" in row["detail"]

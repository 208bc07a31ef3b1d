"""tools/ci_gate.py — the one-command pre-merge gate, in tier-1
(jax-free).

The contract under test:

* the REAL repo is green through all three chained gates (obs_lint +
  bench_schema + bench_trend) — this test IS the pre-merge check;
* a single failing gate turns the whole chain non-zero (drift can
  never ride through on a green neighbour);
* an unimportable gate counts as FAILED, never silently skipped.
"""

import importlib.util
import os

import pytest

pytestmark = pytest.mark.rebalance

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "ci_gate_under_test",
    os.path.join(REPO, "tools", "ci_gate.py"))
GATE = importlib.util.module_from_spec(spec)
spec.loader.exec_module(GATE)


def test_gate_order_is_the_documented_chain():
    assert GATE.GATES == ("obs_lint", "bench_schema", "bench_trend")


def _artifact(value: float) -> dict:
    return {
        "metric": "entity_ticks_per_sec_per_chip", "value": value,
        "unit": "entity-ticks/s/chip", "vs_baseline": 0.0,
        "entities": 1024, "tick_ms": 5.0, "platform": "tpu",
        "stage": "full", "attempts": [],
    }


def test_real_repo_is_green(tmp_path, capsys):
    """The real docs + a trajectory the test synthesises (the
    repository keeps no artifacts of its own): all three gates run and
    are green; with no --dir the bench gates walk the empty repo root
    and have nothing to object to."""
    import json

    for rno, value in ((1, 1000.0), (2, 1200.0)):
        (tmp_path / f"BENCH_r{rno:02d}.json").write_text(
            json.dumps(_artifact(value)))
    for argv in (["--dir", str(tmp_path)], []):
        assert GATE.main(argv) == 0
        out = capsys.readouterr().out
        # every gate actually ran (no silent skip), verdict printed
        for name in GATE.GATES:
            assert f"== {name} ==" in out
        assert "ci_gate: ok (3 gates green)" in out
    # ... and a regression in the walked trajectory turns it red
    (tmp_path / "BENCH_r03.json").write_text(
        json.dumps(_artifact(300.0)))
    assert GATE.main(["--dir", str(tmp_path)]) == 2


def test_threshold_is_forwarded_to_bench_trend_only(monkeypatch):
    seen = {}

    class _Fake:
        def __init__(self, name):
            self.name = name

        def main(self, argv):
            seen[self.name] = list(argv)
            return 0

    monkeypatch.setattr(
        GATE.importlib, "import_module", lambda n: _Fake(n))
    assert GATE.main(["--threshold", "0.25"]) == 0
    assert seen["obs_lint"] == []
    assert seen["bench_schema"] == []
    assert seen["bench_trend"] == ["--threshold", "0.25"]
    assert GATE.main(["--dir", "/x", "--threshold", "0.25"]) == 0
    assert seen["obs_lint"] == []
    assert seen["bench_schema"] == ["--dir", "/x"]
    assert seen["bench_trend"] == ["--dir", "/x", "--threshold", "0.25"]


def test_one_failing_gate_fails_the_chain(monkeypatch, capsys):
    class _Fake:
        def __init__(self, name):
            self.name = name

        def main(self, argv):
            return 2 if self.name == "bench_schema" else 0

    monkeypatch.setattr(
        GATE.importlib, "import_module", lambda n: _Fake(n))
    assert GATE.main([]) == 2
    assert "bench_schema (rc=2)" in capsys.readouterr().out


def test_unimportable_gate_is_a_failure_not_a_skip(monkeypatch,
                                                   capsys):
    def _boom(name):
        if name == "bench_trend":
            raise ImportError("gate deleted")

        class _Ok:
            @staticmethod
            def main(argv):
                return 0

        return _Ok

    monkeypatch.setattr(GATE.importlib, "import_module", _boom)
    assert GATE.main([]) == 2
    out = capsys.readouterr().out
    assert "bench_trend: import failed" in out
    assert "bench_trend (rc=-1)" in out

"""`python -m benchmarks.run` must fail loudly: a phase that raises makes
the command exit nonzero after the remaining phases have run."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def bench_run(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import benchmarks.run as run

    # Keep the persistent compile cache out of the test process.
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    return run


def test_raising_phase_exits_nonzero(bench_run, monkeypatch, capsys):
    ran = []

    def boom():
        raise RuntimeError("phase failed")

    monkeypatch.setattr(bench_run, "ALL", {
        "boom": boom, "after": lambda: ran.append("after") or {}})
    monkeypatch.setattr(sys, "argv", ["benchmarks.run"])
    with pytest.raises(SystemExit) as exc:
        bench_run.main()
    assert exc.value.code == 1
    assert ran == ["after"]
    assert "boom: raised RuntimeError" in capsys.readouterr().out


def test_passing_phases_exit_zero(bench_run, monkeypatch):
    monkeypatch.setattr(bench_run, "ALL",
                        {"ok": lambda: {"meets_target": True}})
    monkeypatch.setattr(sys, "argv", ["benchmarks.run"])
    bench_run.main()

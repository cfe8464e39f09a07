"""`benchmarks/gamma_accuracy.py`, the diagnostic behind the default Γ
path, at a small size on the CPU: every row is produced, and the numbers
it reports hold the contracts the CPU path is known to meet."""
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def acc(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import benchmarks.gamma_accuracy as acc

    return acc


def test_op_accuracy_rows(acc):
    out = acc.op_accuracy(size=8 * 128)
    assert set(out) == set(acc.OPS)
    for name, row in out.items():
        for where in ("pallas", "xla"):
            # float32 ops on the CPU are good to a few ulp
            assert row[where]["max_rel"] <= 1e-6, (name, where, row)


def test_gamma_accuracy_rows(acc):
    out = acc.gamma_accuracy(n_pairs=32, backends=("mixed", "bisect"))
    assert set(out) == {"mixed", "bisect", "kernel_f32_interpret"}
    for b in ("mixed", "bisect"):
        assert out[b]["iters_equal"] == 1.0
        for f in acc.FIELDS:
            assert out[b][f"{f}_max_rel"] <= 1e-6, (b, f, out[b])
    k = out["kernel_f32_interpret"]
    assert k["iters_equal"] >= 0.9
    assert k["time_s_max_rel_iters_equal"] <= 1e-4

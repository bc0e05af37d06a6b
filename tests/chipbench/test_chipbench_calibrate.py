"""The rule that sets a training cell's limits from its readings."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "chipbench"))

import calibrate  # noqa: E402


def readings(program, control, half, no_exchange=None):
    def by_seed(rows):
        return {i: {k: [v, ""] for k, v in row.items()} for i, row in enumerate(rows)}
    return {"program": by_seed(program), "control": by_seed(control),
            "half_batch": by_seed(half), "no_exchange": by_seed(no_exchange or [])}


def test_limit_lies_between_lower_and_upper():
    r = readings(program=[{"loss_gap": 1e-5, "grad_gap": 1e-3, "change_gap": 2e-4},
                          {"loss_gap": 2e-5, "grad_gap": 2e-3, "change_gap": 1e-4}],
                 control=[{"loss_gap": 4e-5, "grad_gap": 3e-2, "change_gap": 3e-3}],
                 half=[{"loss_gap": 1e-3, "grad_gap": 0.2, "change_gap": 1e-2}])
    limits, report = calibrate.limits_from(r)
    # loss: the control reads 2x the lower (under 3x): the half batch sets the upper
    assert limits["loss_gap"] == float(f"{(2e-5) ** 0.4 * (1e-3) ** 0.6:.2g}")
    assert limits["grad_gap"] == float(f"{(2e-3) ** 0.4 * (3e-2) ** 0.6:.2g}")
    assert limits["change_gap"] == float(f"{(2e-4) ** 0.4 * (3e-3) ** 0.6:.2g}")
    for k, lim in limits.items():
        lower = max(p[k][0] for p in r["program"].values())
        assert lower < lim
    assert "control seed 0 fails ['grad_gap', 'change_gap']" in report


def test_no_upper_reading_sets_no_limit():
    r = readings(program=[{"loss_gap": 1e-3}], control=[{"loss_gap": 2e-3}],
                 half=[{"loss_gap": 5e-3}])
    limits, report = calibrate.limits_from(r)
    assert limits == {}
    assert any("no limit holds" in line for line in report)


@pytest.mark.parametrize("text,want", [("1-3", [1, 2, 3]), ("5", [5]),
                                       ("1-2,7", [1, 2, 7])])
def test_seed_ranges(text, want):
    assert calibrate.seeds(text) == want

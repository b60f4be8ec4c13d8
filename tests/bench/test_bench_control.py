"""The control (the program's bounded-staleness read replication switched
on) comes out not correct on each one-chip mix at a tiny size."""
import pytest

from bench_tiny import run_tiny

from bench.control import control_kw


@pytest.mark.parametrize("cell", ["dili4-1chip.r50-uniform",
                                  "dili4-1chip.r50-zipf99"])
def test_control_is_not_correct(cell):
    r = run_tiny(cell, **control_kw())
    assert not r["correct"]
    assert r["checks"]["wrong_results"]["value"] > 0

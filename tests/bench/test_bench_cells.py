"""Each one-chip cell's whole run at a tiny size on the CPU: the reference
agrees, and each fault planted in the timed path makes ``correct`` false."""
import pytest

from bench_tiny import FAULTS, plant, run_tiny

ONE_CHIP = ["dili4-1chip.r50-uniform", "dili4-1chip.r50-zipf99"]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_is_correct(cell):
    r = run_tiny(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"ops_per_s", "op_p50_ms", "op_p99_ms",
                                 "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["flip_one", "frozen", "half_batch"])
def test_fault_in_the_timed_path_is_caught(fault):
    r = run_tiny(ONE_CHIP[0], traffic_hook=plant(FAULTS[fault]))
    assert not r["correct"]
    assert r["failed"] > 0 or r["checks"]["final_keys_differ"]["value"] > 0

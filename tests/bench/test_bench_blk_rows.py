"""``round.blk_rebuild_rows`` reads the ``blk_rows`` counter (packed blocks
the round's rebuild walked) per round, and stays silent for a program that
lacks the counter."""
import pytest

from bench_tiny import tiny_cell

from bench import harness


def _record(counters, rounds=4, ops=100):
    cell = tiny_cell("dili4-1chip.r50-uniform")
    return harness.RunRecord(
        cell=cell, cfg=harness.dili_config(cell.config), rounds=rounds,
        ops_done=ops, counters=counters, trace=None, peaks={})


@pytest.mark.parametrize("counters,rounds,value", [
    ({"blk_rows": 1000, "serial_rows": 90}, 4, 250.0),
    ({"blk_rows": 0}, 4, 0.0),
    ({"blk_rows": 7}, 2, 3.5),
])
def test_blk_rebuild_rows_reader(counters, rounds, value):
    read = harness.metric_reader("round.blk_rebuild_rows")
    assert read(_record(counters, rounds=rounds)) == pytest.approx(value)


@pytest.mark.parametrize("counters,rounds", [
    ({"serial_rows": 90}, 4),      # a program without the counter
    ({"blk_rows": 5}, 0),          # no round in the window
])
def test_blk_rebuild_rows_reader_is_silent(counters, rounds):
    read = harness.metric_reader("round.blk_rebuild_rows")
    assert read(_record(counters, rounds=rounds)) is None

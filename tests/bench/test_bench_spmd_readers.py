"""The four-chip cell's two per-layer metrics: ``spmd.all_to_all_ms_per_round``
reads the exchange's collective from the trace's ops, by the names a TPU
v5e's trace gives them, and ``spmd.routed_rows_per_round`` reads the
``routed_rows`` counter; both stay silent where there is nothing to read.
Only the four-chip cell lists them."""
import pytest

from bench_tiny import FOUR_CHIP, tiny_cell

from bench import harness, trace_reduce

# the exchange's ops as the chip's trace names them (the head of each
# instruction's HLO text, as ``trace_reduce`` keeps it), with the layout
# copies around the collective, which are not it
A2A = ("%all_to_all.3 = s32[4,512,15]{1,2,0:T(8,128)S(1)} all-to-all("
       "%copy.522), channel_id=1, replica_groups={{0,1,2,3}}, dimensions")
COPY_IN = "%copy.522 = s32[4,512,15]{1,0,2:T(4,128)} copy(%param_0.3741)"
COPY_OUT = ("%copy.523 = s32[4,512,15]{1,0,2:T(4,128)S(1)} copy("
            "%all_to_all.3), metadata={op_name=\"jit(per_shard)/shard_map/")
BITCAST = ("%copy_bitcast_fusion.3 = s32[15,4,512]{2,1,0:T(4,128)S(1)} "
           "fusion(%all_to_all.3), kind=kLoop, calls=%fused_computation.1402")
START = "%all-to-all-start.1 = (s32[4,512,15]{2,1,0}) all-to-all-start(%x)"
DONE = "%all-to-all-done.1 = s32[4,512,15]{2,1,0} all-to-all-done(%all-t"
WHILE = "%while.834 = (s32[], s32[128]{0}, s32[65536]{0}) while(%tuple.9)"


def _trace(op_seconds):
    return trace_reduce.TraceSummary(
        window_s=2.0, busy_s=0.5, chips=4, op_seconds=op_seconds,
        op_counts={n: 1.0 for n in op_seconds}, span_seconds={},
        span_counts={}, gaps=[])


def _record(counters=None, trace=None, rounds=40):
    cell = tiny_cell(FOUR_CHIP)
    return harness.RunRecord(
        cell=cell, cfg=harness.dili_config(cell.config), rounds=rounds,
        ops_done=1000, counters=counters or {}, trace=trace, peaks={})


@pytest.mark.parametrize("ops,ms", [
    ({A2A: 0.004, COPY_IN: 0.5, COPY_OUT: 0.5, BITCAST: 0.5, WHILE: 1.0},
     0.1),
    ({START: 0.002, DONE: 0.006, WHILE: 1.0}, 0.2),
])
def test_all_to_all_reader_reads_the_collective_alone(ops, ms):
    read = harness.metric_reader("spmd.all_to_all_ms_per_round")
    assert read(_record(trace=_trace(ops))) == pytest.approx(ms)


@pytest.mark.parametrize("rec", [
    _record(trace=None),                              # an untraced run
    _record(trace=_trace({WHILE: 1.0, COPY_OUT: 0.5})),  # no all_to_all
    _record(trace=_trace({A2A: 0.004}), rounds=0),    # no round
])
def test_all_to_all_reader_is_silent(rec):
    read = harness.metric_reader("spmd.all_to_all_ms_per_round")
    assert read(rec) is None


@pytest.mark.parametrize("counters,rounds,value", [
    ({"routed_rows": 3000, "blk_rows": 90}, 40, 75.0),
    ({"routed_rows": 0}, 4, 0.0),
])
def test_routed_rows_reader(counters, rounds, value):
    read = harness.metric_reader("spmd.routed_rows_per_round")
    assert read(_record(counters, rounds=rounds)) == pytest.approx(value)


@pytest.mark.parametrize("counters,rounds", [
    ({"blk_rows": 90}, 40),         # a program without the counter
    ({"routed_rows": 5}, 0),        # no round in the window
])
def test_routed_rows_reader_is_silent(counters, rounds):
    read = harness.metric_reader("spmd.routed_rows_per_round")
    assert read(_record(counters, rounds=rounds)) is None


NEW = {"spmd.all_to_all_ms_per_round", "spmd.routed_rows_per_round"}


def test_only_the_four_chip_cell_reports_them():
    cell = harness.find_cell(FOUR_CHIP)
    assert cell.chips == 4 and cell.config["backend"] == "shard_map"
    assert NEW <= {m["name"] for m in cell.per_layer}
    for name in ("dili4-1chip.r50-uniform", "dili4-1chip.r50-zipf99"):
        one = harness.find_cell(name)
        assert one.chips == 1
        assert not NEW & {m["name"] for m in one.per_layer}

"""The reduction of the program's own spans and device scopes gives known
numbers on small traces, the new per-layer readers read their counters,
and a traced run records every program span."""
import json
from pathlib import Path

import pytest

from bench_tiny import SEED, WINDOW_S, tiny_cell

from bench import harness, program_trace, trace_reduce
from bench.program_trace import Event

HERE = Path(__file__).resolve().parent
MS = 1e6                                    # ns
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _events():
    """Two chips over a 100 ms window. Chip 0 runs a ``round.blocks``
    while over 0-40 ms with a ``round.serial`` op nested in it, an op
    with no scope over 40-50, a ``round.prepass`` cond over 60-80 with
    the kernel nested in it, and a ``round.serial`` op over 80-90. Chip
    1 runs a ``round.bg`` op over 0-20 and an unscoped op over 10-30.
    The host is in ``backend.step`` over 0-50 (``cluster.launch`` 0-10,
    ``cluster.harvest`` 10-45) and in ``balancer.step`` over 50-100
    (``balancer.inspect`` 50-90, ``balancer.plan`` 90-98)."""
    h = lambda n, a, b: Event("host", "host", n, a * MS, b * MS)
    d = lambda p, n, a, b, s: Event("device", p, n, a * MS, b * MS, s)
    return [
        h("window", 0, 100),
        h("backend.step", 0, 50),
        h("cluster.launch", 0, 10),
        h("cluster.harvest", 10, 45),
        h("balancer.step", 50, 100),
        h("balancer.inspect", 50, 90),
        h("balancer.plan", 90, 98),
        h("cluster.launch", 120, 130),
        d(D0, "%while.1 = s32[1024,160]", 0, 40, "round.blocks"),
        d(D0, "%fusion.2 = s32[1024]", 5, 15, "round.serial"),
        d(D0, "%copy.3 = s32[64]", 40, 50, ""),
        d(D0, "%cond.4 = s32[65536]", 60, 80, "round.prepass"),
        d(D0, "%hybrid_search.1 = s32[128,1]", 65, 70, "round.prepass"),
        d(D0, "%while.5 = s32[2048]", 80, 90, "round.serial"),
        d(D1, "%fusion.1 = s32[8]", 0, 20, "round.bg"),
        d(D1, "%copy.2 = s32[8]", 10, 30, ""),
    ]


def test_scope_attribution_counts_each_instant_once():
    ev = _events()
    sc = program_trace.scope_seconds(ev)
    # per chip, averaged: chip 0 blocks 40 (the nested serial op inside
    # the while counts to the while), other 10, prepass 20 (its kernel
    # once), serial 10; chip 1 bg 20, other 10 (its overlap with bg once)
    assert sc == {"round.blocks": pytest.approx(0.020),
                  "other": pytest.approx(0.010),
                  "round.prepass": pytest.approx(0.010),
                  "round.serial": pytest.approx(0.005),
                  "round.bg": pytest.approx(0.010)}
    assert sum(sc.values()) == pytest.approx(
        trace_reduce.summarize(ev).busy_s)
    s = program_trace.summarize(ev)
    assert sum(s["scope_share"].values()) == pytest.approx(100.0)
    assert s["scope_share"]["other"] == pytest.approx(100 * 10 / 55)


def test_summary_of_a_known_trace():
    s = program_trace.summarize(_events())
    assert s["rounds"] == 1
    m = s["metrics"]
    assert m["round.blocks_share"] == pytest.approx(100 * 20 / 55)
    assert m["round.prepass_share"] == pytest.approx(100 * 10 / 55)
    assert m["round.serial_share"] == pytest.approx(100 * 5 / 55)
    assert m["round.bg_share"] == pytest.approx(100 * 10 / 55)
    # the launch span after the window is left out
    assert m["host.launch_ms_per_round"] == pytest.approx(10.0)
    assert m["host.harvest_ms_per_round"] == pytest.approx(35.0)
    assert m["balancer.inspect_share"] == pytest.approx(40.0)
    assert s["step_coverage"] == {"backend.step": pytest.approx(90.0),
                                  "balancer.step": pytest.approx(96.0)}
    # no chip busy over 50-60 (balancer.inspect open at 55) and 90-100
    # (balancer.plan open at 95): the innermost span names each gap
    assert s["idle_gaps"] == [["balancer.inspect", pytest.approx(0.010)],
                              ["balancer.plan", pytest.approx(0.010)]]
    json.dumps(s)


def test_events_round_trip_and_cut(tmp_path):
    ev = _events()
    program_trace.dump_events(ev, tmp_path / "ev.json.gz")
    assert program_trace.read_events(tmp_path / "ev.json.gz") == ev
    part = program_trace.cut(ev, 60 * MS, 30.0)
    assert program_trace.scope_seconds(part) == {
        "round.prepass": pytest.approx(0.020),
        "round.serial": pytest.approx(0.010)}


def test_scope_is_the_innermost_round_component():
    assert program_trace.scope_of(
        "jit(dili_round)/jit(shard_round)/round.serial/while/body") \
        == "round.serial"
    assert program_trace.scope_of(
        "jit(shard_round)/round.bg/round.prepass/add") == "round.prepass"
    assert program_trace.scope_of("jit(f)/roundabout/x") == ""


HLO = """HloModule jit_shard_round, is_scheduled=true

%body.3 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %add.7 = s32[8]{0} add(%a, %b), metadata={op_type="add" op_name="jit(shard_round)/round.serial/while/body/add" source_file="shard.py"}
  ROOT %tuple.2 = (s32[], s32[8]) tuple(%x, %add.7)
}

ENTRY %main.9 (a: s32[8]) -> s32[8] {
  %copy.1 = s32[8]{0} copy(%a)
  %while.4 = (s32[], s32[8]) while(%t), condition=%c.2, body=%body.3, metadata={op_type="while" op_name="jit(shard_round)/round.serial/while"}
  ROOT %fusion.5 = s32[8]{0} fusion(%g), kind=kLoop, calls=%f.1, metadata={op_name="jit(shard_round)/round.finish/select_n"}
}
"""


def test_scope_map_reads_the_compiled_modules_metadata():
    sm = program_trace.scope_map(HLO)
    assert sm.module == "jit_shard_round"
    assert sm.scopes == {"%add.7": "round.serial", "%while.4": "round.serial",
                         "%fusion.5": "round.finish"}


def test_program_span_names_are_the_programs_and_not_the_harness():
    from repro.core.spans import SPANS
    assert program_trace.PROGRAM_SPANS == SPANS
    assert not set(SPANS) & (set(trace_reduce.HOST_SPANS)
                             | {trace_reduce.WINDOW_SPAN})
    assert len(set(SPANS)) == len(SPANS)


def _record(counters, rounds=4, ops=100):
    cell = tiny_cell("dili4-1chip.r50-uniform")
    return harness.RunRecord(
        cell=cell, cfg=harness.dili_config(cell.config), rounds=rounds,
        ops_done=ops, counters=counters, trace=None, peaks={})


@pytest.mark.parametrize("name,counters,value", [
    ("round.serial_rows", {"serial_rows": 90}, 22.5),
    ("client.queue_rounds_per_op", {"queue_rounds": 250}, 2.5),
])
def test_counter_readers(name, counters, value):
    read = harness.metric_reader(name)
    assert read(_record(counters)) == pytest.approx(value)
    # a program that lacks the counter: the reader stays silent
    assert read(_record({"blk_hits": 3})) is None
    assert read(_record(counters, rounds=0, ops=0)) is None


def test_traced_run_records_every_program_span(monkeypatch):
    """A ``--trace 1`` run of the tiny uniform cell on the CPU, with the
    profiler really on: its trace holds each span of the client, the
    cluster and the balancer (the CPU trace has no chip, so the summary
    is made from the known trace), and the round's compiled text gives
    its instructions' scopes. ``tests/test_round_counters.py`` records
    the ``shardmap.*`` spans."""
    seen = {}
    load = program_trace.load_dir

    def keep(backend):
        seen["backend"] = backend
        return backend

    def capture(d):
        seen["events"] = load(d)
        return _events()

    monkeypatch.setattr(trace_reduce, "load_dir", capture)
    cell = tiny_cell("dili4-1chip.r50-uniform")
    r = harness.run_cell(cell, SEED, WINDOW_S, True, t_process=0.0,
                         require_tpu=False, backend_hook=keep)
    assert r["correct"], r["checks"]
    names = {e.name for e in seen["events"]}
    local = {n for n in program_trace.PROGRAM_SPANS
             if not n.startswith("shardmap.")}
    assert local <= names, local - names
    assert r["metrics"]["round.serial_rows"]["value"] > 0
    assert r["metrics"]["client.queue_rounds_per_op"]["value"] >= 0
    sm = program_trace.scope_map(
        program_trace.compiled_text(seen["backend"]))
    assert sm.module == "jit_shard_round"
    assert set(sm.scopes.values()) <= set(program_trace.ROUND_SCOPES)
    assert set(sm.scopes.values()) >= {
        "round.blocks", "round.prepass", "round.order", "round.serial",
        "round.bg", "round.finish"}


def test_reduction_of_a_recorded_chip_trace_with_scopes():
    """A 20 ms slice of a traced ``r50-uniform`` run on one TPU v5e (op
    names cut to 48 characters; ``bench/program_trace.py --slice``): the
    last of one round's four shard rounds, the host's work after it and
    the start of a balancer pass. Each op's scope was read in the run,
    from the round's compiled HLO by its instruction name. Ops nest in
    ``while`` and ``cond`` ops, so their summed seconds pass the busy
    time; the scopes' seconds equal it, the packed-block rebuild leads,
    and the long idle gap is named by a program span."""
    ev = program_trace.read_events(HERE / "chip_scope_slice.json.gz")
    base = trace_reduce.summarize(ev)
    assert base.chips == 1 and base.window_s == pytest.approx(0.020)
    assert sum(base.op_seconds.values()) > 1.5 * base.busy_s
    sc = program_trace.scope_seconds(ev)
    assert sum(sc.values()) == pytest.approx(base.busy_s, rel=1e-9)
    s = program_trace.summarize(ev)
    assert sum(s["scope_share"].values()) == pytest.approx(100.0)
    assert max(sc, key=sc.get) == "round.blocks"
    assert s["scope_share"]["other"] < 10.0
    name, secs = s["idle_gaps"][0]
    assert name in program_trace.PROGRAM_SPANS and secs > 0.005
    assert s["step_coverage"]["backend.step"] > 90.0

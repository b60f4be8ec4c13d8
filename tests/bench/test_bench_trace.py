"""The trace reduction and the per-layer readers give known numbers on a
small trace, and the traced run's result line carries them."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench_tiny import SEED, WINDOW_S, tiny_cell

from bench import harness, trace_reduce
from bench.trace_reduce import Event

HERE = Path(__file__).resolve().parent
MS = 1e6                                    # ns


def _events():
    """Two chips over a 100 ms window. Chip 0 runs 0-10, 5-20 (nested
    overlap) and 60-70 ms; chip 1 runs 10-30 and 60-80 ms. The host is in
    ``backend.step`` over 0-40 ms, in ``balancer.step`` over 40-60 ms and
    in ``traffic`` over 80-100 ms, inside one ``pump`` span over 0-80."""
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    return [
        Event("host", "host", "window", 0, 100 * MS),
        Event("host", "host", "pump", 0, 80 * MS),
        Event("host", "host", "backend.step", 0, 40 * MS),
        Event("host", "host", "balancer.step", 40 * MS, 60 * MS),
        Event("host", "host", "traffic", 80 * MS, 100 * MS),
        Event("host", "host", "backend.step", -5 * MS, -1 * MS),
        Event("device", d0, "fusion.1", 0, 10 * MS),
        Event("device", d0, "%hybrid_search.3 = (s32[128,1,1]", 5 * MS,
              20 * MS),
        Event("device", d0, "all-to-all.3", 60 * MS, 70 * MS),
        Event("device", d1, "fusion.1", 10 * MS, 30 * MS),
        Event("device", d1, "%hybrid_search.2 = s32[128,1]", 60 * MS,
              80 * MS),
        Event("device", d1, "fusion.1", 150 * MS, 160 * MS),
    ]


def test_reduction_of_a_known_trace(tmp_path):
    trace_reduce.dump_events(_events(), tmp_path / "ev.json")
    tr = trace_reduce.summarize(trace_reduce.read_events(tmp_path / "ev.json"))
    assert tr.window_s == pytest.approx(0.1)
    assert tr.chips == 2
    # chip 0 busy 0-20 + 60-70 = 30 ms; chip 1 busy 10-30 + 60-80 = 40 ms
    assert tr.busy_s == pytest.approx(0.035)
    # per chip, averaged: fusion.1 (10 + 20) / 2 ms; the 150 ms op is
    # outside the window
    assert tr.op_seconds["fusion.1"] == pytest.approx(0.015)
    assert tr.op_counts["fusion.1"] == pytest.approx(1.0)
    assert tr.span_seconds["backend.step"] == pytest.approx(0.04)
    assert tr.span_counts["backend.step"] == 1
    # no chip busy over 30-60 (balancer.step at its middle, 45 ms) and
    # 80-100 (traffic)
    assert tr.gaps == [("balancer.step", pytest.approx(0.03)),
                       ("traffic", pytest.approx(0.02))]
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    assert [g[0] for g in bd["idle_gaps"]] == ["balancer.step", "traffic"]


def _record(tr, rounds=4, ops=100, blk=80):
    cell = tiny_cell("dili4-1chip.r50-uniform")
    return harness.RunRecord(
        cell=cell, cfg=harness.dili_config(cell.config), rounds=rounds,
        ops_done=ops, counters={"blk_hits": blk}, trace=tr,
        peaks={"hbm_bytes_per_s": 819e9})


def test_readers_on_a_known_trace():
    rec = _record(trace_reduce.summarize(_events()))
    read = {p.stem: harness.metric_reader(p.stem)(rec)
            for p in (harness.BENCH_DIR / "metrics").glob("*.py")}
    assert read["device.idle_share"] == pytest.approx(65.0)
    assert read["device.busy_ms_per_round"] == pytest.approx(35 / 4)
    assert read["client.ops_per_round"] == pytest.approx(25.0)
    assert read["probe.blk_hit_share"] == pytest.approx(80.0)
    assert read["host.round_ms"] == pytest.approx(40.0)
    assert read["balancer.time_share"] == pytest.approx(20.0)
    # 80 lanes answered (blk_hits) x (160*4 + 12) B over 819 GB/s, against
    # 15 + 20 ms of kernel time on the two chips
    least = 80 * 652 / 819e9
    assert read["hybrid_search_roofline"] == pytest.approx(
        100 * least / 0.035)


def test_readers_stay_silent_without_their_events():
    ev = [e for e in _events() if "hybrid_search" not in e.name
          and "all-to-all" not in e.name]
    rec = _record(trace_reduce.summarize(ev))
    assert harness.metric_reader("hybrid_search_roofline")(rec) is None
    rec = _record(trace_reduce.summarize(_events()), blk=0)
    assert harness.metric_reader("hybrid_search_roofline")(rec) is None


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    """A whole ``--trace 1`` run at a tiny size on the CPU, with the
    profiler's file replaced by the known trace (a CPU trace has no chip
    to read): the line carries the per-layer metrics, busy and window
    seconds, and the breakdown."""
    monkeypatch.setattr(trace_reduce, "load_dir", lambda d: _events())
    cell = tiny_cell("dili4-1chip.r50-uniform")
    r = harness.run_cell(cell, SEED, WINDOW_S, True, t_process=0.0,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    names = {m["name"] for m in cell.per_layer}
    assert set(r["metrics"]) <= names
    assert "device.idle_share" in r["metrics"]
    assert r["device"]["busy_s"] == pytest.approx(0.035)
    assert r["device"]["window_s"] == pytest.approx(0.1)
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert list(r)[-1] == "checks"
    json.dumps(r)


def test_reduction_of_a_recorded_chip_trace():
    """A 4 ms slice of a traced ``r50-uniform`` run on one TPU v5e (op
    names cut to 48 characters), holding one ``hybrid_search`` call: the
    busy time equals a count on a 100 ns grid, and the kernel's share of
    its roofline is worked out by hand from its two ops, for a call whose
    80 lanes the lookup answered."""
    raw = json.loads(gzip.open(HERE / "chip_trace_slice.json.gz").read())
    ev = [Event(*e) for e in raw]
    tr = trace_reduce.summarize(ev)
    assert tr.chips == 1 and tr.window_s == pytest.approx(0.004)
    grid = np.zeros(40001, bool)
    for e in ev:
        if e.kind == "device":
            a, b = max(e.start, 0) / 100, min(e.end, 4e6) / 100
            if b > a:
                grid[int(a):int(np.ceil(b))] = True
    assert tr.busy_s == pytest.approx(grid.sum() * 1e-7, rel=0.01)
    kernel = [e for e in ev if e.name.startswith("%hybrid_search")]
    assert len(kernel) == 2
    secs = sum(e.end - e.start for e in kernel) / 1e9
    rec = _record(tr)
    least = 80 * 652 / 819e9
    assert harness.metric_reader("hybrid_search_roofline")(rec) == \
        pytest.approx(100 * least / secs)
    assert 0.1 < 100 * least / secs < 1.0

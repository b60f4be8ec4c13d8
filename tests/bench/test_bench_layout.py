"""BENCHMARK.json against the benchmark's contract, and the harness's
discovery of configurations, mixes and metrics by name alone."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_names_and_units_use_the_allowed_characters():
    names = ([m["name"] for m in _metrics()]
             + [c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for kind in ("end_to_end", "per_layer", "configs", "workloads"):
        seen = [x["name"] for x in BENCH[kind]]
        assert len(seen) == len(set(seen)), kind
    for p in BENCH["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")


def test_entries_have_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_each_moves_names_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_every_cell_resolves_to_its_files():
    for w in BENCH["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.chips == w["chips"]
        cfg = harness.dili_config(cell.config)
        assert cfg.num_shards == 4
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        for k in c["reduced"]:
            assert conf[k] != conf["published"].get(k, conf[k]) or \
                k in conf["assumed"]


def test_the_check_fits_its_time_with_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_new_files_are_found_by_name_without_edits(tmp_path):
    """A configuration, a mix and a metric dropped in as files, and named
    in BENCHMARK.json, are found by the harness unchanged."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "bench" / "configs" / "new-conf.json").write_text(
        (ROOT / "bench" / "configs" / "dili4-1chip.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / "r50-uniform.json")
                     .read_text())
    mix["read_frac"] = 0.9
    (tmp_path / "bench" / "traffic" / "r90-new.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "new-conf", "source": "x",
                             "file": "bench/configs/new-conf.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-conf.r90-new",
                               "config": "new-conf", "traffic": "r90-new",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "Kernels", "moves": "ops_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("new-conf.r90-new", root=tmp_path)
    assert cell.mix["read_frac"] == 0.9
    assert "new.metric" in [m["name"] for m in cell.per_layer]
    assert harness.metric_reader("new.metric", root=tmp_path)(None) == 42.0


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"JAX_PLATFORMS": "cpu",
                                  "REPRO_INTERPRET": "1"}])
def test_run_refuses_anything_but_a_tpu(env):
    import os
    e = dict(os.environ, **env)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "dili4-1chip.r50-uniform", "--seed", str(2**31 + 11),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_run_needs_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits nonzero with no result."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    import os
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "dili4-1chip.r50-uniform", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu",
                               PYTHONPATH=""),
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

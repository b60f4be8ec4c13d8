"""Tiny versions of the benchmark's cells for CPU tests, and the faults
the tests plant in the timed path."""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SEED = 2**31 + 77          # larger than 32 signed bits hold
WINDOW_S = 2.0


FOUR_CHIP = "dili4-4chip.r50-uniform"


def four_chip_root(tmp: Path) -> Path:
    """A checkout in ``tmp`` whose ``BENCHMARK.json`` also lists the
    four-chip cell, ``dili4-4chip`` under ``r50-uniform``, as a later
    benchmark adds it: its configuration entry and its workload."""
    bench = harness.load_benchmark()
    bench["configs"].append(dict(bench["configs"][0], name="dili4-4chip",
                                 file="bench/configs/dili4-4chip.json"))
    bench["workloads"].append({"name": FOUR_CHIP, "config": "dili4-4chip",
                               "traffic": "r50-uniform", "chips": 4,
                               "why": "one DiLi server per chip"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "bench").symlink_to(ROOT / "bench")
    return tmp


def tiny_cell(name: str, root: Path = ROOT):
    """The cell ``name`` at a size the CPU runs in seconds: 400 keys over
    a key space of 800, 32 clients, the capacities cut to match."""
    cell = harness.find_cell(name, root=root)
    cell.config["dili"].update(pool_capacity=1024, max_sublists=64,
                               max_ctrs=64)
    cell.config.update(record_count=400, key_space=800, seed_load=100)
    cell.mix.update(clients=32, warmup_rounds=5)
    return cell


def run_tiny(name: str, seed: int = SEED, root: Path = ROOT, **kw) -> dict:
    return harness.run_cell(tiny_cell(name, root), seed, WINDOW_S, False,
                            t_process=time.perf_counter(),
                            require_tpu=False, **kw)


class _Faulty:
    """Stands in for the client's backend from the window on."""

    def __init__(self, backend, step):
        self._backend = backend
        self._step = step

    def step(self):
        return self._step(self._backend)

    def __getattr__(self, name):
        return getattr(self._backend, name)


def plant(step):
    """A window hook that routes the client's rounds through ``step``."""
    def hook(client):
        client.backend = _Faulty(client.backend, step)
    return hook


def plant_from_start(step):
    """A backend hook that routes every round through ``step``."""
    return lambda backend: _Faulty(backend, step)


def flip_one(backend):
    """An answer altered where it is produced: the first completion of
    the window comes back with its result flipped."""
    comps = backend.step()
    if comps and not getattr(backend, "_flipped", False):
        op_id, val, src = comps[0]
        comps[0] = (op_id, 1 - val, src)
        backend._flipped = True
    return comps


def frozen(backend):
    """A round that returns its state unchanged: nothing runs."""
    return []


def half_batch(backend):
    """Half of each round's batch left out: every other completion is
    dropped."""
    return backend.step()[::2]


def no_exchange(backend):
    """The exchange between chips left out: whatever the round routed to
    another shard's inbox is dropped (``ShardMapBackend``)."""
    comps = backend.step()
    backend._inbox = backend._inbox * 0
    return comps


FAULTS = {"flip_one": flip_one, "frozen": frozen, "half_batch": half_batch,
          "no_exchange": no_exchange}

"""RANGE scan suite (DESIGN.md §16).

Three layers of evidence that RANGE(lo, hi, limit) is a linearizable
snapshot of its span:

  * a boundary matrix on a quiesced multi-shard list — empty, singleton,
    full-space and cross-shard spans, limit truncation, error surfacing;
  * differential runs against the sequential oracle while the balancer
    splits/moves/merges under nemesis delays — the client's span-conflict
    admission makes "oracle at the scan's submission index" the exact
    referee (see tests/nemesis_harness.py);
  * the serving-level regressions that motivated the op: `python -O`
    must not strip the pool/batch admission checks, and a missing page
    mapping must surface as a -1 sentinel / KeyError, never alias slot 0.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from nemesis_harness import default_nemesis, run_differential, check

from repro.api import DiLiClient, LocalBackend
from repro.core.types import DiLiConfig, KEY_MIN, KEY_MAX


def _cfg(num_shards=4, **kw):
    base = dict(num_shards=num_shards, pool_capacity=4096,
                max_sublists=32, max_ctrs=32, max_scan=4096,
                batch_size=16, mailbox_cap=256, move_batch=8,
                range_scan=True)
    base.update(kw)
    return DiLiConfig(**base)


def _spread_client(keys, values=None, num_shards=4):
    """A client over a list spread across shards via split + move."""
    c = DiLiClient(LocalBackend(_cfg(num_shards), seed=7))
    c.insert_batch(keys, values).results()
    for target in range(1, num_shards):
        subs = [e for e in c.backend.sublists(0) if e["size"] is not None]
        if not subs:
            break
        big = max(subs, key=lambda e: e["size"])
        mid = c.backend.middle_item(0, big["head_idx"])
        if mid is None:
            break
        assert c.backend.split(0, big["keymax"], mid)
        c.drain()
        subs = [e for e in c.backend.sublists(0) if e["size"] is not None]
        small = min(subs, key=lambda e: e["keymax"])
        assert c.backend.move(0, small["keymax"], target)
        c.drain()
    owners = {e[2] for e in c.backend.registry_entries(0)}
    assert len(owners) > 1, "list did not spread across shards"
    return c


# ------------------------------------------------------ boundary matrix

def test_range_boundary_matrix():
    keys = list(range(10, 610, 5))
    vals = [k * 7 for k in keys]
    c = _spread_client(keys, vals)
    kv = dict(zip(keys, vals))

    def scan(lo, hi, limit=10_000):
        return c.range(lo, hi, limit).items()

    # empty spans: before all keys, in a gap, after all keys, hi <= lo
    assert scan(0, 10) == []
    assert scan(11, 15) == []
    assert scan(700, 9000) == []
    assert scan(50, 50) == []
    assert scan(60, 40) == []
    # singleton spans, inclusive-lo / exclusive-hi edges
    assert scan(10, 11) == [(10, 70)]
    assert scan(605, 606) == [(605, 4235)]
    assert scan(10, 15) == [(10, 70)]
    assert scan(11, 16) == [(15, 105)]
    # full space (cross-shard) and a cross-shard interior span
    assert scan(KEY_MIN, KEY_MAX + 1) == sorted(kv.items())
    expect = [(k, kv[k]) for k in keys if 200 <= k < 400]
    assert scan(200, 400) == expect
    # limit truncation keeps the low end, in order
    assert scan(KEY_MIN, KEY_MAX + 1, limit=7) == sorted(kv.items())[:7]
    assert scan(200, 400, limit=1) == expect[:1]
    assert c.backend.stats["range_hits"] > 0


def test_range_rejects_bad_args():
    c = DiLiClient(LocalBackend(_cfg(), seed=1))
    with pytest.raises(ValueError):
        c.range(0, 10, limit=0)
    with pytest.raises(ValueError):
        c.backend.submit_range(0, KEY_MIN - 2, 10, 5)
    off = DiLiClient(LocalBackend(DiLiConfig(num_shards=2), seed=1))
    with pytest.raises(ValueError):
        off.range(0, 10)


def test_range_span_hold_orders_mutations():
    """A mutation queued after a scan into its span must not appear in
    the scan's snapshot; one queued before must."""
    keys = list(range(0, 200, 2))
    c = DiLiClient(LocalBackend(_cfg(), seed=3))
    c.insert_batch(keys).results()
    ins = c.insert(101)            # queued first: in the snapshot
    r = c.range(0, 200, limit=500)
    rm = c.remove(100)             # queued after: held until r resolves
    c.drain()
    got = r.keys(wait=False)
    assert 101 in got
    assert 100 in got
    assert ins.result(wait=False) is True
    assert rm.result(wait=False) is True
    assert c.find(100).result() is False


# ------------------------------------------- differential (churn+delays)

@pytest.mark.parametrize("seed", [11, 12])
def test_range_differential_local(seed):
    nem = default_nemesis(0.1)
    res = run_differential("local", seed, nem, n_ops=400, scan_every=2)
    check(res, f"range-diff local seed={seed}")
    assert res["n_scans"] >= 10


def test_range_differential_no_faults():
    """Clean wire, heavy churn: every batch carries a scan."""
    from repro.core.net import NemesisConfig
    res = run_differential("local", 21, NemesisConfig(), n_ops=400,
                           scan_every=1, split_threshold=16)
    check(res, "range-diff clean seed=21")
    assert res["n_scans"] >= 20


SHARDMAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["RANGE_EVERY"] = "3"
import sys
sys.path.insert(0, "tests")
from nemesis_harness import main
sys.exit(main(["shardmap", "200", "31"]))
"""


@pytest.mark.slow
def test_range_differential_shardmap():
    """Scan parity on the SPMD backend (hostroute path, nemesis on) —
    subprocess because the device count must be set before jax loads."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SHARDMAP_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK shardmap" in r.stdout


# ------------------------------------------------- serving regressions

OPT_SCRIPT = r"""
import numpy as np
from repro.configs import get_smoke_config
from repro.serving.engine import BatchOverflow, Request, ServingEngine
from repro.serving.paged import PagedKVManager, PagePoolExhausted

if __debug__:
    raise SystemExit("must run under python -O (asserts stripped)")

cfg = get_smoke_config("qwen2_5_3b")
kv = PagedKVManager(cfg, num_pages=2, page_size=4)
kv.alloc_page(0, 0)
kv.alloc_page(0, 1)
try:
    kv.alloc_page(1, 0)
    raise SystemExit("pool exhaustion not raised")
except PagePoolExhausted:
    pass

# admission overflow must raise without building a real model: bypass
# admit()'s prefill by pre-filling the active list
eng = ServingEngine.__new__(ServingEngine)
eng.active = [None] * 2
eng.max_batch = 2
try:
    ServingEngine.admit(eng, Request(9, np.zeros(4, np.int32), 4))
    raise SystemExit("batch overflow not raised")
except BatchOverflow:
    pass
print("OK")
"""


def test_guards_survive_python_O():
    """The pool-exhaustion and batch-admission guards are exceptions,
    not asserts — they must fire under ``python -O``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-O", "-c", OPT_SCRIPT],
                       env=env, capture_output=True, text=True,
                       timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def test_page_table_sentinel_and_never_allocated():
    """Missing-but-allocated pages read as -1 (masked downstream);
    never-allocated pages raise instead of aliasing slot 0."""
    from repro.configs import get_smoke_config
    from repro.serving.paged import PagedKVManager, page_key
    cfg = get_smoke_config("qwen2_5_3b")
    kv = PagedKVManager(cfg, num_pages=8, page_size=4)
    s00 = kv.alloc_page(0, 0)
    kv.alloc_page(0, 1)
    kv.alloc_page(1, 0)
    pt = np.asarray(kv.page_table([0, 1], [2, 1]))
    assert pt.shape == (2, 2)
    assert pt[0, 0] == s00 and (pt >= -1).all()
    assert pt[1, 1] == -1          # padding past seq 1's count
    # allocated but missing from the snapshot (simulated stale cache)
    kv._table.pop(page_key(0, 1))
    pt = np.asarray(kv.page_table([0], [2]))
    assert pt[0, 1] == -1
    # never allocated: refuse
    with pytest.raises(KeyError):
        kv.page_table([2], [1])


def test_free_seq_verifies_removes():
    """A failed remove must not recycle the slot (key resurrection)."""
    from repro.configs import get_smoke_config
    from repro.serving.paged import PagedKVManager, page_key
    cfg = get_smoke_config("qwen2_5_3b")
    kv = PagedKVManager(cfg, num_pages=8, page_size=4)
    kv.alloc_page(0, 0)
    kv.alloc_page(0, 1)
    free_before = len(kv.free_slots)
    # sabotage: remove the key out-of-band so the tracked remove bounces
    kv.client.remove(page_key(0, 1)).result()
    with pytest.raises(RuntimeError, match="still live|failed"):
        kv.free_seq(0, 2)
    # page 0's confirmed remove recycled; page 1's slot must NOT be
    # recycled by the failed path (it is leaked pending operator action)
    assert len(kv.free_slots) == free_before + 1


def test_refresh_seq_matches_rescan_after_migration():
    """refresh_seq's RANGE snapshot equals the full rescan's view of the
    same sequence after a live split+move of the page table."""
    from repro.configs import get_smoke_config
    from repro.serving.paged import PagedKVManager, page_key
    cfg = get_smoke_config("qwen2_5_3b")
    kv = PagedKVManager(cfg, num_pages=64, page_size=4, dili_shards=2)
    for sid in range(3):
        for p in range(8):
            kv.alloc_page(sid, p)
    be = kv.backend
    subs = [e for e in be.sublists(0) if e["size"] is not None]
    big = max(subs, key=lambda e: e["size"])
    mid = be.middle_item(0, big["head_idx"])
    assert be.split(0, big["keymax"], mid)
    kv.client.drain()
    subs = [e for e in be.sublists(0) if e["size"] is not None]
    small = min(subs, key=lambda e: e["keymax"])
    assert be.move(0, small["keymax"], 1)
    kv.client.drain()
    kv._table.clear()
    for sid in range(3):
        n = kv.refresh_seq(sid)
        assert n == 8, (sid, n)
    via_range = dict(kv._table)
    kv.refresh_table()
    assert via_range == {k: v for k, v in kv._table.items()}
    pt = np.asarray(kv.page_table([0, 1, 2], 8))
    assert (pt >= 0).all()

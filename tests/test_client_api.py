"""Tests for the futures-based client API (repro.api, DESIGN.md §9).

Covers: oracle-differential correctness through ``DiLiClient`` under
balancer churn and message delays, admission pacing (client queues instead
of surfacing ``OutboxOverflow``), registry-cache routing (fewer delegation
hops than fixed-shard submission, wrong-route learning), and
Local/ShardMap backend parity on an identical seeded workload.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.api import DiLiClient, LocalBackend, RegistryCache
from repro.core.balancer import Balancer
from repro.core.oracle import OracleList
from repro.core.sim import Cluster, OutboxOverflow
from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE


def _cfg(**kw):
    base = dict(num_shards=4, pool_capacity=2048, max_sublists=32,
                max_ctrs=32, max_scan=2048, batch_size=16,
                mailbox_cap=128, split_threshold=24, move_batch=8)
    base.update(kw)
    return DiLiConfig(**base)


def _mixed(client, oracle, rng, rounds, n_per_round, key_space):
    checks = []
    for _ in range(rounds):
        kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE],
                           n_per_round).tolist()
        keys = rng.integers(1, key_space, n_per_round).tolist()
        checks.append((client.submit(kinds, keys),
                       oracle.apply_batch(kinds, keys)))
        client.pump()
    client.drain()
    return checks


def _assert_checks(checks):
    wrong = [(f.key, f.result(), exp)
             for batch, exps in checks for f, exp in zip(batch, exps)
             if f.result() != exp]
    assert not wrong, f"linearizability violations: {wrong[:5]}"


# ------------------------------------------------------------- correctness

def test_client_matches_oracle_under_churn():
    """Mixed workload + balancer churn + channel delays, vs the oracle."""
    backend = LocalBackend(_cfg(), seed=7, delay_prob=0.15)
    client = DiLiClient(backend, balance=Balancer(backend))
    oracle = OracleList()
    rng = np.random.default_rng(3)

    keys = rng.permutation(np.arange(1, 800))[:200].tolist()
    load = client.insert_batch(keys)
    oracle.apply_batch([OP_INSERT] * len(keys), keys)
    client.drain(run_balance=True)
    assert load.results() == [True] * len(keys)

    checks = _mixed(client, oracle, rng, rounds=12, n_per_round=24,
                    key_space=800)
    client.settle()
    _assert_checks(checks)
    assert client.all_keys() == sorted(oracle.snapshot())
    # churn actually happened: keys spread beyond the bootstrap shard
    owners = {e["owner"] for s in range(backend.n)
              for e in backend.sublists(s)}
    assert len(owners) > 1


def test_future_protocol():
    client = DiLiClient(LocalBackend(_cfg(num_shards=1)))
    f1 = client.insert(5)
    with pytest.raises(RuntimeError, match="pending"):
        f1.result(wait=False)
    assert not f1.done
    assert f1.result()          # wait=True drives drain()
    assert f1.done and f1.src == 0
    f2, f3 = client.insert(5), client.find(5)
    batch = client.remove_batch([5, 6])
    client.drain()
    assert not f2.result()      # duplicate insert
    assert f3.result()
    assert batch.done and batch.results() == [True, False]
    assert len(batch) == 2 and [b.key for b in batch] == [5, 6]


def test_registry_cache_semantics():
    cache = RegistryCache([(0, 10, 1), (10, 20, 2)])
    assert cache.lookup(1) == 1
    assert cache.lookup(10) == 1     # half-open: (keymin, keymax]
    assert cache.lookup(11) == 2
    assert cache.lookup(0) is None
    assert cache.lookup(21) is None
    cache.load([(0, 20, 3)])
    assert cache.lookup(10) == 3 and len(cache) == 1


# ----------------------------------------------------------------- pacing

def test_pacing_queues_instead_of_overflow():
    """A burst that overflows raw submission drains cleanly via the client.

    The raw path feeds ``in_cap`` delegating ops into one round, whose
    replies exceed ``mailbox_cap``; the client's in-flight cap keeps every
    round under budget, so the same burst queues client-side.
    """
    cfg = _cfg(num_shards=2, mailbox_cap=16, batch_size=32, move_batch=4)
    n_ops = 300
    keys = list(range(1, n_ops + 1))

    # control: raw fixed-shard burst at a non-owner overflows the outbox
    raw = Cluster(cfg)
    raw.submit(1, [OP_INSERT] * n_ops, keys)
    with pytest.raises(OutboxOverflow):
        raw.run_until_quiet(400)

    # the client paces the identical burst (fixed-shard routing, worst
    # case: every op delegates) without surfacing the overflow
    backend = LocalBackend(cfg)
    client = DiLiClient(backend, route_cache=False, home_shard=1)
    batch = client.insert_batch(keys)
    client.drain(max_rounds=4000)
    assert batch.results() == [True] * n_ops
    assert client.all_keys() == keys


# ---------------------------------------------------------------- routing

def _loaded_spread_backend(route_cache, *, seed=11):
    """Load 300 keys, balance until keys live on all 4 shards, drain."""
    backend = LocalBackend(_cfg(), seed=seed)
    client = DiLiClient(backend, balance=Balancer(backend),
                        route_cache=route_cache)
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.arange(1, 1200))[:300].tolist()
    client.insert_batch(keys)
    client.settle()
    owners = {e["owner"] for s in range(backend.n)
              for e in backend.sublists(s) if e["owner"] == s}
    assert len(owners) > 1, "balancer never spread the keyspace"
    client.balance = None       # freeze topology for the measured window
    return backend, client, keys


def test_cached_routing_reduces_hops():
    """Registry-cached routing beats fixed-shard submission on hops."""
    results = {}
    for cached in (True, False):
        backend, client, keys = _loaded_spread_backend(cached)
        if cached:
            client.refresh_route_cache()
        backend.stats.update(max_hops=0, delegated=0)
        probe = client.find_batch(keys[::3])
        client.drain()
        assert all(probe.results())
        results[cached] = dict(backend.stats)
    assert results[True]["max_hops"] < results[False]["max_hops"]
    assert results[True]["delegated"] < results[False]["delegated"]
    # a fresh cache routes every probe to its owner: zero delegations
    assert results[True]["max_hops"] == 0
    assert results[False]["max_hops"] >= 1


def test_wrong_route_replies_refresh_cache():
    """A stale cache is corrected by wrong-route completions, not manual
    refreshes: after the first delegated batch the client re-learns the
    registry and later ops go direct."""
    backend, client, keys = _loaded_spread_backend(True)
    # deliberately poison the cache back to the bootstrap view
    client._cache.load([(0, 2 ** 31 - 2, 0)])
    probe1 = client.find_batch(keys[:40])
    client.drain()
    assert all(probe1.results())
    assert client.wrong_routes > 0, "expected stale-route corrections"
    # cache now refreshed from the correcting shard: a second probe of the
    # same keys is hop-free
    backend.stats.update(max_hops=0, delegated=0)
    probe2 = client.find_batch(keys[:40])
    client.drain()
    assert all(probe2.results())
    assert backend.stats["max_hops"] == 0
    assert backend.stats["delegated"] == 0


# ---------------------------------------------------------- backend parity

PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np

    from repro.api import DiLiClient, LocalBackend, ShardMapBackend
    from repro.core.oracle import OracleList
    from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

    cfg = DiLiConfig(num_shards=4, pool_capacity=1024, max_sublists=16,
                     max_ctrs=16, max_scan=1024, batch_size=8,
                     mailbox_cap=64, move_batch=4)

    def run(backend):
        client = DiLiClient(backend)
        oracle = OracleList()
        rng = np.random.default_rng(0)
        results = []
        load = rng.permutation(np.arange(1, 120))[:60].tolist()
        batch = client.insert_batch(load)
        oracle.apply_batch([OP_INSERT] * len(load), load)
        client.drain()
        results += batch.results()

        # identical explicit background commands on both backends
        subs = [e for e in backend.sublists(0) if e["owner"] == 0]
        big = max(subs, key=lambda e: e["size"])
        mid = backend.middle_item(0, big["head_idx"])
        backend.split(0, big["keymax"], mid)
        client.drain()
        subs = [e for e in backend.sublists(0) if e["owner"] == 0]
        backend.move(0, subs[-1]["keymax"], 2)
        mixed = []
        for r in range(16):
            kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE], 8).tolist()
            keys = rng.integers(1, 160, 8).tolist()
            mixed.append(client.submit(kinds, keys))
            oracle.apply_batch(kinds, keys)
            client.pump()
        client.drain()
        for b in mixed:
            results += b.results()
        return results, backend.all_keys(), oracle

    res_local, keys_local, oracle_l = run(LocalBackend(cfg))
    res_smap, keys_smap, oracle_s = run(ShardMapBackend(cfg))

    assert oracle_l.snapshot() == oracle_s.snapshot()
    assert keys_local == sorted(oracle_l.snapshot()), "local diverged"
    assert keys_smap == sorted(oracle_s.snapshot()), "shard_map diverged"
    assert keys_local == keys_smap
    assert res_local == res_smap, "linearized results differ"
    print(f"OK parity over {len(res_local)} checked ops, "
          f"{len(keys_local)} final keys")
""")


@pytest.mark.slow
def test_backend_parity_local_vs_shard_map():
    """Same seeded workload + same bg commands through both backends →
    identical linearized results and final key sets."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", PARITY_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK parity" in r.stdout

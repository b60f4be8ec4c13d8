"""``ShardMapBackend``'s host driver against ``LocalBackend``'s, on four
virtual CPU devices.

One seeded op stream runs through ``DiLiClient`` with the balancer live
(Splits and Moves) on ``LocalBackend`` and on ``ShardMapBackend``, on its
on-device ``all_to_all`` path and on its host-route path. Each round must
complete the same ops with the same results on the same shards, the two
round traces must agree (op ids aside: each backend recycles them in its
own order), the stats must be equal, and every result must be the
sequential oracle's. ``ShardMapBackend.step`` must read the devices once
a round (the packed harvest), and on the ``all_to_all`` path its
``routed_rows`` counter must equal the live rows of the routed inbox,
pulled by hand; its packed host snapshots of the stacked state and
BgTables must hold what a plain pull of each leaf holds. The runs go in a subprocess, so the test session keeps
its single-device view.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import json, os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax._src.array import ArrayImpl

    from repro.api import DiLiClient, LocalBackend, ShardMapBackend
    from repro.core import messages as M
    from repro.core.balancer import Balancer
    from repro.core.net import NemesisConfig
    from repro.core.oracle import OracleList
    from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

    PATH = sys.argv[1]
    NEMESIS = NemesisConfig() if PATH == "hostroute" else None
    cfg = DiLiConfig(num_shards=4, pool_capacity=1024, max_sublists=32,
                     max_ctrs=32, max_scan=1024, batch_size=8,
                     mailbox_cap=64, move_batch=4, block_probe=True)

    # every read of a device array on the host, counted once (a sharded
    # array's ``__array__`` reads through ``_value``)
    READS = [0]
    DEPTH = [0]
    _array, _value = ArrayImpl.__array__, ArrayImpl._value

    def _counted(read):
        def counted(self, *a, **k):
            READS[0] += DEPTH[0] == 0
            DEPTH[0] += 1
            try:
                return read(self, *a, **k)
            finally:
                DEPTH[0] -= 1
        return counted

    _counted_array = _counted(_array)
    _counted_value = _counted(_value.fget)

    ArrayImpl.__array__ = _counted_array
    ArrayImpl._value = property(_counted_value)

    class Recorder:
        # the client's backend: each round's completions by the
        # submission index of their op, the device reads of the round,
        # and the routed inbox's live rows against ``routed_rows``
        def __init__(self, backend):
            self.backend = backend
            self.client = None
            self.index = {}
            self.rounds, self.reads, self.routed = [], [], []

        def step(self):
            r0 = self.backend.stats.get("routed_rows", 0)
            n0 = READS[0]
            comps = self.backend.step()
            self.reads.append(READS[0] - n0)
            futs = self.client._inflight
            self.rounds.append(sorted([self.index[id(futs[o])], v, s]
                                      for o, v, s in comps))
            if PATH == "a2a" and isinstance(self.backend, ShardMapBackend):
                rows = np.asarray(self.backend._inbox)
                live = int((rows[..., M.F_KIND] != M.MSG_NONE).sum())
                self.routed.append([self.backend.stats["routed_rows"] - r0,
                                    live])
            return comps

        def __getattr__(self, name):
            return getattr(self.backend, name)

    def run(backend, trace):
        rec = Recorder(backend)
        bal = Balancer(backend, split_threshold=12, merge_threshold=3,
                       rng=backend.balancer_rng)
        client = DiLiClient(rec, balance=bal, balance_every=3)
        rec.client = client
        call = {OP_FIND: client.find, OP_INSERT: client.insert,
                OP_REMOVE: client.remove}
        ops = []

        def submit(kind, key):
            fut = call[kind](key)
            rec.index[id(fut)] = len(ops)
            ops.append((kind, key, fut))

        rng = np.random.default_rng(5)
        for k in rng.permutation(np.arange(1, 200))[:120].tolist():
            submit(OP_INSERT, k)
        client.drain()
        for _ in range(30):
            for _ in range(16):
                submit(int(rng.choice([OP_FIND, OP_INSERT, OP_REMOVE])),
                       int(rng.integers(1, 200)))
            client.pump()
        client.settle()
        oracle = OracleList()
        wrong = sum(fut.result(wait=False) != oracle.apply(kind, key)
                    for kind, key, fut in ops)
        final = backend.all_keys() == list(oracle.snapshot())
        # the packed snapshots hold what a plain pull of each leaf holds
        exact = True
        if isinstance(backend, ShardMapBackend):
            for stacked, snap in ((backend._states, backend.states),
                                  (backend._bgs, backend.bgs)):
                plain = jax.device_get(stacked)
                for s in range(4):
                    got = jax.tree_util.tree_leaves(snap[s])
                    want = [x[s] for x in jax.tree_util.tree_leaves(plain)]
                    exact &= all(g.dtype == w.dtype
                                 and np.array_equal(g, w)
                                 for g, w in zip(got, want))
        return {"rounds": rec.rounds, "reads": rec.reads,
                "routed": rec.routed, "stats": dict(backend.stats),
                "trace": [re.sub(r"[|]c[[][^]]*[]]", "|c", line)
                          for line in trace(backend)],
                "ops": len(ops), "wrong": wrong, "final": final,
                "exact": exact}

    out = {"local": run(LocalBackend(cfg, seed=3, trace=True,
                                     nemesis=NEMESIS),
                        lambda b: b.cluster.round_trace),
           "spmd": run(ShardMapBackend(cfg, seed=3, trace=True,
                                       nemesis=NEMESIS),
                       lambda b: b.round_trace)}
    print(json.dumps(out))
""")


@pytest.mark.parametrize("path", ["a2a", "hostroute"])
def test_shardmap_driver_matches_local_backend(path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT, path], env=env,
                       cwd=root, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    local, spmd = out["local"], out["spmd"]
    for run in (local, spmd):
        assert run["ops"] > 500 and run["wrong"] == 0 and run["final"]
    assert spmd["exact"]
    assert spmd["rounds"] == local["rounds"]
    assert sum(map(len, local["rounds"])) == local["ops"]
    assert len(spmd["trace"]) == len(spmd["rounds"])
    assert spmd["trace"] == local["trace"]
    routed = spmd["stats"].pop("routed_rows")
    assert spmd["stats"] == local["stats"]
    assert local["stats"]["max_bg_active"] > 0     # the balancer ran
    # one read of the devices a round: the packed harvest
    assert set(spmd["reads"]) == {1}
    if path == "a2a":
        assert [d for d, _ in spmd["routed"]] == \
            [live for _, live in spmd["routed"]]
        assert routed == sum(d for d, _ in spmd["routed"]) > 0
    else:
        assert routed == 0

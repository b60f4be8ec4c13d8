"""The round's counters agree across backends, each backend's round
writes its host spans into a profiler trace, and ``Cluster.step``
harvests each shard-round from one packed vector.

One seeded client workload runs on ``LocalBackend`` and on
``ShardMapBackend`` over four virtual devices, on its on-device
``all_to_all`` path and on its host-route path: the three report equal
``serial_rows`` (rows the serial loop executed), ``blk_rows`` (packed
blocks rebuilt) and fast-path counts, all of them non-zero. The SPMD
paths run in a subprocess, so the test session keeps its single-device
view.
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import messages as M
from repro.core import refs
from repro.core.balancer import Balancer
from repro.core.net.digest import state_digest, trace_digest
from repro.core.shard import Harvest, shard_round, unpack_harvest
from repro.core.sim import Cluster
from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

SCRIPT = textwrap.dedent("""
    import json, os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from pathlib import Path
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.api import DiLiClient, LocalBackend, ShardMapBackend
    from repro.core.net import NemesisConfig
    from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

    cfg = DiLiConfig(num_shards=4, pool_capacity=1024, max_sublists=16,
                     max_ctrs=16, max_scan=1024, batch_size=8,
                     mailbox_cap=64, move_batch=4, block_probe=True)

    def run(backend):
        client = DiLiClient(backend)
        rng = np.random.default_rng(0)
        client.insert_batch(rng.permutation(np.arange(1, 160))[:80].tolist())
        client.drain()
        for r in range(12):
            kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE], 24).tolist()
            keys = rng.integers(1, 160, 24).tolist()
            client.submit(kinds, keys)
            if r == 10:       # the last rounds under the profiler
                d = tempfile.mkdtemp()
                jax.profiler.start_trace(d)
            client.pump()
        client.drain()
        jax.profiler.stop_trace()
        pb = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        names = sorted({e.name for p in ProfileData.from_file(str(pb)).planes
                        for line in p.lines for e in line.events
                        if e.name.startswith(("cluster.", "shardmap.",
                                              "client."))})
        return dict(client.stats), names

    out = {"local": run(LocalBackend(cfg, seed=0)),
           "spmd": run(ShardMapBackend(cfg, seed=0)),
           "hostroute": run(ShardMapBackend(cfg, seed=0,
                                            nemesis=NemesisConfig()))}
    print(json.dumps(out))
""")

COUNTED = ("serial_rows", "blk_rows", "fast_hits", "mut_hits", "blk_hits")


def test_counters_agree_across_backends_and_spans_are_written():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    local, spmd, host = (out[k][0] for k in ("local", "spmd", "hostroute"))
    for name in COUNTED:
        assert local[name] > 0, (name, local)
        assert spmd[name] == local[name], (name, spmd, local)
        assert host[name] == local[name], (name, host, local)
    assert local["rounds"] == spmd["rounds"] == host["rounds"]
    client = ["client.admit", "client.resolve"]
    assert out["local"][1] == client + ["cluster.harvest", "cluster.launch",
                                        "cluster.rates", "cluster.route"]
    assert out["spmd"][1] == client + ["shardmap.harvest",
                                       "shardmap.launch"]
    assert out["hostroute"][1] == client + ["shardmap.harvest",
                                            "shardmap.launch",
                                            "shardmap.route"]


CFG = DiLiConfig(num_shards=4, pool_capacity=2048, max_sublists=64,
                 max_ctrs=64, max_scan=2048, batch_size=16,
                 mailbox_cap=128, move_batch=4, split_threshold=12,
                 block_cap=16, block_probe=True, range_scan=True)


def _seeded_cluster(rounds, on_round=None):
    """A delayed-channel cluster under the balancer: mixed ops and RANGE
    scans submitted at every shard, then run to quiescence."""
    cl = Cluster(CFG, seed=9, delay_prob=0.2, trace=True)
    bal = Balancer(cl, merge_threshold=3)
    rng = np.random.default_rng(9)
    ids, scans = [], []
    for r in range(rounds):
        kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE], 24,
                           p=[0.3, 0.5, 0.2]).tolist()
        ids += cl.submit(r % 4, kinds, rng.integers(1, 400, 24).tolist())
        if r % 5 == 0:
            lo = int(rng.integers(1, 300))
            scans.append(cl.submit_range(r % 4, lo, lo + 60, 40))
        if on_round is not None:
            on_round(cl)
        cl.step()
        if r % 3 == 2:
            bal.step()
    return cl, ids, scans


def test_harvest_unpacks_to_the_round_fields():
    cl, _, _ = _seeded_cluster(12)
    for s in range(cl.n):
        inbox = np.zeros((cl.in_cap, M.FIELDS), np.int32)
        feed = cl.backlog[s][:cl.in_cap]
        inbox[:feed.shape[0]] = feed
        out = shard_round(cl.states[s], cl.bgs[s], s, inbox,
                          np.zeros((0, M.FIELDS), np.int32), CFG)
        h = unpack_harvest(np.asarray(out.harvest), CFG)
        want = dict(counters=out.counters, ent_hits=out.ent_hits,
                    keymax=out.state.registry.keymax, outbox=out.outbox,
                    comp_slot=out.comp_slot, comp_val=out.comp_val,
                    comp_src=out.comp_src, comp_key=out.comp_key)
        assert set(want) == set(Harvest._fields)
        for name, arr in want.items():
            np.testing.assert_array_equal(getattr(h, name), np.asarray(arr),
                                          err_msg=name)
            assert getattr(h, name).dtype == np.int32, name


# The same run at the full-width block rebuild and the harvest's separate
# pulls of each RoundOut field: results (with RANGE items), final shard
# states and BgTables, the round trace and the stats must not move.
SEEDED_RUN = {
    "results": "75f053e63f423ac5d2612895c9bf034697da62322f1c683caec3d086d8b03dd0",
    "states": "8689b3f546ad8dd5f14de2ae8b6ce96a1ef345ef87b076a5d844f286892a04b5",
    "trace": "df793c8b88afaf5c8c79a499e2cb03af6cd10fa29067a91a70bbc6128b48cca2",
    "stats": {"blk_hits": 133, "delegated": 720, "fast_hits": 70,
              "max_bg_active": 2, "max_hops": 1, "max_outbox": 42,
              "move_hits": 0, "mut_hits": 149, "range_hits": 17,
              "rep_hits": 0, "rounds": 42, "serial_rows": 2440},
}


def test_seeded_run_is_unchanged():
    cl, ids, scans = _seeded_cluster(40)
    cl.run_until_quiet(2000)
    res = [(i, cl.results[i], cl.result_src[i]) for i in ids + scans]
    items = [cl.take_range_items(i) for i in scans]
    got = dict(
        results=hashlib.sha256(json.dumps([res, items]).encode()).hexdigest(),
        states=state_digest(cl.states, cl.bgs),
        trace=trace_digest(cl.round_trace),
        stats={k: v for k, v in cl.stats.items() if k != "blk_rows"})
    assert got == SEEDED_RUN


def _need_rows(st, s):
    """Registry entries of shard s's state that ``refresh_blocks`` must
    rebuild: owned, live, unswitched, not moving, and not valid."""
    reg = st.registry
    m = reg.keymin.shape[0]
    sh = np.asarray(reg.subhead).astype(np.int64)
    newloc = np.asarray(st.pool.newloc).astype(np.int64)
    stct = np.asarray(st.stct)
    head = np.clip(sh & refs.IDX_MASK, 0, newloc.shape[0] - 1)
    slot = np.clip(np.asarray(reg.ctr), 0, stct.shape[0] - 1)
    null = refs.NULL_REF
    live = (np.arange(m) < int(reg.size)) \
        & ((sh & ~refs.MARK_BIT) != null) \
        & ((sh & refs.SID_MASK) >> refs.IDX_BITS == s) \
        & (stct[slot] >= 0) & ((newloc[head] & ~refs.MARK_BIT) == null)
    return int((live & ~np.asarray(st.blk.valid)).sum())


def test_blk_rows_counts_the_rows_that_need_a_rebuild():
    seen = []

    def check(cl):
        # the rows of the previous round are settled: compare, then
        # expect this round's
        if seen:
            assert cl.stats["blk_rows"] == seen[-1], (cl.round_no, seen)
        seen.append(cl.stats["blk_rows"]
                    + sum(_need_rows(st, s) for s, st in enumerate(cl.states)))

    cl, _, _ = _seeded_cluster(30, on_round=check)
    assert cl.stats["blk_rows"] == seen[-1]
    assert cl.stats["blk_rows"] > 0

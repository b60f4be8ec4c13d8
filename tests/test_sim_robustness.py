"""Regression tests for Cluster client-API and routing robustness.

R1  ``Cluster.submit`` accepts generators/iterators for kinds/keys/values —
    the old ``len(list(keys))`` probe exhausted the iterator before the
    ``zip``, silently dropping every op (``ids == []``, no error).
R2  submit validates length mismatches loudly instead of zip-truncating.
R3  Outbox overflow raises ``OutboxOverflow`` unconditionally — it must
    not be an ``assert`` (``python -O`` would silently truncate messages,
    and a lost replicate/ack deadlocks ``run_until_quiet``).
R4  Op ids are int32 message lanes: completed ids drained through
    ``take_result`` are recycled, and exhaustion raises instead of
    silently wrapping into colliding ids.
R5  ``shard_chain`` raises on a cyclic/corrupted chain instead of
    returning a silent prefix (which made ``all_keys()``-based
    assertions pass vacuously).
R6  The balancer's vectorized sublist sizes equal the per-node chain walk.
"""
import numpy as np
import pytest

from repro.core import refs
from repro.core.oracle import OracleList
from repro.core.sim import Cluster, OutboxOverflow
from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

CFG = DiLiConfig(num_shards=2, pool_capacity=2048, max_sublists=16,
                 max_ctrs=16, max_scan=2048, batch_size=16,
                 mailbox_cap=128)


def test_submit_accepts_generators():
    """R1: generator inputs must land every op, not silently drop all."""
    cl = Cluster(CFG)
    keys = list(range(10, 26))
    ids = cl.submit(0,
                    (OP_INSERT for _ in keys),
                    (k for k in keys),
                    (k * 2 for k in keys))
    assert len(ids) == len(keys), "generator ops were silently dropped"
    cl.run_until_quiet(400)
    assert [bool(cl.results[j]) for j in ids] == [True] * len(keys)
    assert cl.all_keys() == sorted(keys)
    # values rode along (payload is stored in pool.keymax for items)
    chain = {k: v for k, _, v in cl.shard_chain(0, 0, include_meta=True)}
    assert chain == {k: k * 2 for k in keys}


def test_submit_generator_matches_list_submission():
    """R1: a generator submission behaves exactly like the list one."""
    keys = list(range(5, 45, 3))
    a, b = Cluster(CFG), Cluster(CFG)
    ids_a = a.submit(0, [OP_INSERT] * len(keys), list(keys))
    ids_b = b.submit(0, (OP_INSERT for _ in keys), iter(keys))
    a.run_until_quiet(400)
    b.run_until_quiet(400)
    assert ids_a == ids_b
    assert [a.results[j] for j in ids_a] == [b.results[j] for j in ids_b]
    assert a.all_keys() == b.all_keys() == sorted(set(keys))
    oracle = OracleList(keys)
    assert a.all_keys() == sorted(oracle.snapshot())


def test_submit_length_mismatch_raises():
    """R2: mismatched kinds/keys/values must fail loudly, not truncate."""
    cl = Cluster(CFG)
    with pytest.raises(ValueError):
        cl.submit(0, [OP_INSERT] * 3, [1, 2])
    with pytest.raises(ValueError):
        cl.submit(0, [OP_INSERT] * 2, [1, 2], [7])


def test_outbox_overflow_raises():
    """R3: a round emitting more messages than mailbox_cap must raise."""
    cfg = DiLiConfig(num_shards=2, pool_capacity=512, max_sublists=8,
                     max_ctrs=8, max_scan=512, batch_size=16,
                     mailbox_cap=4, find_fastpath=False, mut_fastpath=False)
    cl = Cluster(cfg)
    # every key is owned by shard 0, so each op submitted at shard 1
    # delegates: 12 outbox rows in one round > mailbox_cap = 4
    cl.submit(1, [OP_FIND] * 12, list(range(10, 22)))
    with pytest.raises(OutboxOverflow, match="mailbox_cap"):
        cl.step()


def test_outbox_at_cap_does_not_raise():
    """R3: exactly-at-cap rounds are legal — only genuine overflow raises."""
    cfg = DiLiConfig(num_shards=2, pool_capacity=512, max_sublists=8,
                     max_ctrs=8, max_scan=512, batch_size=16,
                     mailbox_cap=4, find_fastpath=False, mut_fastpath=False)
    cl = Cluster(cfg)
    cl.submit(1, [OP_FIND] * 4, list(range(10, 14)))
    cl.run_until_quiet(100)
    assert cl.stats["max_outbox"] == 4
    assert all(cl.results[j] == 0 for j in range(4))  # absent keys


def test_op_ids_recycle_via_take_result():
    """R4: drained op ids are reissued; _next_slot stays bounded."""
    cl = Cluster(CFG)
    ids = cl.submit(0, [OP_INSERT] * 4, [10, 11, 12, 13])
    cl.run_until_quiet(200)
    for j in ids:
        assert cl.take_result(j) == 1
        with pytest.raises(KeyError):
            cl.take_result(j)       # already drained
    top = cl._ids.next_id
    ids2 = cl.submit(0, [OP_FIND] * 4, [10, 11, 12, 13])
    assert sorted(ids2) == sorted(ids), "drained ids were not reissued"
    assert cl._ids.next_id == top
    cl.run_until_quiet(200)
    assert [cl.take_result(j) for j in ids2] == [1] * 4


def test_op_id_exhaustion_raises():
    """R4: id-space exhaustion must raise, not wrap into int32 aliasing."""
    cl = Cluster(CFG)
    cl._ids.next_id = np.iinfo(np.int32).max
    with pytest.raises(RuntimeError, match="op-id space exhausted"):
        cl.submit(0, [OP_FIND], [5])
    # recycled ids keep a full results dict submittable at the guard
    cl._ids.release(7)
    assert cl.submit(0, [OP_FIND], [5]) == [7]


def test_shard_chain_cycle_raises():
    """R5: a corrupted (cyclic) chain must raise, not truncate silently."""
    cl = Cluster(CFG)
    ids = cl.submit(0, [OP_INSERT] * 3, [10, 20, 30])
    cl.run_until_quiet(200)
    assert cl.all_keys() == [10, 20, 30]
    # corrupt: point the node holding key 20 back at itself
    st = cl.states[0]
    idx = {k: i for k, i, _ in cl.shard_chain(0, 0, include_meta=True)}[20]
    cl.states[0] = st._replace(pool=st.pool._replace(
        nxt=st.pool.nxt.at[idx].set(refs.make_ref(0, idx))))
    with pytest.raises(RuntimeError, match="did not terminate"):
        cl.shard_chain(0, 0)
    with pytest.raises(RuntimeError, match="did not terminate"):
        cl.all_keys()


def test_sublist_sizes_match_chain_walks():
    """R6: the lock-step ``chain_sizes`` the balancer reads agrees with a
    per-node ``chain_keys`` walk on every entry — mid-churn (marked nodes
    not yet delinked, splits and moves in flight) and once quiet."""
    from repro.core.balancer import Balancer
    from repro.core.sim import chain_keys, state_sublists

    cfg = CFG._replace(split_threshold=12)
    cl = Cluster(cfg)
    bal = Balancer(cl)
    rng = np.random.default_rng(0)
    keys = rng.choice(np.arange(1, 4000), 300, replace=False).tolist()
    cl.submit(0, [OP_INSERT] * len(keys), keys)
    cl.submit(1, [OP_REMOVE] * 100, keys[::3])

    def check():
        for s in range(cfg.num_shards):
            for e in state_sublists(cfg, cl.states, s):
                if e["owner"] == s:
                    assert e["size"] == len(
                        chain_keys(cfg, cl.states, s, e["head_idx"]))
                else:
                    assert e["size"] is None

    for r in range(60):
        cl.step()
        if r % 3 == 0:
            bal.step()
            check()
    cl.run_until_quiet(2000)
    check()
    assert sum(e["size"] or 0 for s in range(cfg.num_shards)
               for e in state_sublists(cfg, cl.states, s)
               if not e["switched"]) == len(cl.all_keys())

"""The packed-block rebuild walks only the rows that need it
(DESIGN.md §12): ``refresh_blocks`` compacts the dirty, owned, live
registry entries into chunks of up to 128 lanes and walks one chunk at a
time. Rows never interacted in the full-width lock-step walk over all M
entries, so the compacted walk must leave ``keys``, ``idx`` and
``valid`` exactly as that walk does. The full-width walk is kept here as
the reference, and both run on seeded states of a churned cluster —
mid-Split, mid-Move and switched entries among them — with more than 128
needing rows in one shard, and on the same states with a tombstone, an
in-chain SubHead, a mid-Split subtail, a moving node, a remote node and
a switched counter planted in owned chains. Every valid row is also
checked against ``sim.chain_keys``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocks as BL
from repro.core import refs
from repro.core.balancer import Balancer
from repro.core.sim import Cluster, chain_keys
from repro.core.types import (Blocks, DiLiConfig, OP_INSERT, OP_REMOVE,
                              SH_KEY, ST_KEY)

# many small sublists (split at 6 keys) so one shard holds > 128 entries;
# block_cap 8 lets some chains overflow their row between splits
CFG = DiLiConfig(num_shards=2, pool_capacity=4096, max_sublists=320,
                 max_ctrs=320, max_scan=4096, batch_size=64,
                 mailbox_cap=512, move_batch=8, split_threshold=6,
                 bg_slots=8, block_cap=8, block_probe=True)


def full_width_refresh(state, me, cfg):
    """The rebuild as one lock-step walk across all M entries."""
    pool = state.pool
    reg = state.registry
    blk = state.blk
    m = reg.keymin.shape[0]
    c = cfg.block_cap
    n = pool.key.shape[0]
    me = jnp.asarray(me, jnp.int32)

    eidx = jnp.arange(m, dtype=jnp.int32)
    sh = reg.subhead
    head_idx = jnp.clip(refs.ref_idx(sh).astype(jnp.int32), 0, n - 1)
    slot = jnp.clip(reg.ctr, 0, state.stct.shape[0] - 1)
    live = (eidx < reg.size) & (~refs.is_null(sh)) & \
        (refs.ref_sid(sh) == me) & (state.stct[slot] >= 0) & \
        refs.is_null(pool.newloc[head_idx])
    need = live & (~blk.valid)

    keys0 = jnp.where(need[:, None], ST_KEY, blk.keys)
    idx0 = jnp.where(need[:, None], 0, blk.idx)
    st_ref = refs.unmarked(reg.subtail)
    rows_ = jnp.arange(m, dtype=jnp.int32)
    bound = int(cfg.max_scan)

    def w_cond(carry):
        i, keys, idxs, col, cur, collecting, good = carry
        return (i < bound) & jnp.any(collecting)

    def w_body(carry):
        i, keys, idxs, col, cur, collecting, good = carry
        ci = jnp.clip(refs.ref_idx(cur).astype(jnp.int32), 0, n - 1)
        local = refs.ref_sid(cur) == me
        word = pool.nxt[ci]
        marked = refs.ref_mark(word)
        moving = ~refs.is_null(pool.newloc[ci])
        switched = state.stct[jnp.clip(pool.ctr[ci], 0,
                                       state.stct.shape[0] - 1)] < 0
        k = pool.key[ci]
        at_st = k == ST_KEY
        reach_ok = at_st & (~marked) & (refs.unmarked(cur) == st_ref)
        hop = (k == SH_KEY) | (marked & ~at_st)
        want_write = (~at_st) & (~hop)
        bad = (~local) | refs.is_null(cur) | moving | switched \
            | (at_st & ~reach_ok) | (want_write & (col >= c))
        write = collecting & (~bad) & want_write
        at_col = jnp.where(write, col, c)
        keys = keys.at[rows_, at_col].set(k, mode="drop")
        idxs = idxs.at[rows_, at_col].set(ci, mode="drop")
        good = good | (collecting & reach_ok)
        collecting = collecting & (~bad) & (~reach_ok)
        col = col + write.astype(jnp.int32)
        cur = jnp.where(collecting, word, cur)
        return i + 1, keys, idxs, col, cur, collecting, good

    init = (jnp.zeros((), jnp.int32), keys0, idx0,
            jnp.zeros((m,), jnp.int32), pool.nxt[head_idx], need,
            jnp.zeros((m,), bool))
    _, keys, idxs, _, _, _, good = jax.lax.while_loop(w_cond, w_body, init)
    valid = (blk.valid | good) & live
    return state._replace(blk=Blocks(keys=keys, idx=idxs, valid=valid)), \
        jnp.sum(need, dtype=jnp.int32)


_compacted = jax.jit(BL.refresh_blocks, static_argnames=("cfg",))
_reference = jax.jit(full_width_refresh, static_argnames=("cfg",))


def _churned_snapshots():
    """Both shards' states before each round of a seeded load-and-remove
    run under the balancer (splits, moves, merges in flight)."""
    cl = Cluster(CFG, seed=3)
    bal = Balancer(cl, merge_threshold=2)
    rng = np.random.default_rng(11)
    keys = rng.permutation(np.arange(1, 3000))[:1400]
    kinds = np.where(rng.random(keys.size) < 0.8, OP_INSERT, OP_REMOVE)
    snaps = []
    b = CFG.batch_size
    for r, i in enumerate(range(0, keys.size, b)):
        snaps.append(list(cl.states))
        cl.submit(r % 2, kinds[i:i + b].tolist(), keys[i:i + b].tolist())
        cl.step()
        bal.step()
    for _ in range(60):
        snaps.append(list(cl.states))
        cl.step()
        bal.step()
    snaps.append(list(cl.states))
    return snaps


@pytest.fixture(scope="module")
def snapshots():
    return _churned_snapshots()


def _owned_chains(states, s, min_len):
    """(entry, live (key, idx) nodes) of shard s's owned live entries."""
    st = states[s]
    reg = st.registry
    n = int(reg.size)
    sh = np.asarray(reg.subhead)[:n].astype(np.int64)
    out = []
    for e in range(n):
        if (sh[e] & refs.SID_MASK) >> refs.IDX_BITS != s:
            continue
        items = chain_keys(CFG, states, s, int(sh[e] & refs.IDX_MASK),
                           include_meta=True)
        if len(items) >= min_len:
            out.append((e, [(k, i) for k, i, _ in items]))
    return out


def _plant(states, s):
    """Shard s's state with one of each screened case planted in its own
    owned chain; returns (state, {case: entry})."""
    st = states[s]
    pool = st.pool
    nxt = np.asarray(pool.nxt).copy()
    key = np.asarray(pool.key).copy()
    newloc = np.asarray(pool.newloc).copy()
    ctr = np.asarray(pool.ctr).copy()
    stct = np.asarray(st.stct).copy()
    chains = _owned_chains(states, s, 3)
    cases = ("tombstone", "in_chain_sh", "mid_split_st", "moving", "remote",
             "switched")
    assert len(chains) >= len(cases), len(chains)
    at = {}
    for case, (e, nodes) in zip(cases, chains):
        at[case] = e
        x = nodes[1][1]                       # a node inside the chain
        if case == "tombstone":
            nxt[x] |= refs.MARK_BIT
        elif case == "in_chain_sh":
            key[x] = SH_KEY
        elif case == "mid_split_st":
            key[x] = ST_KEY
        elif case == "moving":
            newloc[x] = int(refs.make_ref(1 - s, 0))
        elif case == "remote":
            prev = nodes[0][1]
            nxt[prev] = (int(nxt[prev]) & refs.MARK_BIT) | \
                int(refs.make_ref(1 - s, nodes[1][1]))
        else:
            free = np.nonzero(stct == 0)[0]
            free = free[free != 0]
            ctr[x] = int(free[-1])
            stct[ctr[x]] = -1
    planted = st._replace(
        pool=pool._replace(nxt=jnp.asarray(nxt), key=jnp.asarray(key),
                           newloc=jnp.asarray(newloc),
                           ctr=jnp.asarray(ctr)),
        stct=jnp.asarray(stct))
    return planted, at


def _assert_same(got, want):
    for f in ("keys", "idx", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got.blk, f)),
                                      np.asarray(getattr(want.blk, f)),
                                      err_msg=f)


def _assert_rows_mirror_chains(states, s, st):
    """Every valid row holds exactly its entry's live chain, ST_KEY-padded."""
    valid = np.asarray(st.blk.valid)
    keys = np.asarray(st.blk.keys)
    idx = np.asarray(st.blk.idx)
    sh = np.asarray(st.registry.subhead)
    view = list(states)
    view[s] = st
    for e in np.nonzero(valid)[0]:
        items = chain_keys(CFG, view, s, int(sh[e]) & refs.IDX_MASK,
                           include_meta=True)
        n = len(items)
        np.testing.assert_array_equal(keys[e, :n], [k for k, _, _ in items])
        np.testing.assert_array_equal(idx[e, :n], [i for _, i, _ in items])
        assert (keys[e, n:] == ST_KEY).all(), e


def test_compacted_walk_equals_full_width_walk(snapshots):
    seen = dict(max_need=0, rejected=0, valid=0, kept=0)
    rng = np.random.default_rng(7)
    for states in snapshots[::3]:
        for s in range(CFG.num_shards):
            cold = states[s]._replace(blk=BL.invalidate_all(states[s].blk))
            warm, _ = _reference(cold, s, cfg=CFG)
            # a mirror with some rows still valid, as writers leave it
            dirty = jnp.asarray(rng.random(CFG.max_sublists) < 0.5)
            warm = warm._replace(blk=warm.blk._replace(
                valid=warm.blk.valid & ~dirty))
            for st in (states[s], cold, warm):
                got, rows = _compacted(st, s, cfg=CFG)
                want, rows_ref = _reference(st, s, cfg=CFG)
                _assert_same(got, want)
                assert int(rows) == int(rows_ref)
                _assert_rows_mirror_chains(states, s, got)
                seen["max_need"] = max(seen["max_need"], int(rows))
                valid = np.asarray(got.blk.valid)
                seen["valid"] += int(valid.sum())
                seen["rejected"] += int(rows) - int(
                    (valid & ~np.asarray(st.blk.valid)).sum())
                seen["kept"] += int((valid & np.asarray(st.blk.valid)).sum())
    # several chunks in one shard, rows rejected by the screens, rows
    # rebuilt, and rows a still-valid mirror kept as they were
    assert seen["max_need"] > 128, seen
    assert seen["rejected"] > 0 and seen["valid"] > 0 and seen["kept"] > 0, \
        seen


def test_compacted_walk_on_planted_chains(snapshots):
    states = snapshots[-1]
    s = max(range(CFG.num_shards),
            key=lambda s: len(_owned_chains(states, s, 3)))
    planted, at = _plant(states, s)
    planted = planted._replace(blk=BL.invalidate_all(planted.blk))
    got, rows = _compacted(planted, s, cfg=CFG)
    want, _ = _reference(planted, s, cfg=CFG)
    _assert_same(got, want)
    assert int(rows) > 128
    valid = np.asarray(got.blk.valid)
    # logically absent nodes are stepped over; every other case rejects
    assert valid[at["tombstone"]] and valid[at["in_chain_sh"]], at
    for case in ("mid_split_st", "moving", "remote", "switched"):
        assert not valid[at[case]], case
    _assert_rows_mirror_chains(states, s, got)

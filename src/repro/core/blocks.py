"""Packed-block sublists (DESIGN.md §12): maintain and probe the
``Blocks`` mirror — each owned registry entry's live chain keys as one
contiguous, sorted ``int32[C]`` row — so the stage-2 probe of both batched
fast-paths can run as ``kernels/hybrid_search``'s single VMEM sweep
instead of ``traverse.probe_batch``'s lock-step pointer-gather walk.

The discipline is cache-with-detectable-staleness, never a second source
of truth:

  * ``refresh_blocks`` runs at round *start* (before anything mutates) and
    rebuilds only rows that are dirty AND owned-and-live. The rebuild is
    a lock-step chain walk over those rows alone, compacted into chunks
    of up to 128 lanes; a row validates only
    when its walk saw exclusively local, non-moving (newLoc == null),
    non-switched (stCt >= 0) nodes, collected at most C *live* keys, and
    terminated at the entry's *registered*, unmarked SubTail. Marked
    nodes are *skipped*, not rejected: they are logically absent (exactly
    what ``sim.chain_keys`` and the serial traversal do), and tombstones
    linger until a delinking walk — rejecting them would permanently
    invalidate any entry that ever saw a remove. The subtail-identity
    check screens out a mid-Split chain (the walk would stop at the
    freshly inserted mid-ST, capturing only the left half while the
    registry entry still covers both). Anything dirtier stays invalid and
    bounces to the pointer walk — the differential oracle.

  * writers invalidate: the mutation fast-path clears the rows it fires
    into (``batch_apply``), the bg phases clear at their compaction points
    (split/merge/replay hooks), and ``shard_round`` drops the whole mirror
    on any serial-path mutation or bg activity (the blanket rule — serial
    rows and bg phases may touch any chain or shift the registry's
    entry indexing, and per-entry attribution there is not worth the
    bookkeeping).

A valid block therefore proves more than membership: its chain is
entirely local/non-moving/non-switched *as of round start* and its live
keys are exactly the row, so a block-answered lane needs none of
``probe_batch``'s per-node screens — only the caller's usual left-node
re-check when the Harris window's left is the SubHead itself (never
walked by either probe). A block window ``(left, right)`` may have
*marked* nodes physically between its two live nodes; the mutation
fast-path's net-insert splice (``left.nxt = new, new.nxt = right``)
then delinks them — precisely the Harris delink the serial traversal
performs on the way, so the physical divergence from a pointer-walk
window is itself a legal step of the algorithm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import refs
from ..kernels import ops as K
from .types import Blocks, DiLiConfig, SH_KEY, ST_KEY, ShardState


def invalidate_all(blk: Blocks) -> Blocks:
    return blk._replace(valid=jnp.zeros_like(blk.valid))


def invalidate_entry(blk: Blocks, e, when=True) -> Blocks:
    """Clear entry ``e``'s valid bit (scatter-drop when e is out of range
    or ``when`` is False)."""
    m = blk.valid.shape[0]
    at = jnp.where(when & (e >= 0), e, m)
    return blk._replace(valid=blk.valid.at[at].set(False, mode="drop"))


# Lanes of one chunk of the rebuild walk: the TPU's vector lane width.
_LANES = 128


def refresh_blocks(state: ShardState, me, cfg: DiLiConfig):
    """Rebuild every dirty, owned, live registry entry's packed block.

    Returns ``(state, rows)``, ``rows`` the number of entries rebuilt.
    The rows that need a rebuild are compacted, in entry order, into
    chunks of up to 128 lanes, and each chunk runs one lock-step chain
    walk with a per-row write cursor: live keys land at their cursor
    column, marked tombstones and in-chain SubHeads are stepped over
    without writing (matching ``chain_keys`` / the serial traversal's
    view). A chunk's finished rows are scattered back once. Rows never
    interact in the walk, so each row comes out as a walk over all M
    entries would leave it. Cost scales with the dirty rows, not with M:
    one chunk in the usual round, none in a round with nothing dirty,
    each bounded by its longest chain (early exit) — and amortized: a row
    rebuilt once serves every subsequent round until a writer dirties it.
    """
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
    n_need = jnp.sum(need, dtype=jnp.int32)

    lanes = min(_LANES, m)
    n_pad = -(-m // lanes) * lanes
    # needing rows first, in entry order; m pads (dropped by the scatters)
    todo = jnp.nonzero(need, size=n_pad, fill_value=m)[0].astype(jnp.int32)
    st_ref = refs.unmarked(reg.subtail)
    cols = jnp.arange(c, dtype=jnp.int32)
    # chain steps, not live keys: tombstones stretch the walk past C
    bound = int(cfg.max_scan)

    def w_cond(carry):
        i, keys, idxs, col, cur, collecting, good, st = carry
        return (i < bound) & jnp.any(collecting)

    def w_body(carry):
        i, keys, idxs, col, cur, collecting, good, st = carry
        ci = jnp.clip(refs.ref_idx(cur).astype(jnp.int32), 0, n - 1)
        local = refs.ref_sid(cur) == me
        word = pool.nxt[ci]
        marked = refs.ref_mark(word)
        moving = ~refs.is_null(pool.newloc[ci])
        switched = state.stct[jnp.clip(pool.ctr[ci], 0,
                                       state.stct.shape[0] - 1)] < 0
        k = pool.key[ci]
        at_st = k == ST_KEY
        # the terminating ST must be the *registered* subtail, unmarked —
        # a mid-Split ST (or a merge-neutralized one) fails the identity
        # check and the row stays invalid until the registry catches up
        reach_ok = at_st & (~marked) & (refs.unmarked(cur) == st)
        # marked non-ST nodes and in-chain SubHeads are logically absent:
        # step over them, exactly as chain_keys / the serial walk do
        hop = (k == SH_KEY) | (marked & ~at_st)
        want_write = (~at_st) & (~hop)
        bad = (~local) | refs.is_null(cur) | moving | switched \
            | (at_st & ~reach_ok) | (want_write & (col >= c))
        write = collecting & (~bad) & want_write

        # each writing lane sets its cursor column: a dense select, as a
        # TPU scatter into the [lanes, C] buffer costs ~8 us a step
        at = cols[None, :] == jnp.where(write, col, c)[:, None]
        keys = jnp.where(at, k[:, None], keys)
        idxs = jnp.where(at, ci[:, None], idxs)
        good = good | (collecting & reach_ok)
        collecting = collecting & (~bad) & (~reach_ok)
        col = col + write.astype(jnp.int32)
        cur = jnp.where(collecting, word, cur)
        return i + 1, keys, idxs, col, cur, collecting, good, st

    def c_cond(carry):
        j = carry[0]
        return j * lanes < n_need

    def c_body(carry):
        j, keys, idxs, good = carry
        rows = jax.lax.dynamic_slice(todo, (j * lanes,), (lanes,))
        r = jnp.clip(rows, 0, m - 1)
        init = (jnp.zeros((), jnp.int32),
                jnp.full((lanes, c), ST_KEY, keys.dtype),
                jnp.zeros((lanes, c), idxs.dtype),
                jnp.zeros((lanes,), jnp.int32), pool.nxt[head_idx[r]],
                rows < m, jnp.zeros((lanes,), bool), st_ref[r])
        _, k_ch, i_ch, _, _, _, g_ch, _ = jax.lax.while_loop(
            w_cond, w_body, init)
        return (j + 1, keys.at[rows].set(k_ch, mode="drop"),
                idxs.at[rows].set(i_ch, mode="drop"),
                good.at[rows].set(g_ch, mode="drop"))

    _, keys, idxs, good = jax.lax.while_loop(
        c_cond, c_body, (jnp.zeros((), jnp.int32), blk.keys, blk.idx,
                         jnp.zeros((m,), bool)))
    # rows still collecting at the bound never reached their subtail (or
    # overflowed C live keys): not good.
    valid = (blk.valid | good) & live
    return state._replace(blk=Blocks(keys=keys, idx=idxs, valid=valid)), \
        n_need


def probe_blocks(state: ShardState, entry, sh_ref, q, me, cfg: DiLiConfig):
    """Answer probe lanes from valid packed blocks via the Pallas kernel.

    ``entry`` is each lane's resolved registry entry (``Route.entry``),
    ``sh_ref`` its routed subhead Ref, ``q`` its key. Returns
    ``(usable, present, left, right)`` with ``left``/``right`` pool
    indices forming the same Harris window ``probe_batch`` would return:
    ``right`` is the first live node with key >= q (the entry's SubTail
    when q exceeds every block key — including the fixed pos == C
    full-block edge) and ``left`` its predecessor (the SubHead for
    pos == 0, which callers re-screen exactly as for probe_batch lanes).
    Lanes that are not ``usable`` (no entry, dirty block, hint pointing
    away from the registered subhead, sentinel key) carry no information
    — bounce them.
    """
    reg = state.registry
    blk = state.blk
    pool = state.pool
    m, c = blk.keys.shape
    n = pool.key.shape[0]

    e = jnp.clip(entry, 0, m - 1)
    usable = (entry >= 0) & blk.valid[e] & \
        (refs.unmarked(sh_ref) == refs.unmarked(reg.subhead[e])) & \
        (q > SH_KEY) & (q < ST_KEY)

    slot, found = K.hybrid_search(reg.keymin, blk.keys, q)
    # decode against OUR entry, never slot // C: a full block with every
    # key < q answers pos == C, where slot aliases (entry+1)*C
    pos = slot - e * c
    usable = usable & (pos >= 0) & (pos <= c)

    posc = jnp.clip(pos, 0, c - 1)
    past = (pos >= c) | (blk.keys[e, posc] == ST_KEY)
    st_idx = jnp.clip(refs.ref_idx(reg.subtail[e]).astype(jnp.int32),
                      0, n - 1)
    right = jnp.where(past, st_idx, blk.idx[e, posc])
    hd = jnp.clip(refs.ref_idx(reg.subhead[e]).astype(jnp.int32), 0, n - 1)
    left = jnp.where(pos == 0, hd, blk.idx[e, jnp.clip(pos - 1, 0, c - 1)])
    return usable, found, left, right

"""One shard's round: process inbox, apply client ops, advance the
background slot table (up to ``cfg.bg_slots`` concurrent Split/Move/Merge
ops per shard — DESIGN.md §10).

The round is the unit of linearization (DESIGN.md §2). Handlers are
dispatched per message kind with ``lax.switch`` — a single jit compilation
serves every shard (``me`` is a traced argument).

With ``cfg.find_fastpath`` (DESIGN.md §4) a vectorized pre-pass answers the
round's eligible FIND rows before the serial scan; those rows dispatch to
the no-op branch (their per-op ``while_loop`` pointer chase is skipped) and
their completions are patched in from the pre-pass. Ineligible finds flow
through the serial path untouched. ``cfg.mut_fastpath`` (DESIGN.md §4b) is
the write-side twin: a second pre-pass *applies* the round's eligible
INSERT/REMOVE rows in one scatter sweep against round-start state, so those
rows skip the serial loop too. Both pre-passes classify against the same
round-start state (eligible finds never share a key with any mutation, so
the order between the two pre-passes is immaterial); the serial loop then
runs on the mutated state — safe because eligible mutations commute with
every remaining row.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import batch_apply as BA
from . import bg as B
from . import blocks as BL
from . import messages as M
from . import ops as O
from . import range_scan as RS
from . import refs
from . import registry as REG
from . import replica as R
from .types import DiLiConfig, RES_PENDING, SH_KEY, ShardState


class RoundOut(NamedTuple):
    state: ShardState
    bg: B.BgTable
    outbox: jnp.ndarray      # [cap, FIELDS]
    out_count: jnp.ndarray
    comp_slot: jnp.ndarray   # [K] client slots completed this round (-1 pad)
    comp_val: jnp.ndarray    # [K]
    comp_src: jnp.ndarray    # [K] shard that *executed* each completed op —
                             # != the submission shard means the op was
                             # delegated, i.e. the client's route was stale
                             # (the client API uses this to refresh its
                             # registry cache; DESIGN.md §9)
    comp_key: jnp.ndarray    # [K] SH_KEY for scalar completions; a real
                             # key marks the row as one RANGE item
                             # (comp_val is then the item's value and the
                             # host accumulates it instead of publishing
                             # a result; DESIGN.md §16)
    counters: jnp.ndarray    # int32[len(COUNTERS)] — the round's scalar
                             # counters, packed so the host pulls them
                             # in one transfer
    ent_hits: jnp.ndarray    # int32[M] — ops this round attributed to
                             # each local registry entry (owned-entry
                             # arrivals + replica serves). The host feeds
                             # these into the per-entry op-rate EWMA the
                             # balancer's load model reads.


# The scalar counters of one shard-round, in ``RoundOut.counters`` order.
COUNTERS = (
    "out_count",    # outbox pushes attempted (> mailbox_cap: rows lost)
    "fast_hits",    # finds answered by the fast-path
    "mut_hits",     # mutations applied by the fast-path
    "move_hits",    # MoveItems replayed by the batched scatter splice
                    # (vs the serial walk)
    "blk_hits",     # fast-path lanes whose stage-2 probe was the
                    # packed-block hybrid-search kernel (subset of
                    # fast_hits + mut_hits; DESIGN.md §12)
    "rep_hits",     # FINDs answered from a replica slot (DESIGN.md §15)
    "range_hits",   # RANGE segments served by the packed-block gather
                    # pre-pass (vs the serial chain walk; DESIGN.md §16)
    "bg_active",    # background slots busy after the round
    "serial_rows",  # rows the serial loop executed
)
CTR = {name: i for i, name in enumerate(COUNTERS)}
_SUMMED = ("fast_hits", "mut_hits", "move_hits", "blk_hits", "rep_hits",
           "range_hits", "serial_rows")


def add_counters(stats, ctr) -> None:
    """Fold shard-round counters (int[S, >= len(COUNTERS)], a row per
    shard, ``COUNTERS`` first) into a backend's ``stats``."""
    for name in _SUMMED:
        stats[name] += int(ctr[:, CTR[name]].sum())
    stats["max_bg_active"] = max(stats["max_bg_active"],
                                 int(ctr[:, CTR["bg_active"]].max()))


# handlers return (state, bg, outbox, count, cslot, cval, csrc, ckey);
# ckey is SH_KEY for scalar completions — only MSG_RANGE_ITEM rows carry
# a real key there (DESIGN.md §16).
_NOKEY = SH_KEY


def _handle_op(state, bg, me, row, outbox, count, cfg):
    out = O.apply_op(state, me, row, outbox, count, cfg)
    reply_sid, slot = row[M.F_SID], row[M.F_TS]
    local_done = (out.result != RES_PENDING) & (reply_sid == me) & \
        (row[M.F_A] != 0)
    cslot = jnp.where(local_done, slot, -1)
    cval = jnp.where(local_done, out.result, 0)
    return (out.state, bg, out.outbox, out.count, cslot, cval, me,
            jnp.asarray(_NOKEY, jnp.int32))


def _handle_result(state, bg, me, row, outbox, count, cfg):
    # F_SRC is the shard that executed the op and routed the result home —
    # the corrected route for the op's key.
    return (state, bg, outbox, count, row[M.F_TS], row[M.F_A],
            row[M.F_SRC], jnp.asarray(_NOKEY, jnp.int32))


def _wrap_bg(fn):
    def h(state, bg, me, row, outbox, count, cfg):
        state, bg, outbox, count = fn(state, bg, me, row, outbox, count, cfg)
        neg = jnp.asarray(-1, jnp.int32)
        return (state, bg, outbox, count, neg, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.asarray(_NOKEY, jnp.int32))
    return h


def _noop(state, bg, me, row, outbox, count, cfg):
    neg = jnp.asarray(-1, jnp.int32)
    return (state, bg, outbox, count, neg, jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.asarray(_NOKEY, jnp.int32))


def _handle_epoch(state, bg, me, row, outbox, count, cfg):
    # Monotone merge of the membership announcement (DESIGN.md §13):
    # a newer epoch replaces the peer bitmask wholesale; an equal epoch
    # carries an identical mask (the host is the single writer), so
    # duplicates and cross-lane reorderings are idempotent by max().
    e = row[M.F_KEY]
    take = e > state.epoch
    state = state._replace(
        epoch=jnp.maximum(state.epoch, e),
        peers=jnp.where(take, row[M.F_X1], state.peers))
    neg = jnp.asarray(-1, jnp.int32)
    return (state, bg, outbox, count, neg, jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.asarray(_NOKEY, jnp.int32))


_HANDLERS = {
    M.MSG_OP: _handle_op,
    M.MSG_RESULT: _handle_result,
    M.MSG_REP_INSERT: _wrap_bg(B.h_rep_insert),
    M.MSG_REP_DELETE: _wrap_bg(B.h_rep_delete),
    M.MSG_ACK_INSERT: _wrap_bg(B.h_ack_insert),
    M.MSG_ACK_DELETE: _wrap_bg(B.h_ack_delete),
    M.MSG_MOVE_SH: _wrap_bg(B.h_move_sh),
    M.MSG_MOVE_SH_ACK: _wrap_bg(B.h_move_sh_ack),
    M.MSG_MOVE_ITEM: _wrap_bg(B.h_move_item),
    # batch-run member the replay pre-pass bounced: same field layout, so
    # the serial per-item replay is the universal fallback
    M.MSG_MOVE_ITEMS: _wrap_bg(B.h_move_item),
    M.MSG_MOVE_ACK: _wrap_bg(B.h_move_ack),
    M.MSG_SWITCH_ST: _wrap_bg(B.h_switch_st),
    M.MSG_SWITCH_ST_ACK: _wrap_bg(B.h_switch_st_ack),
    M.MSG_REG_SPLIT: _wrap_bg(B.h_reg_split),
    M.MSG_SWITCH_SERVER: _wrap_bg(B.h_switch_server),
    M.MSG_REG_MERGED: _wrap_bg(B.h_reg_merged),
    M.MSG_EPOCH: _handle_epoch,
    M.MSG_REPLICA_DELTA: _wrap_bg(R.h_replica_delta),
    M.MSG_REPLICA_INSTALL: _wrap_bg(R.h_replica_install),
    M.MSG_REPLICA_DROP: _wrap_bg(R.h_replica_drop),
    M.MSG_RANGE: RS.h_range,
    M.MSG_RANGE_ITEM: RS.h_range_item,
}
_N_KINDS = M.N_KINDS


@partial(jax.jit, static_argnames=("cfg",))
def shard_round(state: ShardState, bg: B.BgTable, me, inbox, client,
                cfg: DiLiConfig) -> RoundOut:
    """``inbox``/``client``: [*, FIELDS] int32 rows, MSG_NONE-padded."""
    me = jnp.asarray(me, jnp.int32)
    rows = jnp.concatenate([inbox, client], axis=0)
    n_rows = rows.shape[0]
    outbox, count = M.empty_outbox(cfg.mailbox_cap)

    # rebuild dirty packed blocks against round-start state, BEFORE any
    # mutation — a block validated here mirrors exactly the state both
    # pre-passes classify against (DESIGN.md §12). Replication also needs
    # the mirror: replica_step publishes blk rows as session images
    # (§15), so a replicating shard refreshes even with the probe off.
    # With both off, the mirror stays all-invalid and costs nothing.
    with jax.named_scope("round.blocks"):
        if cfg.block_probe or cfg.replication or cfg.range_scan:
            state = BL.refresh_blocks(state, me, cfg)

    # RANGE gather pre-pass (DESIGN.md §16): serve scan cursors whose
    # covering entry has a valid packed block, against the same
    # round-start snapshot the blocks mirror — before anything mutates.
    # Unserved cursors fall through to the serial h_range walk.
    with jax.named_scope("round.range"):
        if cfg.range_scan:
            outbox, count, range_handled, range_hits = RS.range_prepass(
                state, rows, me, outbox, count, cfg)
        else:
            range_handled = jnp.zeros((n_rows,), bool)
            range_hits = jnp.zeros((), jnp.int32)

    # one combined pre-pass: answers eligible FINDs from round-start state
    # and applies eligible INSERT/REMOVEs against it (eligible finds never
    # share a key with a mutation, so their relative order is immaterial),
    # sharing a single route-resolve + bounded gather-walk.
    with jax.named_scope("round.prepass"):
        pre = BA.round_prepass(state, rows, me, cfg,
                               run_find=cfg.find_fastpath,
                               run_mut=cfg.mut_fastpath)
        state = pre.state

    # migration rounds get their own pre-pass (mutually exclusive with the
    # client one — any move row makes the round non-benign for §4/§4b):
    # chain-contiguous MSG_MOVE_ITEMS runs are replayed in one scatter
    # splice and their MOVE_ACKs pushed ahead of the serial rows'
    # messages. Acks interact with the source only through per-slot
    # counters and newLoc writes, so their position among the round's
    # other outbox rows is not semantically ordered (DESIGN.md §10).
    with jax.named_scope("round.replay"):
        mrp = B.replay_prepass(state, rows, me, outbox, count, cfg)
        state, outbox, count = mrp.state, mrp.outbox, mrp.count

    # replica read pre-pass (DESIGN.md §15): fresh local FINDs whose key
    # lands in a serving replica slot are answered from the packed image
    # and skip the serial loop. Compiled out unless cfg.replication.
    with jax.named_scope("round.replica"):
        if cfg.replication:
            rep_elig, rep_res = R.replica_serve(state, rows, me, cfg)
            rep_elig = (rep_elig & ~pre.find_elig & ~pre.mut_elig
                        & ~mrp.handled)
        else:
            rep_elig = jnp.zeros((n_rows,), bool)
            rep_res = jnp.zeros((n_rows,), jnp.int32)

    with jax.named_scope("round.order"):
        # Stable-partition the rows the serial pass must execute to the front,
        # so it runs a *dynamic* trip count: padding costs nothing (rounds are
        # usually mostly MSG_NONE), and fast-path-answered rows never enter
        # the loop at all — fast finds neither mutate state nor emit messages,
        # and fast mutations commute with every remaining row and emit nothing
        # either, so removing them leaves the remaining rows' serial order (and
        # with it per-(src,dst) FIFO) intact. The composite key skip*n + i is
        # unique, so the sort is order-preserving on the kept rows.
        skip = (rows[:, M.F_KIND] == M.MSG_NONE) | pre.find_elig \
            | pre.mut_elig | mrp.handled | rep_elig | range_handled
        # blanket packed-block invalidation trigger (DESIGN.md §12): any row
        # the serial loop will execute, other than pure result routing and
        # transport acks, may mutate a chain or shift the registry's entry
        # indexing — per-entry attribution is done where the writer knows the
        # entry (fast-path apply, bg phase hooks); everything else drops the
        # whole mirror below.
        kind0 = rows[:, M.F_KIND]
        # replica rows rewrite only the rslots tables — never a chain, never
        # the registry — so they don't trigger the blanket block drop.
        # RANGE rows are pure reads (serial h_range walks without delinking),
        # so they don't either.
        serial_mut = jnp.any((~skip) & (kind0 != M.MSG_NONE)
                             & (kind0 != M.MSG_RESULT)
                             & (kind0 != M.MSG_NET_ACK)
                             & (kind0 != M.MSG_EPOCH)
                             & (kind0 != M.MSG_REPLICA_DELTA)
                             & (kind0 != M.MSG_REPLICA_INSTALL)
                             & (kind0 != M.MSG_REPLICA_DROP)
                             & (kind0 != M.MSG_RANGE)
                             & (kind0 != M.MSG_RANGE_ITEM))

        # per-entry op attribution (pre-reorder): an MSG_OP row counts at the
        # shard that will answer it — owned-entry arrivals here, or a replica
        # serve here; delegated-away rows count on arrival at their owner.
        m_ent = state.registry.keymin.shape[0]
        ent = REG.get_by_key(state.registry, rows[:, M.F_KEY])
        entc = jnp.clip(ent, 0, m_ent - 1)
        owned_ent = (ent >= 0) & \
            (refs.ref_sid(state.registry.subhead[entc]) == me)
        count_here = (kind0 == M.MSG_OP) & (owned_ent | rep_elig)
        ent_hits = jnp.zeros((m_ent,), jnp.int32).at[
            jnp.where(count_here, entc, m_ent)].add(1, mode="drop")

        order = jnp.argsort(skip.astype(jnp.int32) * n_rows
                            + jnp.arange(n_rows, dtype=jnp.int32))
        rows = rows[order]
        elig = pre.find_elig[order]
        melig = pre.mut_elig[order]
        relig = rep_elig[order]
        res_all = jnp.where(rep_elig, rep_res, pre.res)
        n_live = jnp.sum(~skip)

        branches = []
        for kind in range(_N_KINDS):
            fn = _HANDLERS.get(kind, _noop)

            def mk(f):
                def br(args):
                    st, b, row, ob, ct = args
                    return f(st, b, me, row, ob, ct, cfg)
                return br

            branches.append(mk(fn))

        def cond(c):
            return c[0] < n_live

        def body(c):
            i, st, b, ob, ct, cslots, cvals, csrcs, ckeys = c
            row = rows[i]
            kind = jnp.clip(row[M.F_KIND], 0, _N_KINDS - 1)
            st, b, ob, ct, cs, cv, cr, ck = jax.lax.switch(
                kind, branches, (st, b, row, ob, ct))
            return (i + 1, st, b, ob, ct,
                    cslots.at[i].set(cs), cvals.at[i].set(cv),
                    csrcs.at[i].set(cr), ckeys.at[i].set(ck))

        # completions start pre-filled with the pre-pass answers (those rows
        # sit past n_live); the serial loop overwrites its own rows' slots.
        # Pre-pass rows are local clients answered here, so their src is ``me``.
        init = (jnp.zeros((), jnp.int32), state, bg, outbox, count,
                jnp.where(elig | melig | relig,
                          rows[:, M.F_TS], -1).astype(jnp.int32),
                jnp.where(elig | melig | relig,
                          res_all[order], 0).astype(jnp.int32),
                jnp.full((n_rows,), me, jnp.int32),
                jnp.full((n_rows,), SH_KEY, jnp.int32))
    with jax.named_scope("round.serial"):
        (_, state, bg, outbox, count, cslots, cvals, csrcs,
         ckeys) = jax.lax.while_loop(cond, body, init)

    with jax.named_scope("round.bg"):
        bg_busy = jnp.any(bg.phase != B.BG_IDLE)
        state, bg, outbox, count = B.bg_step(state, bg, me, outbox, count,
                                             cfg)
        bg_busy = bg_busy | jnp.any(bg.phase != B.BG_IDLE)

    # publication engine (DESIGN.md §15): runs after the serial loop and
    # bg step so a fresh image walk already sees this round's mutations —
    # a change at the primary is on the wire the same round it happened.
    with jax.named_scope("round.replica"):
        if cfg.replication:
            traffic = jnp.any(kind0 != M.MSG_NONE)
            mutated = serial_mut | jnp.any(pre.mut_elig) | bg_busy
            state, outbox, count = R.replica_step(
                state, me, mutated, traffic, outbox, count, cfg)

    with jax.named_scope("round.finish"):
        # blanket invalidation: serial mutating rows, any bg slot active
        # around bg_step, or a replayed move splice — a stale valid bit here
        # would let next round's block probe answer from a chain that changed.
        dirty_all = serial_mut | bg_busy | jnp.any(mrp.handled)
        state = state._replace(blk=state.blk._replace(
            valid=jnp.where(dirty_all, jnp.zeros_like(state.blk.valid),
                            state.blk.valid)))
        counters = dict(
            out_count=count,
            fast_hits=jnp.sum(pre.find_elig),
            mut_hits=jnp.sum(pre.mut_elig),
            move_hits=jnp.sum(mrp.handled),
            blk_hits=pre.blk_hits,
            rep_hits=jnp.sum(rep_elig),
            range_hits=range_hits,
            bg_active=jnp.sum(bg.phase != B.BG_IDLE),
            serial_rows=n_live)
        return RoundOut(state=state, bg=bg, outbox=outbox, out_count=count,
                        comp_slot=cslots, comp_val=cvals, comp_src=csrcs,
                        comp_key=ckeys,
                        counters=jnp.stack([counters[n] for n in COUNTERS])
                        .astype(jnp.int32),
                        ent_hits=ent_hits)

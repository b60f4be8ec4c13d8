"""Cluster simulator: N shards, reliable FIFO routing, round-based execution.

This is the single-host execution backend for the DiLi runtime. Each round:

  1. every shard consumes its inbox + a batch of fresh client ops
     (``shard.shard_round`` — one jit compilation reused by all shards),
  2. outboxes are routed host-side into next-round inboxes (per-(src,dst)
     FIFO preserved; undeliverable overflow is backlogged, never dropped —
     the reliable-channel condition of conditional lock-freedom).

With ``delay_prob > 0`` (deterministic under ``seed``) whole (src,dst)
channels are held back for a round to exercise out-of-order-across-pairs
delivery (replay retries must heal).

With ``nemesis=NemesisConfig(...)`` the cluster routes through the
reliable transport (``core.net``, DESIGN.md §11): the wire below it may
drop, duplicate, reorder and delay frames, and the transport's
seq/ack/dedup machinery restores exactly-once in-order delivery. Every
random stream (channel delays, nemesis, balancer tie-breaks) is spawned
from one root ``SeedSequence``, so an entire run — including its
per-round ``round_trace`` — is a pure function of ``(seed, config)``.

The shard_map/TPU backend with ``all_to_all`` routing lives in
``distributed.py``; it runs the same ``shard_round``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from . import bg as B
from . import messages as M
from . import range_scan as RS
from . import refs
from . import replica as R
from .durability import Durability, wal
from .membership import (Membership, epoch_broadcast, moves_targeting,
                         owned_entry_count)
from .net import Nemesis, NemesisConfig, Transport, trace_entry
from .shard import CTR, add_counters, shard_round, unpack_harvest
from .spans import span
from .types import (DiLiConfig, KEY_MAX, KEY_MIN, OP_FIND, OP_INSERT,
                    OP_REMOVE, SH_KEY, ST_KEY, ShardState, init_shard)


class OutboxOverflow(RuntimeError):
    """A shard emitted more messages in one round than ``mailbox_cap``.

    Overflowing rows are not stored (``messages.push``), and a lost
    replicate/ack deadlocks ``run_until_quiet`` — so this is raised
    unconditionally (never an ``assert``: ``python -O`` must not turn it
    into silent truncation). Fix: raise ``cfg.mailbox_cap`` or feed the
    shard fewer ops per round.
    """


# ------------------------------------------------------ client-op plumbing
# Shared by every execution backend (Cluster below, api.ShardMapBackend) so
# the MSG_OP row layout and the op-id lifecycle have exactly one home —
# divergence here is precisely what the Local-vs-ShardMap parity test
# guards against.

class OpIdAllocator:
    """Op ids for the int32 ``F_TS`` message lane, with recycling.

    ``alloc`` reissues released ids first and raises before the int32
    ceiling — a wrapped id would silently alias a live op.
    """

    def __init__(self):
        self.next_id = 0
        self.free: List[int] = []

    def alloc(self) -> int:
        if self.free:
            return self.free.pop()
        if self.next_id >= np.iinfo(np.int32).max:
            raise RuntimeError(
                "op-id space exhausted: op ids are int32 message lanes and "
                "would wrap — drain results (take_result / backend.step) "
                "so ids recycle")
        nid = self.next_id
        self.next_id += 1
        return nid

    def release(self, op_id: int) -> None:
        self.free.append(op_id)


def materialize_ops(kinds, keys, values):
    """Materialize (once) and length-check a client op batch."""
    kinds = [int(k) for k in kinds]
    keys = [int(k) for k in keys]
    if len(kinds) != len(keys):
        raise ValueError(f"submit: {len(kinds)} kinds vs {len(keys)} keys")
    values = ([0] * len(keys) if values is None
              else [int(v) for v in values])
    if len(values) != len(keys):
        raise ValueError(f"submit: {len(values)} values vs {len(keys)} keys")
    return kinds, keys, values


def make_op_row(shard: int, kind: int, key: int, val: int,
                slot: int) -> np.ndarray:
    """One fresh MSG_OP row addressed at server ``shard`` (null subhead
    hint — the server resolves the route; reply shard = ``shard``)."""
    row = np.zeros((M.FIELDS,), np.int32)
    row[M.F_KIND] = M.MSG_OP
    row[M.F_DST] = shard
    row[M.F_SRC] = shard
    row[M.F_A] = kind
    row[M.F_KEY] = key
    row[M.F_REF1] = np.int64(refs.NULL_REF).astype(np.int32)
    row[M.F_SID] = shard
    row[M.F_TS] = slot
    row[M.F_VAL] = val
    return row


# ------------------------------------------------------- state inspection
# Free functions over (cfg, states) so every execution backend (the
# simulator below, the shard_map backend behind ``api.ShardMapBackend``)
# shares one chain walker and one registry reader.

def chain_keys(cfg: DiLiConfig, states: Sequence[ShardState], s: int,
               head_idx: int, include_meta: bool = False):
    """Walk a chain from a subhead; returns live keys, or (key, idx, value)
    triples with ``include_meta``.

    A healthy chain terminates (SubTail, null, or a foreign ref) within
    ``pool_capacity`` steps — the nodes of one chain are distinct pool
    slots. Exhausting the bound therefore proves a cycle (corruption), and
    raising beats returning a silent prefix: ``all_keys()``-based
    assertions must not pass vacuously on a truncated walk.
    """
    st = states[s]
    nxt = np.asarray(st.pool.nxt)
    key = np.asarray(st.pool.key)
    vals = np.asarray(st.pool.keymax)
    out = []
    ref = int(nxt[head_idx])
    for _ in range(int(cfg.pool_capacity) + 2):
        idx = ref & refs.IDX_MASK
        sid = (ref & refs.SID_MASK) >> refs.IDX_BITS
        if idx == refs.NULL_IDX or sid != s:
            break
        k = int(key[idx])
        marked = bool(int(nxt[idx]) & refs.MARK_BIT)
        if k == ST_KEY:
            break
        if k != SH_KEY and not marked:
            out.append((k, idx, int(vals[idx])) if include_meta else k)
        ref = int(nxt[idx])
    else:
        raise RuntimeError(
            f"shard {s} chain from head {head_idx} did not terminate "
            f"within pool_capacity={int(cfg.pool_capacity)} steps "
            f"— cyclic or corrupted chain")
    return out


def chain_sizes(cfg: DiLiConfig, states: Sequence[ShardState], s: int,
                heads: np.ndarray) -> np.ndarray:
    """``len(chain_keys(cfg, states, s, h))`` for every subhead ``h`` in
    ``heads``, all chains walked in lock step — one vectorized step per
    chain position instead of one Python step per node, which is what lets
    the balancer inspect a store of a million keys every pass."""
    st = states[s]
    nxt = np.asarray(st.pool.nxt).astype(np.int64)
    key = np.asarray(st.pool.key)
    size = np.zeros(len(heads), np.int64)
    lane = np.arange(len(heads))            # chains still being walked
    ref = nxt[np.asarray(heads, np.int64)]
    for _ in range(int(cfg.pool_capacity) + 2):
        idx = ref & refs.IDX_MASK
        sid = (ref & refs.SID_MASK) >> refs.IDX_BITS
        go = (idx != refs.NULL_IDX) & (sid == s)
        k = key[np.where(go, idx, 0)]
        go &= k != ST_KEY
        lane, idx, k = lane[go], idx[go], k[go]
        if not len(lane):
            return size
        ref = nxt[idx]
        size[lane] += (k != SH_KEY) & ((ref & refs.MARK_BIT) == 0)
    raise RuntimeError(
        f"shard {s}: a chain did not terminate within "
        f"pool_capacity={int(cfg.pool_capacity)} steps "
        f"— cyclic or corrupted chain")


def state_sublists(cfg: DiLiConfig, states: Sequence[ShardState], s: int):
    """(keymin, keymax, owner, size, head_idx, switched) per entry of
    shard s's registry replica; ``size`` is None for entries owned
    elsewhere. ``switched`` flags an owned entry whose sublist has been
    switched away (stCt < 0) — a stale local copy awaiting quarantine."""
    st = states[s]
    reg = st.registry
    n = int(reg.size)
    sh = np.asarray(reg.subhead)[:n].astype(np.int64)
    sid = (sh & refs.SID_MASK) >> refs.IDX_BITS
    head = sh & refs.IDX_MASK
    own = sid == s
    size = np.zeros(n, np.int64)
    size[own] = chain_sizes(cfg, states, s, head[own])
    switched = np.zeros(n, bool)
    switched[own] = np.asarray(st.stct)[np.asarray(st.pool.ctr)[head[own]]] < 0
    kmin = np.asarray(reg.keymin)[:n].tolist()
    kmax = np.asarray(reg.keymax)[:n].tolist()
    return [dict(keymin=kmin[e], keymax=kmax[e], owner=int(sid[e]),
                 size=int(size[e]) if own[e] else None,
                 head_idx=int(head[e]), switched=bool(switched[e]))
            for e in range(n)]


def global_keys(cfg: DiLiConfig, states: Sequence[ShardState]) -> List[int]:
    """Global key set: union over every shard's owned, non-switched
    sublists (one registry walk, shared with ``state_sublists``)."""
    keys: List[int] = []
    for s in range(len(states)):
        for e in state_sublists(cfg, states, s):
            if e["owner"] != s or e["switched"]:
                continue
            keys.extend(chain_keys(cfg, states, s, e["head_idx"]))
    return sorted(keys)


def registry_entries(reg):
    """One shard's registry replica (``ShardState.registry``) as (keymin,
    keymax, owner) triples, sorted by keymin — the view a client
    seeds/refreshes its route cache from (DESIGN.md §9)."""
    size = int(reg.size)
    kmin = np.asarray(reg.keymin)[:size]
    kmax = np.asarray(reg.keymax)[:size]
    sh = np.asarray(reg.subhead)[:size].astype(np.int64)
    owner = (sh & refs.SID_MASK) >> refs.IDX_BITS
    return [(int(a), int(b), int(o)) for a, b, o in zip(kmin, kmax, owner)]


class Cluster:
    def __init__(self, cfg: DiLiConfig, *, seed: int = 0,
                 delay_prob: float = 0.0,
                 nemesis: Optional[NemesisConfig] = None,
                 retransmit_after: int = 4, net_window: int = 4096,
                 trace: Optional[bool] = None,
                 key_lo: int = KEY_MIN, key_hi: int = KEY_MAX,
                 initial_shards: Optional[int] = None,
                 durability=None):
        self.cfg = cfg
        self.n = cfg.num_shards
        # elastic membership (DESIGN.md §13): cfg.num_shards is the
        # jit-static *capacity*; all capacity shards are constructed and
        # stepped every round, and which of them are members is a
        # host-side overlay. initial_shards=None means all-active (the
        # legacy fixed-membership cluster, byte-identical to before).
        self.membership = Membership(self.n, initial_shards)
        self._mb_logged = 0
        # host->shard control rows (MSG_EPOCH broadcasts) staged between
        # rounds; flushed into the routed message stream in step() so they
        # ride the same (partitionable, retransmitted) wire as everything
        # else.
        self._ctrl_out: List[Tuple[int, np.ndarray]] = []
        # shard 0 bootstraps the full key range; the others hold registry
        # replicas routing to it (the paper's lazily-replicated registry
        # starts synchronized). Initially-retired slots get the replica
        # too — a later join_shard must be able to route from round one.
        peers0 = self.membership.mask()
        self.states: List[ShardState] = [
            init_shard(cfg, s, bootstrap=(s == 0),
                       key_lo=key_lo, key_hi=key_hi, peers_mask=peers0)
            for s in range(self.n)
        ]
        from . import registry as reg_ops
        for s in range(1, self.n):
            st = self.states[s]
            reg = reg_ops.add_entry(
                st.registry, key_lo - 1, key_hi,
                refs.make_ref(0, 0), refs.make_ref(0, 1), 0, 0)
            self.states[s] = st._replace(registry=reg)
        self.bgs: List[B.BgTable] = [B.init_bg_table(cfg)
                                     for _ in range(self.n)]
        self.in_cap = max(cfg.mailbox_cap * self.n, cfg.batch_size * 2)
        self.inboxes = [np.zeros((0, M.FIELDS), np.int32)
                        for _ in range(self.n)]
        self.backlog = [np.zeros((0, M.FIELDS), np.int32)
                        for _ in range(self.n)]
        self.results: Dict[int, int] = {}
        self.result_src: Dict[int, int] = {}
        self.last_completions: List[Tuple[int, int, int]] = []
        self._ids = OpIdAllocator()
        self._pending_ops: Dict[int, Tuple[int, int]] = {}
        # RANGE scans in flight (DESIGN.md §16): item rows accumulate in
        # ``_range_parts`` until the terminal result's count says the set
        # is complete — items from different serving shards ride
        # different transport lanes, so arrival order proves nothing.
        self._range_ops: set = set()
        self._range_parts: Dict[int, List[Tuple[int, int]]] = {}
        self._range_done: Dict[int, Tuple[int, int]] = {}
        self.round_no = 0
        self.delay_prob = delay_prob
        # One splittable root: independent child streams for channel
        # delays, the nemesis, and balancer tie-breaks — adding a consumer
        # to one stream never perturbs another, so the whole run (and its
        # round_trace) is a pure function of (seed, config).
        self.seed = seed
        root = np.random.SeedSequence(seed)
        delay_ss, nemesis_ss, balancer_ss = root.spawn(3)
        self.rng = np.random.default_rng(delay_ss)
        self.balancer_rng = np.random.default_rng(balancer_ss)
        self.nemesis_config = nemesis
        self.net: Optional[Transport] = None
        if nemesis is not None:
            if delay_prob > 0.0:
                # the legacy channel-hold knob is replaced wholesale by
                # transport routing; accepting both would silently run
                # weaker fault injection than asked for
                raise ValueError(
                    "delay_prob and nemesis are mutually exclusive — "
                    "use NemesisConfig.delay_prob for delays under the "
                    "reliable transport")
            self.net = Transport(
                self.n, Nemesis(nemesis, np.random.default_rng(nemesis_ss)),
                retransmit_after=retransmit_after, window=net_window)
        # durability (DESIGN.md §14): per-shard WAL + snapshots. Crash
        # plans require it (recovery needs a durable base), so a run
        # with crashes and no explicit store gets an ephemeral tempdir.
        # ``durability`` accepts a directory path, a Durability, or None.
        self._crash_plans = tuple(nemesis.crashes) if nemesis else ()
        if self._crash_plans:
            from .durability.engine import validate_crash_plans
            validate_crash_plans(self._crash_plans, self.n)
        self._tmp_durability = None
        if durability is None and self._crash_plans:
            import tempfile
            self._tmp_durability = tempfile.TemporaryDirectory(
                prefix="dili-durability-")
            durability = self._tmp_durability.name
        self.durability: Optional[Durability] = None
        if durability is not None:
            self.durability = (durability if isinstance(durability,
                                                        Durability)
                               else Durability(durability, cfg))
            for s in range(self.n):
                self.durability.ensure_genesis(
                    s, self.states[s], self.bgs[s], self.backlog[s],
                    self._lane_image(s))
        # per-round observable-outcome trace, the byte-identical-replay
        # witness. Default: on for nemesis runs (where the (seed, config)
        # repro contract needs it), off on the clean fast path (a per-
        # round string append for nothing).
        self.trace_enabled = (nemesis is not None) if trace is None \
            else bool(trace)
        self.round_trace: List[str] = []
        self.stats = {"max_outbox": 0, "max_hops": 0, "rounds": 0,
                      "fast_hits": 0, "mut_hits": 0, "delegated": 0,
                      "move_hits": 0, "blk_hits": 0, "max_bg_active": 0,
                      "rep_hits": 0, "range_hits": 0, "serial_rows": 0,
                      "blk_rows": 0}
        # per-entry op-rate EWMA (keyed by entry keymax), fed from every
        # round's RoundOut.ent_hits — the load signal the balancer's
        # op-rate model and hot-entry replication stage read (§15). Decays
        # to zero at rest, so key-count calibrated behavior is unchanged
        # for settled clusters.
        self.op_rate_ewma: Dict[int, float] = {}
        # per-shard EWMA of replica-served FINDs (keyed by shard id) — the
        # balancer folds this into shard load so serving replicas don't
        # read as idle (see step()).
        self.rep_rate_ewma: Dict[int, float] = {}
        # host-authoritative replica map (keymax -> (primary, targets)),
        # maintained by the replicate/drop_replica commands; replica_epoch
        # bumps on every change so clients know to refresh routing.
        self._replica_map: Dict[int, Tuple[int, set]] = {}
        self.replica_epoch = 0
        # pre-compile the jitted replicate/drop commands so the first hot
        # entry detected mid-run doesn't pay trace+compile on that round
        R.warm_commands(self.states[0], cfg)

    # ------------------------------------------------------------ client API
    def submit(self, shard: int, kinds: Sequence[int],
               keys: Sequence[int],
               values: Optional[Sequence[int]] = None) -> List[int]:
        """Enqueue fresh client ops at their assigned server ``shard``.

        Returns op ids; results appear in ``self.results`` once linearized.
        ``values`` ride with inserts (item payload, e.g. a KV-page slot).
        ``kinds``/``keys``/``values`` may be any iterables (generators
        included) — they are materialized exactly once up front.

        Op ids travel in an int32 message lane, so they must stay below
        2**31. Ids returned to ``take_result`` are recycled; ids whose
        results linger in ``self.results`` are not — a long-running caller
        that never drains them exhausts the space and ``submit`` raises
        (never silently wraps).
        """
        if not self.membership.is_routable(shard):
            raise ValueError(
                f"submit: shard {shard} is "
                f"{self.membership.state_of(shard)} at epoch "
                f"{self.membership.epoch} — route ops to one of "
                f"{self.membership.routable}")
        kinds, keys, values = materialize_ops(kinds, keys, values)
        ids = []
        rows = []
        for kind, key, val in zip(kinds, keys, values):
            slot = self._ids.alloc()
            rows.append(make_op_row(shard, kind, key, val, slot))
            ids.append(slot)
            self._pending_ops[slot] = (kind, key)
        if rows:
            self.backlog[shard] = np.concatenate(
                [self.backlog[shard], np.stack(rows)], axis=0)
            if self.durability is not None:
                # journal on acceptance: an op whose id was handed out
                # must survive a crash of its server (DESIGN.md §14)
                self.durability.log_submit(shard, self.round_no,
                                           np.stack(rows))
        return ids

    def submit_range(self, shard: int, lo: int, hi: int,
                     limit: int) -> int:
        """Enqueue a RANGE(lo, hi, limit) scan at server ``shard``
        (DESIGN.md §16): all keys in ``[lo, hi)``, at most ``limit`` of
        them. Returns an op id; the result value is the item count and
        ``take_range_items`` pops the (key, value) pairs — call it
        *before* ``take_result`` recycles the id."""
        if not self.cfg.range_scan:
            raise ValueError(
                "submit_range: cfg.range_scan is off — the RANGE "
                "pre-pass and serial walk are compiled out of "
                "shard_round")
        if not self.membership.is_routable(shard):
            raise ValueError(
                f"submit_range: shard {shard} is "
                f"{self.membership.state_of(shard)} at epoch "
                f"{self.membership.epoch} — route ops to one of "
                f"{self.membership.routable}")
        lo, hi, limit = int(lo), int(hi), int(limit)
        if lo < KEY_MIN or hi > KEY_MAX + 1 or limit < 1:
            raise ValueError(
                f"submit_range: span [{lo}, {hi}) / limit {limit} out "
                f"of bounds (keys in [{KEY_MIN}, {KEY_MAX}], "
                f"limit >= 1)")
        slot = self._ids.alloc()
        row = RS.make_range_row(shard, lo, hi, limit, slot)
        self.backlog[shard] = np.concatenate(
            [self.backlog[shard], row[None]], axis=0)
        if self.durability is not None:
            self.durability.log_submit(shard, self.round_no, row[None])
        self._pending_ops[slot] = (-1, lo)
        self._range_ops.add(slot)
        self._range_parts[slot] = []
        return slot

    def take_range_items(self, op_id: int) -> List[Tuple[int, int]]:
        """Pop a completed RANGE's (key, value) pairs, sorted by key."""
        return sorted(self._range_parts.pop(op_id, []))

    def take_result(self, op_id: int) -> int:
        """Pop a completed op's result and recycle its id.

        Raises ``KeyError`` while the op is still pending. This is the
        drain path long-running clients must use: ids handed back here are
        reissued by ``submit`` instead of growing the id space toward the
        int32 wraparound guard.
        """
        val = self.results.pop(op_id)
        self.result_src.pop(op_id, None)
        # a recycled id must not inherit a stale scan's items
        self._range_parts.pop(op_id, None)
        self._range_ops.discard(op_id)
        self._ids.release(op_id)
        return val

    # ------------------------------------------------- membership (§13)
    def join_shard(self, shard: Optional[int] = None) -> int:
        """Admit a retired capacity slot as a JOINING member (empty — the
        balancer's rebalancing drains sublists onto it; the host promotes
        it to ACTIVE once it owns one). Returns the joined shard id."""
        s = self.membership.begin_join(shard)
        self._broadcast_epoch()
        return s

    def retire_shard(self, shard: int) -> None:
        """Begin draining ``shard``: the balancer force-evacuates every
        sublist it owns, it keeps executing (delegations in flight must
        land), and the host retires it — resetting its transport lanes —
        once ``_drain_complete`` proves nothing can still reach it."""
        self.membership.begin_drain(shard)
        self._broadcast_epoch()

    def _broadcast_epoch(self) -> None:
        """Stage a MSG_EPOCH announcement to every capacity slot, from the
        lowest *active* shard — never from a draining one, whose own
        retirement is gated on its lanes going idle (a self-announcement
        would deadlock that gate)."""
        rows = epoch_broadcast(self.membership)
        src = int(min(self.membership.active))
        self._ctrl_out.append((src, np.stack(rows).astype(np.int32)))

    def _drain_complete(self, s: int) -> bool:
        """True when retiring ``s`` can strand nothing: it owns no
        sublist, runs no bg op, no peer's in-flight Move targets it, no
        queued/staged row can still be delivered to it, and every
        transport lane touching it is idle (incl. nemesis-held frames)."""
        if owned_entry_count(self.cfg, self.states, s) != 0:
            return False
        if B.any_active(self.bgs[s]):
            return False
        if moves_targeting(self.bgs, s) != 0:
            return False
        if self.backlog[s].shape[0]:
            return False
        if self._ctrl_out:
            return False
        if self.net is not None and not self.net.shard_idle(s):
            return False
        return True

    def _membership_maintenance(self) -> None:
        """Host-driven lifecycle advance, once per round (deterministic:
        a pure function of post-round state). Promotes joining shards
        that own their first sublist; retires draining shards whose drain
        is provably complete, resetting their lanes before announcing."""
        mb = self.membership
        if not (mb.joining or mb.draining):
            return
        changed = False
        for s in mb.joining:
            if owned_entry_count(self.cfg, self.states, s) > 0:
                mb.promote(s)
                changed = True
        for s in mb.draining:
            if self._drain_complete(s):
                mb.finish_drain(s)
                if self.net is not None:
                    self.net.reset_shard(s)
                changed = True
        if changed:
            self._broadcast_epoch()

    # ------------------------------------------------- crash-restart (§14)
    def _lane_image(self, s: int) -> Dict[str, np.ndarray]:
        return (self.net.export_shard_lanes(s)
                if self.net is not None else {})

    def _down(self):
        return self.net.down if self.net is not None else ()

    def _apply_crash_plans(self) -> None:
        """Execute due CrashPlans at the top of the round. Restarts run
        before crashes so a plan pair sharing a round boundary recovers
        one shard while killing another deterministically."""
        for c in self._crash_plans:
            if c.restart_round == self.round_no and c.shard in self._down():
                self._restart_shard(c.shard)
        for c in self._crash_plans:
            if c.crash_round == self.round_no:
                self._crash_shard(c.shard)

    def _crash_shard(self, s: int) -> None:
        """kill -9: the process's memory — shard state, BgTable, host
        backlog, its halves of every transport lane — vanishes. Durable
        WAL + snapshots (and everything client-side: results, pending op
        ids) survive."""
        self.membership.crash(s)
        if not self.membership.active:
            raise RuntimeError(
                f"crash of shard {s} leaves no active shard — the "
                f"coordinator for epoch broadcasts must survive")
        self._broadcast_epoch()
        self.states[s] = init_shard(self.cfg, s, peers_mask=0)
        self.bgs[s] = B.init_bg_table(self.cfg)
        self.backlog[s] = np.zeros((0, M.FIELDS), np.int32)
        self.net.crash_shard(s)

    def _restart_shard(self, s: int) -> None:
        """Recovery: snapshot + WAL replay rebuilds the shard at its last
        durable round; the lane image re-arms its retransmit rings and
        receiver cursors, so exactly-once delivery spans the reboot. The
        shard re-enters as JOINING-with-state (crash ≠ drain) — host
        maintenance promotes it back to ACTIVE since it still owns its
        pre-crash sublists, and carve-out / delegation healing repairs
        anything that restructured while it was down."""
        rec = self.durability.recover(s, in_cap=self.in_cap)
        self.states[s] = rec.state
        self.bgs[s] = rec.bg
        self.backlog[s] = rec.backlog
        self.net.restart_shard(s, rec.lanes)
        self.membership.restart(s)
        self._broadcast_epoch()
        # fresh durable base: the replayed suffix is now redundant
        self.durability.snapshot_now(s, self.round_no - 1, self.states[s],
                                     self.bgs[s], self.backlog[s],
                                     self._lane_image(s))

    # ------------------------------------------------------------- execution
    def step(self) -> int:
        """One synchronized round across all shards. Returns #completed."""
        cfg = self.cfg
        with span("cluster.launch"):
            self._apply_crash_plans()
            down = self._down()
            outs = []
            client_feeds: List[np.ndarray] = []
            for s in range(self.n):
                if s in down:
                    outs.append(None)
                    client_feeds.append(np.zeros((0, M.FIELDS), np.int32))
                    continue
                # feed: backlog first (FIFO), bounded by in_cap
                feed = self.backlog[s][:self.in_cap]
                self.backlog[s] = self.backlog[s][self.in_cap:]
                inbox = np.zeros((self.in_cap, M.FIELDS), np.int32)
                inbox[:feed.shape[0]] = feed
                client = np.zeros((0, M.FIELDS), np.int32)
                client_feeds.append(client)
                out = shard_round(self.states[s], self.bgs[s], s,
                                  jnp.asarray(inbox),
                                  jnp.asarray(client.reshape(0, M.FIELDS)),
                                  cfg)
                # the harvest reads this one vector: start its copy now, so
                # it runs as soon as this shard's program ends
                out.harvest.copy_to_host_async()
                outs.append(out)

        with span("cluster.harvest"):
            ndone = 0
            self.last_completions = []
            new_msgs: List[np.ndarray] = []
            out_counts: List[int] = []
            comp_by_shard: List[np.ndarray] = []
            ent_rates: Dict[int, int] = {}
            rep_served: Dict[int, int] = {}
            for s, out in enumerate(outs):
                if out is None:                  # crashed: emitted nothing
                    out_counts.append(0)
                    comp_by_shard.append(np.zeros((0, 4), np.int32))
                    continue
                self.states[s] = out.state
                self.bgs[s] = out.bg
                h = unpack_harvest(np.asarray(out.harvest), cfg)  # one pull
                ctr = h.counters
                add_counters(self.stats, ctr[None])
                rh = int(ctr[CTR["rep_hits"]])
                if rh:
                    rep_served[s] = rep_served.get(s, 0) + rh
                hits = h.ent_hits
                nz = np.nonzero(hits)[0]
                if nz.size:
                    kmax = h.keymax
                    for e in nz:
                        k = int(kmax[e])
                        if k != ST_KEY:
                            ent_rates[k] = ent_rates.get(k, 0) + int(hits[e])
                cnt = int(ctr[CTR["out_count"]])
                out_counts.append(cnt)
                self.stats["max_outbox"] = max(self.stats["max_outbox"], cnt)
                if cnt > cfg.mailbox_cap:
                    # not an assert: under ``python -O`` a dropped message
                    # (replicate/ack) would silently deadlock
                    # run_until_quiet.
                    raise OutboxOverflow(
                        f"shard {s} emitted {cnt} messages in round "
                        f"{self.round_no}, mailbox_cap={cfg.mailbox_cap}: "
                        f"{cnt - cfg.mailbox_cap} rows dropped — raise "
                        f"mailbox_cap or reduce the per-round feed")
                ob = h.outbox[:cnt]
                if ob.size:
                    new_msgs.append((s, ob))
                    hops = ob[ob[:, M.F_KIND] == M.MSG_OP, M.F_X2]
                    if hops.size:
                        self.stats["max_hops"] = max(self.stats["max_hops"],
                                                     int(hops.max()))
                        self.stats["delegated"] += int(hops.size)
                cs, cv, cr, ck = (h.comp_slot, h.comp_val, h.comp_src,
                                  h.comp_key)
                done = cs >= 0
                comp_by_shard.append(np.stack(
                    [cs[done], cv[done], cr[done], ck[done]],
                    axis=1).astype(np.int32))
                for slot, val, src, key in zip(cs[done], cv[done], cr[done],
                                               ck[done]):
                    slot = int(slot)
                    if int(key) != SH_KEY:
                        # one RANGE item — accumulate, publication waits
                        # for the terminal count (DESIGN.md §16)
                        self._range_parts.setdefault(slot, []).append(
                            (int(key), int(val)))
                        continue
                    if slot in self._range_ops:
                        # terminal scan result: F_A is the total item count
                        self._range_done[slot] = (int(val), int(src))
                        continue
                    self.results[slot] = int(val)
                    self.result_src[slot] = int(src)
                    self.last_completions.append((slot, int(val), int(src)))
                    self._pending_ops.pop(slot, None)
                    ndone += 1
            ndone += self._publish_ranges()

        with span("cluster.rates"):
            # per-entry op-rate EWMA update (once per round): decay every
            # tracked entry, add this round's hits, drop entries decayed to
            # noise so the dict tracks only recently-active sublists.
            alpha = 0.3
            nxt_rates: Dict[int, float] = {}
            for k, v in self.op_rate_ewma.items():
                d = v * (1.0 - alpha)
                if d > 1e-3:
                    nxt_rates[k] = d
            for k, h in ent_rates.items():
                nxt_rates[k] = nxt_rates.get(k, 0.0) + alpha * h
            self.op_rate_ewma = nxt_rates
            # per-shard replica-service EWMA (keyed by shard): FINDs a shard
            # serves from its read replicas are real load but invisible to
            # the registry-keyed entry rates (the entry lives on the
            # primary), so without this the balancer sees serving replicas
            # as idle and churns moves against phantom imbalance.
            nxt_rep: Dict[int, float] = {}
            for s2, v in self.rep_rate_ewma.items():
                d = v * (1.0 - alpha)
                if d > 1e-3:
                    nxt_rep[s2] = d
            for s2, h in rep_served.items():
                nxt_rep[s2] = nxt_rep.get(s2, 0.0) + alpha * h
            self.rep_rate_ewma = nxt_rep

        with span("cluster.route"):
            # host->shard membership announcements join the routed stream
            # here (after the shard outboxes, a deterministic position) so
            # they are partitioned/retransmitted like any protocol message.
            if self._ctrl_out:
                new_msgs.extend(self._ctrl_out)
                self._ctrl_out = []

            pre_lens = [b.shape[0] for b in self.backlog]
            if self.net is not None:
                # reliable transport over the (possibly nemesis-perturbed)
                # wire: loopback rows bypass it, everything else is
                # sequenced, retransmitted and delivered exactly once in
                # per-lane order. Runs even on quiet rounds so retransmit
                # timers, acks and delayed frames keep moving.
                self.net.route_round(self.backlog, new_msgs, self.round_no)
            elif new_msgs:
                allm = np.concatenate([ob for _, ob in new_msgs], axis=0)
                for d in range(self.n):
                    mine = allm[allm[:, M.F_DST] == d]
                    if self.delay_prob > 0.0 and mine.size:
                        # hold back whole (src,dst) channels — preserves pair
                        # FIFO while exercising cross-pair reordering
                        srcs = np.unique(mine[:, M.F_SRC])
                        held = srcs[self.rng.random(srcs.shape)
                                    < self.delay_prob]
                        hold_mask = np.isin(mine[:, M.F_SRC], held)
                        later, now = mine[hold_mask], mine[~hold_mask]
                        self.backlog[d] = np.concatenate(
                            [self.backlog[d], now, later], axis=0)
                    else:
                        self.backlog[d] = np.concatenate(
                            [self.backlog[d], mine], axis=0)
            self._membership_maintenance()
            if self.durability is not None:
                # journal the round per live shard: the inputs consumed (the
                # feed discipline re-derives them from backlog + appends),
                # the completions produced (replay audit), and the post-
                # routing lane image. fsync'd before this round's effects
                # become observable via next round's acks (§14).
                for s in range(self.n):
                    if s in down:
                        continue
                    self.durability.log_round(
                        s, self.round_no,
                        appends=self.backlog[s][pre_lens[s]:],
                        client=client_feeds[s], comp=comp_by_shard[s],
                        bg_phases=B.slot_phases(self.bgs[s]),
                        epoch=int(np.asarray(self.states[s].epoch)),
                        lanes=self._lane_image(s))
                    self.durability.maybe_snapshot(
                        s, self.round_no, self.states[s], self.bgs[s],
                        self.backlog[s], self._lane_image(s))
            if self.trace_enabled:
                # membership transitions are part of the replay witness: a
                # run that joins/retires at a different round is not a replay
                for ep, ev, sh in self.membership.log[self._mb_logged:]:
                    self.round_trace.append(
                        f"r{self.round_no} mb {ev} s{sh} e{ep}")
                self._mb_logged = len(self.membership.log)
                self.round_trace.append(trace_entry(
                    self.round_no, self.last_completions, out_counts,
                    extra=sum(b.shape[0] for b in self.backlog)
                    + (self.net.in_flight() if self.net is not None else 0)))
        self.round_no += 1
        self.stats["rounds"] += 1
        return ndone

    def _publish_ranges(self) -> int:
        """Publish RANGE completions whose item parts have all arrived.
        Items from different serving shards ride different transport
        lanes, so the terminal count — not arrival order — gates
        publication. A negative count is an error result (e.g.
        RES_OVERFLOW) and publishes immediately."""
        n = 0
        for slot, (total, src) in list(self._range_done.items()):
            if total >= 0 and len(self._range_parts.get(slot, ())) < total:
                continue
            self.results[slot] = total
            self.result_src[slot] = src
            self.last_completions.append((slot, total, src))
            self._pending_ops.pop(slot, None)
            del self._range_done[slot]
            n += 1
        return n

    def run(self, rounds: int) -> None:
        for _ in range(rounds):
            self.step()

    def run_until_quiet(self, max_rounds: int = 200) -> None:
        """Step until no messages are in flight and all bg ops are idle."""
        for _ in range(max_rounds):
            self.step()
            busy = any(b.shape[0] for b in self.backlog)
            busy = busy or any(B.any_active(bg) for bg in self.bgs)
            busy = busy or bool(self._pending_ops)
            busy = busy or bool(self._ctrl_out)
            busy = busy or (self.net is not None and not self.net.idle())
            # a crashed shard is not quiet — keep stepping toward its
            # scheduled restart so recovery (and retransmission into it)
            # can finish the run
            busy = busy or bool(self.membership.crashed)
            if not busy:
                return
        raise RuntimeError(
            f"cluster did not quiesce: backlog="
            f"{[b.shape[0] for b in self.backlog]} "
            f"bg={[B.slot_phases(bg).tolist() for bg in self.bgs]} "
            f"pending={len(self._pending_ops)} "
            f"net={self.net.in_flight() if self.net is not None else 0}")

    # ----------------------------------------------------------- inspection
    def shard_chain(self, s: int, head_idx: int, include_meta=False):
        """Walk a chain from a subhead (see ``chain_keys``); raises on a
        cyclic/corrupted chain instead of returning a silent prefix."""
        return chain_keys(self.cfg, self.states, s, head_idx, include_meta)

    def all_keys(self) -> List[int]:
        """Global key set: union over every shard's owned sublists."""
        return global_keys(self.cfg, self.states)

    def sublists(self, s: int):
        """(keymin, keymax, owner, size, head_idx) per entry."""
        return state_sublists(self.cfg, self.states, s)

    def registry_entries(self, s: int = 0):
        """Shard ``s``'s registry replica as (keymin, keymax, owner)."""
        return registry_entries(self.states[s].registry)

    # ---------------------------------------------------------- bg commands
    # Each returns True if a slot accepted the command, False if it was
    # dropped (no idle slot, or the entry is claimed by an in-flight op) —
    # the balancer uses the verdict to keep its load model honest.
    def split(self, s: int, entry_keymax: int, sitem_idx: int) -> bool:
        self.bgs[s], ok = B.queue_split(self.bgs[s], entry_keymax, sitem_idx)
        self._log_command(s, wal.CMD_SPLIT, (entry_keymax, sitem_idx), ok)
        return bool(ok)

    def move(self, s: int, entry_keymax: int, target: int) -> bool:
        self.bgs[s], ok = B.queue_move(self.bgs[s], entry_keymax, target)
        self._log_command(s, wal.CMD_MOVE, (entry_keymax, target), ok)
        return bool(ok)

    def merge(self, s: int, left_keymax: int, right_keymax: int) -> bool:
        self.bgs[s], ok = B.queue_merge(self.bgs[s], left_keymax,
                                        right_keymax)
        self._log_command(s, wal.CMD_MERGE, (left_keymax, right_keymax), ok)
        return bool(ok)

    def replicate(self, s: int, entry_keymax: int, target: int) -> bool:
        """Start (or widen) read replication of the entry ``s`` owns with
        upper bound ``entry_keymax`` onto shard ``target`` (§15). Like the
        bg commands, this is a host-side state edit journaled through the
        WAL so recovery replays it byte-identically."""
        if not self.cfg.replication:
            raise ValueError(
                "replicate: cfg.replication is off — replica serve and "
                "publication are compiled out of shard_round")
        self.states[s], ok = R.queue_replicate_jit(
            self.states[s], self.cfg, entry_keymax, target)
        ok = bool(np.asarray(ok))
        self._log_command(s, wal.CMD_REPLICATE, (entry_keymax, target), ok)
        if ok:
            prim, tg = self._replica_map.get(entry_keymax, (s, set()))
            tg = set(tg) | {int(target)}
            self._replica_map[int(entry_keymax)] = (s, tg)
            self.replica_epoch += 1
        return ok

    def drop_replica(self, s: int, entry_keymax: int,
                     target: int = -1) -> bool:
        """Retire replicas of ``entry_keymax`` on ``target`` (-1 = all)."""
        if not self.cfg.replication:
            raise ValueError("drop_replica: cfg.replication is off")
        self.states[s], ok = R.queue_drop_replica_jit(
            self.states[s], self.cfg, entry_keymax, target)
        ok = bool(np.asarray(ok))
        self._log_command(s, wal.CMD_DROP_REPLICA,
                          (entry_keymax, target), ok)
        if entry_keymax in self._replica_map:
            prim, tg = self._replica_map[entry_keymax]
            tg = set() if target < 0 else set(tg) - {int(target)}
            if tg:
                self._replica_map[entry_keymax] = (prim, tg)
            else:
                del self._replica_map[entry_keymax]
            self.replica_epoch += 1
        return ok

    def replica_sets(self):
        """Live replica routing view for clients: ``{keymax: (keymin,
        primary, [replica shards])}``. Entries whose primary no longer
        owns a matching registry entry are pruned (ownership moved; the
        session's self-audit is dropping those replicas anyway)."""
        out = {}
        stale = []
        for kmax, (prim, tg) in self._replica_map.items():
            reg = self.states[prim].registry
            size = int(np.asarray(reg.size))
            kmaxes = np.asarray(reg.keymax)[:size]
            at = np.nonzero(kmaxes == kmax)[0]
            owned = False
            if at.size:
                sh = int(np.asarray(reg.subhead)[at[0]])
                owned = ((sh & refs.SID_MASK) >> refs.IDX_BITS) == prim
            if not owned:
                stale.append(kmax)
                continue
            kmin = int(np.asarray(reg.keymin)[at[0]])
            out[int(kmax)] = (kmin, int(prim), sorted(tg))
        for kmax in stale:
            del self._replica_map[kmax]
            self.replica_epoch += 1
        return out

    def _log_command(self, s: int, cmd: int, args, ok) -> None:
        """Balancer commands mutate the BgTable outside the inbox, so
        replay needs them journaled (wal.py KIND_COMMAND)."""
        if self.durability is not None:
            self.durability.log_command(s, self.round_no, cmd, args,
                                        bool(ok))

    def middle_item(self, s: int, head_idx: int) -> Optional[int]:
        """Pool idx of the middle live item of a sublist (split point)."""
        items = self.shard_chain(s, head_idx, include_meta=True)
        if len(items) < 2:
            return None
        return items[len(items) // 2][1]

"""Execution backends behind ``DiLiClient`` (DESIGN.md §9).

A backend is one round-based execution engine for the DiLi protocol. The
client is backend-agnostic: the same workload runs unchanged against the
single-host simulator (``LocalBackend`` wrapping ``core.sim.Cluster``) or
the SPMD device mesh (``ShardMapBackend`` wrapping
``core.distributed.make_dili_round``).

The contract (``Backend`` protocol):

  * ``submit(shard, kinds, keys, values)`` enqueues fresh client ops at a
    server and returns op ids;
  * ``step()`` runs one synchronized round and returns the ops completed in
    it as ``(op_id, result, src_shard)`` triples — ``src_shard`` is the
    shard that *executed* the op, the client's route-correction signal.
    Returned op ids are recycled by the backend;
  * ``quiescent()`` — no messages in flight and all background ops idle;
  * ``registry_entries(shard)`` — one shard's (lazily-replicated) registry
    view, which clients seed/refresh their route cache from;
  * the balance surface (``sublists``/``middle_item``/``split``/``move``/
    ``merge`` plus ``states``/``bgs``/``cfg``/``n``) — the same duck type
    ``core.balancer.Balancer`` has always driven, so today's balancer runs
    unmodified as a policy over either backend.
"""
from __future__ import annotations

from collections import deque
from functools import lru_cache
from typing import Dict, List, Optional, Protocol, Sequence, Tuple

import numpy as np

from repro.core import bg as B
from repro.core.durability import wal
from repro.core import messages as M
from repro.core import range_scan as RS
from repro.core import refs
from repro.core import replica as R
from repro.core.distributed import ROUTED_HOPS, ROUTED_LIVE, ROUTED_OPS
from repro.core.membership import (Membership, epoch_row, moves_targeting,
                                   owned_entry_count)
from repro.core.net import trace_entry
from repro.core.shard import CTR, add_counters, unpack_harvest
from repro.core.sim import (Cluster, OpIdAllocator, OutboxOverflow,
                            chain_keys, global_keys, make_op_row,
                            materialize_ops, registry_entries,
                            state_sublists)
from repro.core.spans import span
from repro.core.types import (DiLiConfig, KEY_MAX, KEY_MIN, SH_KEY,
                              ST_KEY)

Completion = Tuple[int, int, int]           # (op_id, result, src_shard)
RegEntry = Tuple[int, int, int]             # (keymin, keymax, owner)


class Backend(Protocol):
    """Round-based DiLi execution engine (see module docstring)."""

    cfg: DiLiConfig
    stats: Dict[str, int]

    @property
    def n(self) -> int: ...

    def submit(self, shard: int, kinds: Sequence[int], keys: Sequence[int],
               values: Optional[Sequence[int]] = None) -> List[int]: ...

    # RANGE scans (DESIGN.md §16): completion carries the item *count*
    # (or a negative RES_* error); the (key, value) pairs are fetched
    # once with ``take_range_items`` after the op completes.
    def submit_range(self, shard: int, lo: int, hi: int,
                     limit: int) -> int: ...

    def take_range_items(self, op_id: int) -> List[Tuple[int, int]]: ...

    def step(self) -> List[Completion]: ...

    def quiescent(self) -> bool: ...

    def registry_entries(self, shard: int = 0) -> List[RegEntry]: ...

    # ------------------------------------------------------ balance surface
    def sublists(self, s: int) -> List[dict]: ...

    def middle_item(self, s: int, head_idx: int) -> Optional[int]: ...

    # each returns True when a background slot accepted the command,
    # False when it was dropped (no idle slot / entry already claimed)
    def split(self, s: int, entry_keymax: int, sitem_idx: int) -> bool: ...

    def move(self, s: int, entry_keymax: int, target: int) -> bool: ...

    def merge(self, s: int, left_keymax: int, right_keymax: int) -> bool: ...

    # -------------------------------------------------- replication (§15)
    # op-rate load signal + hot-entry read replication; ``replica_epoch``
    # bumps whenever the replica map changes so clients know to re-pull
    # ``replica_sets()`` for FIND routing.
    def replicate(self, s: int, entry_keymax: int, target: int) -> bool: ...

    def drop_replica(self, s: int, entry_keymax: int,
                     target: int = -1) -> bool: ...

    def replica_sets(self) -> Dict[int, Tuple[int, int, List[int]]]: ...


@lru_cache(maxsize=None)
def _slot_command(fn, cfg, sharding):
    """A one-shard command ``fn(tree, [cfg,] *args) -> (tree, ok)``
    applied to slot ``s`` of a tree stacked over the mesh, as one jitted
    call: one dispatch, where slicing and updating each leaf took two."""
    import jax

    def run(stacked, s, *args):
        tree_map = jax.tree_util.tree_map
        one = tree_map(lambda col: col[s], stacked)
        one, ok = fn(one, *args) if cfg is None else fn(one, cfg, *args)
        stacked = tree_map(lambda col, leaf: col.at[s].set(leaf), stacked,
                           one)
        return jax.lax.with_sharding_constraint(stacked, sharding), ok

    return jax.jit(run)


@lru_cache(maxsize=None)
def _packer(sharding):
    """One jitted call that lays the leaves of a tree stacked over the
    mesh side by side as int32 ``[S, N]`` (bools widened, 32-bit types
    bitcast), so that a host snapshot is one transfer a shard and not one
    a leaf; ``ShardMapBackend._unstack`` undoes it."""
    import jax
    import jax.numpy as jnp

    def pack(stacked):
        cols = []
        for x in jax.tree_util.tree_leaves(stacked):
            x = x.reshape(x.shape[0], -1)
            cols.append(x.astype(jnp.int32) if x.dtype == jnp.bool_
                        else jax.lax.bitcast_convert_type(x, jnp.int32))
        return jax.lax.with_sharding_constraint(
            jnp.concatenate(cols, axis=1), sharding)

    return jax.jit(pack)


class LocalBackend:
    """The single-host simulator as a client backend.

    Wraps ``core.sim.Cluster`` — which stays the execution machinery (round
    loop, host-side routing, overflow detection) while this class adapts it
    to the backend contract: per-step completion harvesting with executing
    shard, and op-id recycling via ``Cluster.take_result``.
    """

    def __init__(self, cfg: Optional[DiLiConfig] = None, *,
                 cluster: Optional[Cluster] = None, seed: int = 0,
                 delay_prob: float = 0.0, nemesis=None,
                 retransmit_after: int = 4, net_window: int = 4096,
                 key_lo: int = KEY_MIN, key_hi: int = KEY_MAX,
                 initial_shards: Optional[int] = None,
                 trace: Optional[bool] = None, durability=None):
        if cluster is None:
            if cfg is None:
                raise ValueError("LocalBackend needs a DiLiConfig or Cluster")
            cluster = Cluster(cfg, seed=seed, delay_prob=delay_prob,
                              nemesis=nemesis,
                              retransmit_after=retransmit_after,
                              net_window=net_window,
                              key_lo=key_lo, key_hi=key_hi,
                              initial_shards=initial_shards, trace=trace,
                              durability=durability)
        self.cluster = cluster
        self.cfg = cluster.cfg
        self._issued: set = set()
        # RANGE ops issued through this backend; items are captured at
        # harvest time (``Cluster.take_result`` purges the cluster-side
        # parts, so they must be pulled *before* the id is recycled) and
        # held here until the caller fetches them.
        self._range_issued: set = set()
        self._range_items: Dict[int, List[Tuple[int, int]]] = {}

    # ------------------------------------------------------------- protocol
    @property
    def n(self) -> int:
        return self.cluster.n

    @property
    def stats(self) -> Dict[str, int]:
        return self.cluster.stats

    def submit(self, shard, kinds, keys, values=None) -> List[int]:
        ids = self.cluster.submit(shard, kinds, keys, values)
        self._issued.update(ids)
        return ids

    def submit_range(self, shard: int, lo: int, hi: int,
                     limit: int) -> int:
        op_id = self.cluster.submit_range(shard, lo, hi, limit)
        self._issued.add(op_id)
        self._range_issued.add(op_id)
        return op_id

    def take_range_items(self, op_id: int) -> List[Tuple[int, int]]:
        return self._range_items.pop(op_id)

    def step(self) -> List[Completion]:
        """One round; returns and recycles completions of ops issued
        *through this backend*. Ops submitted raw at the wrapped cluster
        keep their results in ``cluster.results`` untouched — draining
        them would orphan the raw caller's poll loop and let its live id
        be reissued to a client op. Harvesting goes through
        ``cluster.results`` (not ``last_completions``, which the next raw
        ``Cluster.step`` overwrites) so tools stepping the cluster
        directly between backend rounds cannot orphan client futures."""
        self.cluster.step()
        with span("cluster.harvest"):
            comps = []
            done = [op_id for op_id in self._issued
                    if op_id in self.cluster.results]
            for op_id in done:
                src = self.cluster.result_src.get(op_id, -1)
                if op_id in self._range_issued:
                    # pull the scan items before take_result purges them
                    self._range_items[op_id] = \
                        self.cluster.take_range_items(op_id)
                    self._range_issued.discard(op_id)
                val = self.cluster.take_result(op_id)  # pops, recycles id
                self._issued.discard(op_id)
                comps.append((op_id, val, src))
        return comps

    @property
    def net(self):
        """The reliable transport, or None when routing is direct."""
        return self.cluster.net

    @property
    def balancer_rng(self):
        """Balancer child stream of the run's root SeedSequence."""
        return self.cluster.balancer_rng

    # ------------------------------------------------- membership (§13)
    @property
    def membership(self) -> Membership:
        return self.cluster.membership

    def join_shard(self, shard: Optional[int] = None) -> int:
        return self.cluster.join_shard(shard)

    def retire_shard(self, shard: int) -> None:
        self.cluster.retire_shard(shard)

    def quiescent(self) -> bool:
        cl = self.cluster
        if cl.membership.crashed:
            return False        # keep stepping toward the scheduled restart
        if any(b.shape[0] for b in cl.backlog):
            return False
        if cl.net is not None and not cl.net.idle():
            return False
        return not any(B.any_active(bg) for bg in cl.bgs)

    def registry_entries(self, shard: int = 0) -> List[RegEntry]:
        return self.cluster.registry_entries(shard)

    # ------------------------------------------------------ balance surface
    @property
    def states(self):
        return self.cluster.states

    @property
    def bgs(self):
        return self.cluster.bgs

    def sublists(self, s: int):
        return self.cluster.sublists(s)

    def middle_item(self, s: int, head_idx: int) -> Optional[int]:
        return self.cluster.middle_item(s, head_idx)

    def split(self, s, entry_keymax, sitem_idx) -> bool:
        return self.cluster.split(s, entry_keymax, sitem_idx)

    def move(self, s, entry_keymax, target) -> bool:
        return self.cluster.move(s, entry_keymax, target)

    def merge(self, s, left_keymax, right_keymax) -> bool:
        return self.cluster.merge(s, left_keymax, right_keymax)

    # -------------------------------------------------- replication (§15)
    @property
    def op_rate_ewma(self):
        return self.cluster.op_rate_ewma

    @property
    def rep_rate_ewma(self):
        return self.cluster.rep_rate_ewma

    @property
    def replica_epoch(self) -> int:
        return self.cluster.replica_epoch

    def replicate(self, s, entry_keymax, target) -> bool:
        return self.cluster.replicate(s, entry_keymax, target)

    def drop_replica(self, s, entry_keymax, target=-1) -> bool:
        return self.cluster.drop_replica(s, entry_keymax, target)

    def replica_sets(self):
        return self.cluster.replica_sets()

    # ------------------------------------------------------------ debugging
    def all_keys(self) -> List[int]:
        return self.cluster.all_keys()

    def shard_chain(self, s, head_idx, include_meta=False):
        return self.cluster.shard_chain(s, head_idx, include_meta)


class ShardMapBackend:
    """The SPMD ``shard_map`` round as a client backend.

    One device of the mesh per DiLi shard; routing is the on-device
    ``all_to_all`` inside ``make_dili_round``. The host side here only
    feeds client batches, harvests completions, and keeps the same overflow
    discipline as the simulator: ``cap_pair`` defaults to ``mailbox_cap``
    so no per-destination bucket can drop a row without the (host-checked)
    total outbox count exceeding ``mailbox_cap`` first — which raises
    ``OutboxOverflow`` exactly like ``Cluster.step``.

    A round is one launch and one pull: the round's packed harvest
    (``make_dili_round``), whose copy starts as the call returns. The
    balance surface works on host snapshots of the stacked device state
    and BgTables, each pulled as one packed array on first use after a
    change (a round, a command, a crash or restart); Split/Move/Merge are queued
    by editing slot ``s`` of the stacked table in one jitted call, and
    execute inside the round like any other background phase.
    """

    def __init__(self, cfg: DiLiConfig, *, mesh=None,
                 cap_pair: Optional[int] = None, seed: int = 0,
                 nemesis=None, retransmit_after: int = 4,
                 net_window: int = 4096,
                 key_lo: int = KEY_MIN, key_hi: int = KEY_MAX,
                 initial_shards: Optional[int] = None,
                 durability=None, trace: Optional[bool] = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.core.distributed import (make_dili_round,
                                            make_dili_round_hostroute,
                                            stack_states)
        from repro.core.net import Nemesis, Transport
        self._jnp = jnp
        self._jax = jax
        self.cfg = cfg
        if mesh is None:
            devs = np.array(jax.devices())
            if devs.size < cfg.num_shards:
                raise ValueError(
                    f"need {cfg.num_shards} devices for {cfg.num_shards} "
                    f"shards, found {devs.size} "
                    f"{jax.default_backend()} device(s)")
            mesh = Mesh(devs[:cfg.num_shards].reshape(cfg.num_shards),
                        ("shard",))
        self.mesh = mesh
        # host arrays go straight to the round's layout, one shard a device
        self._sharding = NamedSharding(mesh,
                                       PartitionSpec(tuple(mesh.axis_names)))
        self.cap_pair = int(cap_pair if cap_pair is not None
                            else cfg.mailbox_cap)
        if self.cap_pair < cfg.mailbox_cap:
            # with cap_pair < mailbox_cap a single destination's bucket can
            # drop rows while the total outbox stays under mailbox_cap —
            # the host-side overflow check would never fire, and a dropped
            # replicate/ack deadlocks the protocol silently
            raise ValueError(
                f"cap_pair={self.cap_pair} < mailbox_cap="
                f"{cfg.mailbox_cap}: per-destination buckets could drop "
                f"rows undetected")
        # borrow the simulator's init: bootstrap sublist on shard 0 plus
        # synchronized registry replicas everywhere else — and the
        # membership overlay, so both backends share one lifecycle engine
        boot = Cluster(cfg, seed=seed, key_lo=key_lo, key_hi=key_hi,
                       initial_shards=initial_shards)
        self.membership = boot.membership
        self._mb_logged = 0
        self._states, self._bgs = self._put(stack_states(boot.states,
                                                         boot.bgs))
        # same child-stream layout as Cluster: (delay, nemesis, balancer)
        self.seed = seed
        root = np.random.SeedSequence(seed)
        _, nemesis_ss, balancer_ss = root.spawn(3)
        self.balancer_rng = np.random.default_rng(balancer_ss)
        self.nemesis_config = nemesis
        self.net = None
        # per-round observable outcome, as ``Cluster.round_trace``: on by
        # default for nemesis runs, off on the clean path
        self.trace_enabled = (nemesis is not None) if trace is None \
            else bool(trace)
        self.round_trace: List[str] = []
        if nemesis is not None:
            # nemesis lives on the wire between outboxes and inboxes, so
            # routing crosses the host: the round skips its on-device
            # all_to_all and the Transport does delivery
            self.net = Transport(
                cfg.num_shards,
                Nemesis(nemesis, np.random.default_rng(nemesis_ss)),
                retransmit_after=retransmit_after, window=net_window)
            self._rnd = make_dili_round_hostroute(mesh, cfg)
            self.in_cap = max(cfg.mailbox_cap * cfg.num_shards,
                              cfg.batch_size * 2)
            self._net_backlog = [np.zeros((0, M.FIELDS), np.int32)
                                 for _ in range(cfg.num_shards)]
        else:
            self._rnd = make_dili_round(mesh, cfg, cap_pair=self.cap_pair)
            self.in_cap = cfg.num_shards * self.cap_pair
            # the persistent device inbox feeds the all_to_all round;
            # the hostroute path builds a fresh host inbox each round
            self._inbox = self._put(np.zeros(
                (cfg.num_shards, self.in_cap, M.FIELDS), np.int32))
        self._inflight_msgs = 0
        self._queues: List[deque] = [deque() for _ in range(cfg.num_shards)]
        self._ids = OpIdAllocator()
        self._host_states: Optional[list] = None
        self._host_bgs: Optional[list] = None
        # any background slot busy; None = unknown, read off the snapshot
        self._bg_busy: Optional[bool] = False
        self.round_no = 0
        # durability + crash plans (DESIGN.md §14): same semantics as
        # Cluster — crashes ride the nemesis config (hostroute path), so
        # the transport's down-NIC model and the WAL see the same rounds.
        from repro.core.durability import Durability
        from repro.core.durability.engine import validate_crash_plans
        self._crash_plans = tuple(nemesis.crashes) if nemesis else ()
        if self._crash_plans:
            validate_crash_plans(self._crash_plans, cfg.num_shards)
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
            empty = np.zeros((0, M.FIELDS), np.int32)
            for s in range(cfg.num_shards):
                self.durability.ensure_genesis(
                    s, boot.states[s], boot.bgs[s], empty,
                    self.net.export_shard_lanes(s)
                    if self.net is not None else {})
        self.stats = {"max_outbox": 0, "max_hops": 0, "rounds": 0,
                      "fast_hits": 0, "mut_hits": 0, "delegated": 0,
                      "move_hits": 0, "blk_hits": 0, "max_bg_active": 0,
                      "rep_hits": 0, "range_hits": 0, "serial_rows": 0,
                      "blk_rows": 0, "routed_rows": 0}
        # RANGE reassembly (DESIGN.md §16) — same count-gated protocol
        # as ``Cluster``: items and the terminal count ride separate
        # completion rows (and, across shards, separate transport lanes),
        # so publication waits until every journaled item arrived.
        self._range_ops: set = set()
        self._range_parts: Dict[int, List[Tuple[int, int]]] = {}
        self._range_done: Dict[int, Tuple[int, int]] = {}
        self._range_items: Dict[int, List[Tuple[int, int]]] = {}
        # same load/replication host state as Cluster (see sim.py): the
        # balancer and client API read an identical surface off either
        # backend.
        self.op_rate_ewma: Dict[int, float] = {}
        self.rep_rate_ewma: Dict[int, float] = {}
        self._replica_map: Dict[int, Tuple[int, set]] = {}
        self.replica_epoch = 0
        if cfg.replication:
            tree_map = self._jax.tree_util.tree_map
            R.warm_commands(tree_map(lambda x: x[0], self._states), cfg)

    # ------------------------------------------------------------- protocol
    @property
    def n(self) -> int:
        return self.cfg.num_shards

    def submit(self, shard, kinds, keys, values=None) -> List[int]:
        if not self.membership.is_routable(shard):
            raise ValueError(
                f"submit: shard {shard} is "
                f"{self.membership.state_of(shard)} at epoch "
                f"{self.membership.epoch} — route ops to one of "
                f"{self.membership.routable}")
        kinds, keys, values = materialize_ops(kinds, keys, values)
        ids = []
        for kind, key, val in zip(kinds, keys, values):
            slot = self._ids.alloc()
            self._queues[shard].append(make_op_row(shard, kind, key, val,
                                                   slot))
            ids.append(slot)
        return ids

    def submit_range(self, shard: int, lo: int, hi: int,
                     limit: int) -> int:
        """Enqueue one RANGE(lo, hi, limit) scan at ``shard`` (§16)."""
        if not self.cfg.range_scan:
            raise ValueError(
                "submit_range: cfg.range_scan is off — the scan pre-pass "
                "and MSG_RANGE handlers are compiled out of shard_round")
        if not self.membership.is_routable(shard):
            raise ValueError(
                f"submit_range: shard {shard} is "
                f"{self.membership.state_of(shard)} at epoch "
                f"{self.membership.epoch}")
        if lo < KEY_MIN or hi > KEY_MAX + 1 or limit < 1:
            raise ValueError(
                f"submit_range: span [{lo}, {hi}) limit={limit} outside "
                f"[{KEY_MIN}, {KEY_MAX + 1}) or non-positive limit")
        slot = self._ids.alloc()
        self._queues[shard].append(RS.make_range_row(shard, lo, hi,
                                                     limit, slot))
        self._range_ops.add(slot)
        self._range_parts[slot] = []
        # a recycled id must not inherit a prior scan's unfetched items
        self._range_items.pop(slot, None)
        return slot

    def take_range_items(self, op_id: int) -> List[Tuple[int, int]]:
        return self._range_items.pop(op_id)

    # ------------------------------------------------- membership (§13)
    def join_shard(self, shard: Optional[int] = None) -> int:
        """Admit a retired mesh slot as a JOINING member. The SPMD mesh
        stays at its jit-static capacity — the slot was stepping empty
        rounds all along, so no recompilation happens on join."""
        s = self.membership.begin_join(shard)
        self._broadcast_epoch()
        return s

    def retire_shard(self, shard: int) -> None:
        """Begin draining ``shard``; the host retires it (and resets its
        transport lanes, when routing is host-side) once drain completion
        is provable. The device keeps stepping the empty slot."""
        self.membership.begin_drain(shard)
        self._broadcast_epoch()

    def _broadcast_epoch(self) -> None:
        """Announce the membership view by injecting one MSG_EPOCH row
        into every capacity slot's client feed. The host feeds each
        device directly (the rows never cross the shard-to-shard wire),
        so a nemesis partition cannot block the announcement — shards
        behind a cut still act on a stale mask safely, exactly as in the
        Cluster backend, for the *data*-path messages."""
        mb = self.membership
        for dst in range(mb.capacity):
            self._queues[dst].append(
                epoch_row(dst, dst, mb.epoch, mb.mask()))

    def _drain_complete(self, s: int) -> bool:
        """Backend-specific half of the retire gate (see
        ``Cluster._drain_complete`` for the invariant): on the hostroute
        path the transport's per-lane idleness is exact; on the device
        path the on-device inbox is opaque, so the conservative witness
        is the routed-message total hitting zero."""
        bgs = self.bgs
        if owned_entry_count(self.cfg, self.states, s) != 0:
            return False
        if B.any_active(bgs[s]):
            return False
        if moves_targeting(bgs, s) != 0:
            return False
        if len(self._queues[s]):
            return False
        if self.net is not None:
            if self._net_backlog[s].shape[0]:
                return False
            if not self.net.shard_idle(s):
                return False
        elif self._inflight_msgs:
            return False
        return True

    def _membership_maintenance(self) -> None:
        """Host-driven lifecycle advance, once per round (same rules as
        ``Cluster._membership_maintenance`` — the differential harness
        holds the two backends to the same membership schedule)."""
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

    def _feed_client(self, down=()) -> np.ndarray:
        cfg = self.cfg
        client = np.zeros((self.n, cfg.batch_size, M.FIELDS), np.int32)
        for s in range(self.n):
            if s in down:
                continue        # queue is client-side memory: it survives
            q = self._queues[s]
            for b in range(min(len(q), cfg.batch_size)):
                client[s, b] = q.popleft()
        return client

    # ------------------------------------------------- crash-restart (§14)
    def _set_shard(self, s: int, state, bg) -> None:
        """Overwrite slot ``s`` of the stacked device state."""
        tree_map = self._jax.tree_util.tree_map
        jnp = self._jnp
        self._states = tree_map(
            lambda col, leaf: col.at[s].set(jnp.asarray(leaf)),
            self._states, state)
        self._bgs = tree_map(
            lambda col, leaf: col.at[s].set(jnp.asarray(leaf)),
            self._bgs, bg)
        self._drop_snapshots()
        self._bg_busy = None

    def _apply_crash_plans(self) -> None:
        """Same top-of-round ordering as ``Cluster._apply_crash_plans``:
        restarts before crashes, so both backends execute one schedule
        identically (the differential harness compares their traces)."""
        for c in self._crash_plans:
            if c.restart_round == self.round_no and c.shard in self.net.down:
                self._restart_shard(c.shard)
        for c in self._crash_plans:
            if c.crash_round == self.round_no:
                self._crash_shard(c.shard)

    def _crash_shard(self, s: int) -> None:
        from repro.core.types import init_shard
        self.membership.crash(s)
        if not self.membership.active:
            raise RuntimeError(
                f"crash of shard {s} leaves no active shard — the "
                f"coordinator for epoch broadcasts must survive")
        self._broadcast_epoch()
        self._set_shard(s, init_shard(self.cfg, s, peers_mask=0),
                        B.init_bg_table(self.cfg))
        self._net_backlog[s] = np.zeros((0, M.FIELDS), np.int32)
        self.net.crash_shard(s)

    def _restart_shard(self, s: int) -> None:
        rec = self.durability.recover(s, in_cap=self.in_cap)
        self._set_shard(s, rec.state, rec.bg)
        self._net_backlog[s] = rec.backlog
        self.net.restart_shard(s, rec.lanes)
        self.membership.restart(s)
        self._broadcast_epoch()
        self.durability.snapshot_now(
            s, self.round_no - 1, rec.state, rec.bg, rec.backlog,
            self.net.export_shard_lanes(s))

    def _check_overflow(self, out_counts) -> None:
        """Shared overflow discipline of both round paths (the same check
        ``Cluster.step`` applies): a count past ``mailbox_cap`` means rows
        were silently not stored — raise, never truncate."""
        over = max(out_counts)
        self.stats["max_outbox"] = max(self.stats["max_outbox"], over)
        if over > self.cfg.mailbox_cap:
            s = int(np.argmax(np.asarray(out_counts)))
            raise OutboxOverflow(
                f"shard {s} emitted {over} messages in round "
                f"{self.round_no}, mailbox_cap={self.cfg.mailbox_cap} — "
                f"raise mailbox_cap or reduce the per-round feed")

    def _harvest(self, h) -> List[Completion]:
        """Completions of one round as (op_id, result, src) with id
        recycling — shared by both round paths. ``h`` is the round's
        stacked ``Harvest``; its ``comp_key`` lane holds SH_KEY for a
        scalar completion, a real key for a RANGE item row (key, value)
        of the slot's scan (DESIGN.md §16)."""
        comps: List[Completion] = []
        cs, cv, cr, ck = h.comp_slot, h.comp_val, h.comp_src, h.comp_key
        done = cs >= 0
        for slot, val, src, key in zip(cs[done], cv[done], cr[done],
                                       ck[done]):
            slot, key = int(slot), int(key)
            if key != SH_KEY:
                self._range_parts.setdefault(slot, []).append(
                    (key, int(val)))
                continue
            if slot in self._range_ops:
                # terminal row: F_A is the total item count (negative =
                # error). Publication is count-gated below — items from
                # other serving shards may still be in flight.
                self._range_done[slot] = (int(val), int(src))
                continue
            comps.append((slot, int(val), int(src)))
            self._ids.release(slot)
        for slot, (total, src) in list(self._range_done.items()):
            if total >= 0 and len(self._range_parts.get(slot, ())) < total:
                continue
            self._range_items[slot] = sorted(
                self._range_parts.pop(slot, []))
            self._range_ops.discard(slot)
            del self._range_done[slot]
            comps.append((slot, total, src))
            self._ids.release(slot)
        return comps

    def _fold_counters(self, h) -> List[int]:
        """Fold the round's counters into ``stats`` and the op-rate model,
        after the overflow check; returns each shard's outbox count."""
        ctr = h.counters
        out_counts = [int(c) for c in ctr[:, CTR["out_count"]]]
        self._check_overflow(out_counts)
        add_counters(self.stats, ctr)
        self._bg_busy = bool(ctr[:, CTR["bg_active"]].any())
        self._update_op_rates(h.ent_hits, h.keymax, ctr[:, CTR["rep_hits"]])
        return out_counts

    def _update_op_rates(self, hits, kmax, rep_hits) -> None:
        """Per-entry op-rate EWMA, mirroring ``Cluster.step``'s update
        (same alpha/prune so the differential harness sees one model):
        decay every tracked entry, add this round's per-shard hits
        (``hits`` [S, M]) keyed by the post-round registry keymax
        (``kmax`` [S, M]), drop entries decayed to noise. ``rep_hits``
        (per-shard replica-served FIND counts, [S]) feeds the per-shard
        ``rep_rate_ewma`` the balancer folds into shard load — replica
        service is invisible to the registry-keyed rates (the entry lives
        on the primary), and an uncorrected model reads serving replicas
        as idle and churns moves against phantom imbalance."""
        ent_rates: Dict[int, int] = {}
        for s, e in zip(*np.nonzero(hits)):
            k = int(kmax[s, e])
            if k != ST_KEY:
                ent_rates[k] = ent_rates.get(k, 0) + int(hits[s, e])
        alpha = 0.3
        nxt: Dict[int, float] = {}
        for k, v in self.op_rate_ewma.items():
            d = v * (1.0 - alpha)
            if d > 1e-3:
                nxt[k] = d
        for k, h in ent_rates.items():
            nxt[k] = nxt.get(k, 0.0) + alpha * h
        self.op_rate_ewma = nxt
        nxt_rep: Dict[int, float] = {}
        for s, v in self.rep_rate_ewma.items():
            d = v * (1.0 - alpha)
            if d > 1e-3:
                nxt_rep[s] = d
        for s, h in enumerate(rep_hits):
            if h:
                nxt_rep[s] = nxt_rep.get(s, 0.0) + alpha * int(h)
        self.rep_rate_ewma = nxt_rep

    def _trace_round(self, comps, out_counts, extra: int) -> None:
        """Append the round to ``round_trace`` as ``Cluster.step`` does:
        the round's membership transitions, then its outcome."""
        for ep, ev, sh in self.membership.log[self._mb_logged:]:
            self.round_trace.append(f"r{self.round_no} mb {ev} s{sh} e{ep}")
        self._mb_logged = len(self.membership.log)
        self.round_trace.append(trace_entry(self.round_no, comps, out_counts,
                                            extra=extra))

    def _put(self, x):
        """A stacked host array onto the mesh, one shard a device."""
        return self._jax.device_put(x, self._sharding)

    def _drop_snapshots(self) -> None:
        self._host_states = None
        self._host_bgs = None

    def _step_hostroute(self) -> List[Completion]:
        """One round on the nemesis path: device round (no all_to_all),
        host-side transport routing of the raw outboxes."""
        with span("shardmap.launch"):
            if self._crash_plans:
                self._apply_crash_plans()
            down = self.net.down
            client = self._feed_client(down)
            inbox = np.zeros((self.n, self.in_cap, M.FIELDS), np.int32)
            for s in range(self.n):
                feed = self._net_backlog[s][:self.in_cap]
                self._net_backlog[s] = self._net_backlog[s][self.in_cap:]
                inbox[s, :feed.shape[0]] = feed
            self._states, self._bgs, vec = self._rnd(
                self._states, self._bgs, self._put(inbox), self._put(client))
            vec.copy_to_host_async()
            self._drop_snapshots()
        with span("shardmap.harvest"):
            h = unpack_harvest(np.asarray(vec), self.cfg)   # one pull
            out_counts = self._fold_counters(h)
            per_src = []
            for s in range(self.n):
                rows = h.outbox[s][:out_counts[s]]
                hops = rows[rows[:, M.F_KIND] == M.MSG_OP, M.F_X2]
                if hops.size:
                    self.stats["max_hops"] = max(self.stats["max_hops"],
                                                 int(hops.max()))
                    self.stats["delegated"] += int(hops.size)
                per_src.append((s, rows))
            comps = self._harvest(h)
        with span("shardmap.route"):
            pre_lens = [b.shape[0] for b in self._net_backlog]
            self.net.route_round(self._net_backlog, per_src, self.round_no)
            self._membership_maintenance()
            if self.durability is not None:
                # journal per live shard (same record layout as
                # Cluster.step): the client feed consumed, the routed
                # appends, completions + bg phases + epoch (replay audit),
                # post-routing lane image.
                phases = np.asarray(self._bgs.phase)
                epochs = np.asarray(self._states.epoch)
                for s in range(self.n):
                    if s in down:
                        continue
                    done = h.comp_slot[s] >= 0
                    comp = np.stack([h.comp_slot[s][done],
                                     h.comp_val[s][done],
                                     h.comp_src[s][done],
                                     h.comp_key[s][done]],
                                    axis=1).astype(np.int32)
                    lanes = self.net.export_shard_lanes(s)
                    self.durability.log_round(
                        s, self.round_no,
                        appends=self._net_backlog[s][pre_lens[s]:],
                        client=client[s], comp=comp, bg_phases=phases[s],
                        epoch=int(epochs[s]), lanes=lanes)
                    if (self.durability.config.snapshot_every > 0
                            and (self.round_no + 1)
                            % self.durability.config.snapshot_every == 0):
                        st = self._jax.tree_util.tree_map(
                            lambda x, s=s: np.asarray(x)[s], self._states)
                        bg = self._jax.tree_util.tree_map(
                            lambda x, s=s: np.asarray(x)[s], self._bgs)
                        self.durability.snapshot_now(
                            s, self.round_no, st, bg, self._net_backlog[s],
                            lanes)
            if self.trace_enabled:
                self._trace_round(comps, out_counts,
                                  extra=sum(b.shape[0]
                                            for b in self._net_backlog)
                                  + self.net.in_flight())
        self.round_no += 1
        self.stats["rounds"] += 1
        return comps

    def step(self) -> List[Completion]:
        if self.net is not None:
            return self._step_hostroute()
        with span("shardmap.launch"):
            client = self._feed_client()
            self._states, self._bgs, self._inbox, vec = self._rnd(
                self._states, self._bgs, self._inbox, self._put(client))
            # the harvest reads this one array: start its copy now, so it
            # runs as soon as the round ends
            vec.copy_to_host_async()
            self._drop_snapshots()
        with span("shardmap.harvest"):
            # the routed inbox itself never crosses to the host: its
            # counts ride at the end of each shard's harvest row
            vec = np.asarray(vec)                 # the round's one pull
            h = unpack_harvest(vec[:, :ROUTED_LIVE], self.cfg)
            out_counts = self._fold_counters(h)
            live = int(vec[:, ROUTED_LIVE].sum())
            self._inflight_msgs = live
            self.stats["routed_rows"] += live
            delegated = int(vec[:, ROUTED_OPS].sum())
            if delegated:
                self.stats["delegated"] += delegated
                self.stats["max_hops"] = max(self.stats["max_hops"],
                                             int(vec[:, ROUTED_HOPS].max()))
            comps = self._harvest(h)
            self._membership_maintenance()
            if self.trace_enabled:
                self._trace_round(comps, out_counts, extra=live)
        self.round_no += 1
        self.stats["rounds"] += 1
        return comps

    def quiescent(self) -> bool:
        if self.membership.crashed:
            return False        # keep stepping toward the scheduled restart
        if any(len(q) for q in self._queues):
            return False
        if self.net is not None:
            if any(b.shape[0] for b in self._net_backlog):
                return False
            if not self.net.idle():
                return False
        elif self._inflight_msgs:
            return False
        if self._bg_busy is None:
            self._bg_busy = any(B.any_active(bg) for bg in self.bgs)
        return not self._bg_busy

    def registry_entries(self, shard: int = 0) -> List[RegEntry]:
        if self._host_states is not None:
            return registry_entries(self._host_states[shard].registry)
        # the registries alone, not a whole snapshot
        return registry_entries(self._unstack(self._states.registry)[shard])

    # ------------------------------------------------------ balance surface
    def _unstack(self, stacked) -> list:
        """Per-shard host views of a stacked device tree, pulled as one
        packed array (``_packer``)."""
        leaves, treedef = self._jax.tree_util.tree_flatten(stacked)
        flat = np.asarray(_packer(self._sharding)(stacked))
        host, at = [], 0
        for leaf in leaves:
            n = int(np.prod(leaf.shape[1:]))
            part = flat[:, at:at + n]
            at += n
            part = (part.astype(bool) if leaf.dtype == bool
                    else part.view(leaf.dtype))
            host.append(part.reshape(leaf.shape))
        host = treedef.unflatten(host)
        tree_map = self._jax.tree_util.tree_map
        return [tree_map(lambda x, s=s: x[s], host) for s in range(self.n)]

    @property
    def states(self):
        if self._host_states is None:
            self._host_states = self._unstack(self._states)
        return self._host_states

    @property
    def bgs(self):
        if self._host_bgs is None:
            self._host_bgs = self._unstack(self._bgs)
        return self._host_bgs

    def sublists(self, s: int):
        return state_sublists(self.cfg, self.states, s)

    def middle_item(self, s: int, head_idx: int) -> Optional[int]:
        items = chain_keys(self.cfg, self.states, s, head_idx,
                           include_meta=True)
        if len(items) < 2:
            return None
        return items[len(items) // 2][1]

    def _queue_bg(self, s: int, fn, cmd: int, *args) -> bool:
        self._bgs, ok = _slot_command(fn, None, self._sharding)(
            self._bgs, s, *args)
        ok = bool(ok)
        if ok:          # a refused command leaves the table as it was
            self._host_bgs = None
            self._bg_busy = True
        if self.durability is not None:
            # host-side BgTable mutation bypasses the inbox — journal it
            # so WAL replay re-queues the command (wal.py KIND_COMMAND)
            self.durability.log_command(s, self.round_no, cmd, args, ok)
        return ok

    def split(self, s, entry_keymax, sitem_idx) -> bool:
        return self._queue_bg(s, B.queue_split, wal.CMD_SPLIT,
                              entry_keymax, sitem_idx)

    def move(self, s, entry_keymax, target) -> bool:
        return self._queue_bg(s, B.queue_move, wal.CMD_MOVE,
                              entry_keymax, target)

    def merge(self, s, left_keymax, right_keymax) -> bool:
        return self._queue_bg(s, B.queue_merge, wal.CMD_MERGE,
                              left_keymax, right_keymax)

    # -------------------------------------------------- replication (§15)
    def _queue_state(self, s: int, fn, cmd: int, *args) -> bool:
        """Like ``_queue_bg`` but for commands that edit ``ShardState``
        (the replication session table) instead of the BgTable."""
        self._states, ok = _slot_command(fn, self.cfg, self._sharding)(
            self._states, s, *args)
        self._host_states = None
        ok = bool(ok)
        if self.durability is not None:
            self.durability.log_command(s, self.round_no, cmd, args, ok)
        return ok

    def replicate(self, s, entry_keymax, target) -> bool:
        if not self.cfg.replication:
            raise ValueError(
                "replicate: cfg.replication is off — replica serve and "
                "publication are compiled out of shard_round")
        ok = self._queue_state(s, R.queue_replicate_jit, wal.CMD_REPLICATE,
                               entry_keymax, target)
        if ok:
            prim, tg = self._replica_map.get(entry_keymax, (s, set()))
            tg = set(tg) | {int(target)}
            self._replica_map[int(entry_keymax)] = (s, tg)
            self.replica_epoch += 1
        return ok

    def drop_replica(self, s, entry_keymax, target=-1) -> bool:
        if not self.cfg.replication:
            raise ValueError("drop_replica: cfg.replication is off")
        ok = self._queue_state(s, R.queue_drop_replica_jit,
                               wal.CMD_DROP_REPLICA, entry_keymax, target)
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
        """Same contract as ``Cluster.replica_sets`` (the two backends
        must expose one routing view to the client API)."""
        out = {}
        stale = []
        states = self.states
        for kmax, (prim, tg) in self._replica_map.items():
            reg = states[prim].registry
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

    # ------------------------------------------------------------ debugging
    def all_keys(self) -> List[int]:
        return global_keys(self.cfg, self.states)

    def shard_chain(self, s, head_idx, include_meta=False):
        return chain_keys(self.cfg, self.states, s, head_idx, include_meta)

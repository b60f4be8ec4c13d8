"""SPMD backend: the DiLi round under ``shard_map`` on a real device mesh.

Each device of the (flattened) mesh is one DiLi shard ("server"). A round is:

  1. ``shard_round`` locally (same jitted body as the simulator — identical
     semantics by construction; ``cfg.find_fastpath`` therefore applies here
     too: eligible reads are answered by the vectorized pre-pass on-device,
     never entering the collective fabric),
  2. bucket the outbox by destination shard,
  3. one ``all_to_all`` — the paper's RPC fabric. ≤2 collective hops per
     client op (≤3 during a Switch) is exactly Theorem 4's delegation bound.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import bg as B
from . import messages as M
from .shard import shard_round
from .types import DiLiConfig, ShardState

AXIS = "shard"
# make_dili_round's harvest row: the shard-round's packed ``RoundOut.
# harvest`` (``shard.Harvest`` layout), then these three counts over the
# rows the all_to_all routed to the shard, at these negative offsets
ROUTED_LIVE = -3    # live rows (quiescence signal)
ROUTED_OPS = -2     # delegated MSG_OP rows
ROUTED_HOPS = -1    # max delegation-hop count among them


def bucket_by_dst(outbox, count, num_shards: int, cap_pair: int):
    """Scatter outbox rows into per-destination buckets [S, cap_pair, F].

    Overflow beyond ``cap_pair`` per pair is dropped; capacities are sized so
    tests/benchmarks never hit the cap (asserted in the simulator backend).
    """
    cap = outbox.shape[0]
    buckets = jnp.zeros((num_shards, cap_pair, M.FIELDS), M.MSG_DTYPE)
    counts = jnp.zeros((num_shards,), jnp.int32)

    def body(i, c):
        buckets, counts = c
        row = outbox[i]
        live = (row[M.F_KIND] != M.MSG_NONE) & (i < count)
        d = jnp.clip(row[M.F_DST], 0, num_shards - 1)
        p = jnp.clip(counts[d], 0, cap_pair - 1)
        buckets = jnp.where(live, buckets.at[d, p].set(row), buckets)
        counts = counts.at[d].add(live.astype(jnp.int32))
        return buckets, counts

    buckets, counts = jax.lax.fori_loop(0, cap, body, (buckets, counts))
    return buckets, counts


def make_dili_round(mesh: Mesh, cfg: DiLiConfig, cap_pair: int = 8):
    """Build the jitted SPMD round: (states, bgs, inbox, client) ->
    (states, bgs, inbox_next, harvest).

    All arguments are stacked over the leading shard axis and sharded over
    the mesh's flattened device axes. ``harvest`` is int32 ``[S, H + 3]``:
    each shard-round's packed ``RoundOut.harvest`` (counters, ``ent_hits``,
    registry keymax, outbox, the four completion columns; read with
    ``shard.unpack_harvest``), then at ``ROUTED_LIVE``/``ROUTED_OPS``/
    ``ROUTED_HOPS`` the live rows, the delegated MSG_OP rows and their max
    hop count routed to the shard — everything the host driver reads of a
    round, in one array, so the routed inbox never crosses to the host.
    The completion columns carry RANGE items too (``comp_key`` a real
    key; DESIGN.md §16): on this path they are the only channel scan
    items can ride. The counters' ``out_count`` detects ``bucket_by_dst``
    overflow instead of silently losing rows.
    """
    num = cfg.num_shards
    assert num == mesh.devices.size, (num, mesh.devices.size)
    axes = tuple(mesh.axis_names)

    def per_shard(state, bg, inbox, client):
        # leading singleton shard dim from shard_map
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        bg = jax.tree_util.tree_map(lambda x: x[0], bg)
        inbox = inbox[0]
        client = client[0]
        me = jax.lax.axis_index(axes)
        out = shard_round(state, bg, me, inbox, client, cfg)
        with jax.named_scope("round.exchange"):
            buckets, _ = bucket_by_dst(out.outbox, out.out_count, num,
                                       cap_pair)
            # route: one all_to_all over the flattened mesh axes (the
            # paper's RPCs)
            routed = jax.lax.all_to_all(buckets, axes, split_axis=0,
                                        concat_axis=0)
        inbox_next = routed.reshape(1, num * cap_pair, M.FIELDS)
        rows = inbox_next[0]
        live = rows[:, M.F_KIND] != M.MSG_NONE
        is_op = rows[:, M.F_KIND] == M.MSG_OP
        harvest = jnp.concatenate([out.harvest, jnp.stack([
            jnp.sum(live).astype(jnp.int32),
            jnp.sum(is_op).astype(jnp.int32),
            jnp.max(jnp.where(is_op, rows[:, M.F_X2], 0)).astype(jnp.int32),
        ])])
        add1 = lambda x: x[None]
        return (jax.tree_util.tree_map(add1, out.state),
                jax.tree_util.tree_map(add1, out.bg),
                inbox_next, harvest[None])

    pspec = P(axes)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(pspec, pspec, pspec, pspec),
        out_specs=(pspec, pspec, pspec, pspec),
        check_vma=False)
    return jax.jit(fn)


def make_dili_round_hostroute(mesh: Mesh, cfg: DiLiConfig):
    """The SPMD round *without* the on-device ``all_to_all``: outboxes come
    back to the host, which routes them through ``core.net.Transport`` (the
    nemesis-enabled path — the adversary lives on the wire between
    outboxes and inboxes, so routing must cross the host).

    (states, bgs, inbox, client) -> (states, bgs, harvest)

    ``harvest`` is each shard-round's packed ``RoundOut.harvest`` stacked
    ``[S, H]`` (``shard.unpack_harvest``); the raw outboxes ride in it.
    Delegation stats (hops) are computed host-side from the outbox rows
    themselves — the host sees every frame on this path.
    """
    num = cfg.num_shards
    assert num == mesh.devices.size, (num, mesh.devices.size)
    axes = tuple(mesh.axis_names)

    def per_shard(state, bg, inbox, client):
        state = jax.tree_util.tree_map(lambda x: x[0], state)
        bg = jax.tree_util.tree_map(lambda x: x[0], bg)
        me = jax.lax.axis_index(axes)
        out = shard_round(state, bg, me, inbox[0], client[0], cfg)
        add1 = lambda x: x[None]
        return (jax.tree_util.tree_map(add1, out.state),
                jax.tree_util.tree_map(add1, out.bg), out.harvest[None])

    pspec = P(axes)
    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(pspec, pspec, pspec, pspec),
        out_specs=(pspec, pspec, pspec),
        check_vma=False)
    return jax.jit(fn)


def stack_states(states, bgs):
    st = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    bg = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *bgs)
    return st, bg


def service_input_specs(cfg: DiLiConfig, num_shards: int, in_cap: int):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    from .types import init_shard
    proto_state = jax.eval_shape(lambda: init_shard(cfg, 0))
    proto_bg = jax.eval_shape(lambda: B.init_bg_table(cfg))

    def stackit(sds):
        return jax.ShapeDtypeStruct((num_shards,) + sds.shape, sds.dtype)

    states = jax.tree_util.tree_map(stackit, proto_state)
    bgs = jax.tree_util.tree_map(stackit, proto_bg)
    inbox = jax.ShapeDtypeStruct((num_shards, in_cap, M.FIELDS), jnp.int32)
    client = jax.ShapeDtypeStruct(
        (num_shards, cfg.batch_size, M.FIELDS), jnp.int32)
    return states, bgs, inbox, client

"""Pallas TPU kernels: DiLi hybrid search (registry search + bounded
sublist scan) for batched key lookups — the paper's §4 "hybrid search",
restructured for the TPU memory hierarchy.

Hardware adaptation (DESIGN.md §2): the C++ DiLi chases ``next`` pointers —
a latency-bound random walk that is hostile to the TPU's vector unit. The
paper itself notes (§8) that the chunked-sublist optimization of Braginsky &
Petrank "is also applicable to the sublists of DiLi". We apply it: each
sublist's keys live in a contiguous, sorted, fixed-capacity block (the load
balancer's split threshold bounds occupancy), so the hybrid search becomes
two kernels:

    1. registry search: a broadcast compare-and-count of each query against
       the keymin column, streamed through VMEM in lane-dense chunks —
       on a sorted keymin, ``count(keymin < q) - 1`` is exactly the entry a
       binary search lands on (Algorithm 6);
    2. block sweep: the entries ride in scalar prefetch, so each query's
       block row is brought from HBM by its BlockSpec index map (the
       ``paged_attention`` pattern) and swept with one vectorized compare,

which is exactly the paper's "logarithmic index + bounded linear scan", with
the linear scan now a single VPU sweep instead of ~125 dependent loads.

Mosaic (the TPU kernel compiler) has no vector gather beyond 2-D, and a DMA
may not slice a block row whose width is not a multiple of 128 lanes — so
stage 2 views the table as ``[M/8, 8, C]`` and fetches the aligned group of
8 rows holding the entry (a whole tile, for any ``C``), then masks the one
row it wants. The table stays in HBM at any ``M``; only one group per query
is resident in VMEM.

The runtime's batched round pre-pass (``core/batch_apply.py`` — FINDs per
DESIGN.md §4, INSERT/REMOVE per §4b) implements the same two stages
against the live linked pool — stage 1 is ``registry.get_by_key``
over the identical sorted-keymin layout, stage 2 a lock-step bounded walk
(``traverse.probe_batch``) in place of the block sweep — so on TPU, once
sublists are kept in packed blocks, these kernels drop in as both
fast-paths' probe with no contract change: the mutation pre-pass consumes
stage 2's Harris window ``(left, right)``, and this search already returns
its packed-block equivalent — ``pos`` (the insertion point inside the
block) IS the link slot an insert writes and the slot a remove marks, so
the §4b conflict screen ("two lanes, one link word") maps to "two lanes,
one (entry, pos) pair" verbatim.

Layout:
  * ``keymin``  int32[M]      — registry, sorted, padding rows = INT32_MAX
  * ``blocks``  int32[M, C]   — per-sublist sorted keys, padding = INT32_MAX
  * ``queries`` int32[B]      — keys to look up
Returns:
  * ``slot``  int32[B] — M*C-flattened position of the match (or insertion
                         point) — this is the "page slot" the serving layer
                         addresses. When every key of a *full* block is
                         below q the insertion point is C (past the block),
                         so ``slot == entry*C + C`` aliases ``(entry+1)*C``
                         numerically: callers that need (entry, pos) must
                         decode against their own resolved entry, never
                         ``slot // C``.
  * ``found`` int32[B] — 1 where the block holds q (Mosaic has no bool
                         outputs; ``kernels.ops`` converts)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_MAX = jnp.iinfo(jnp.int32).max
KEYMIN_CHUNK = 2048          # keymin lanes per stage-1 grid step
GROUP = 8                    # int32 sublane tile: rows fetched per query


def _entry_kernel(q_ref, keymin_ref, cnt_ref):
    """cnt[q] += #(keymin < q) over one keymin chunk."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    below = (keymin_ref[...] < q_ref[...]).astype(jnp.int32)   # [TQ, MC]
    cnt_ref[...] += jnp.sum(below, axis=1, keepdims=True)


def _sweep_kernel(entry_ref, q_ref, grp_ref, pos_ref, found_ref, *,
                  tile_q: int):
    """One query per step: sweep its entry's row within the fetched group."""
    t = pl.program_id(1)
    i = pl.program_id(0) * tile_q + t
    e = entry_ref[i]
    q = q_ref[i]
    rows = grp_ref[...]                                        # [8, C]
    mine = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == e % GROUP
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    # insertion point = first position with key >= q. A full block with
    # every key < q has no such position: pos must be C there (insertion
    # past the block, i.e. the caller delegates to whatever follows it),
    # which the min over an all-C fill gives directly.
    pos = jnp.min(jnp.where(mine & (rows >= q), lane, rows.shape[1]),
                  axis=1, keepdims=True)
    hit = jnp.max((mine & (rows == q)).astype(jnp.int32), axis=1,
                  keepdims=True)
    pos_ref[pl.ds(t, 1)] = jnp.min(pos, axis=0, keepdims=True)[None]
    found_ref[pl.ds(t, 1)] = jnp.max(hit, axis=0, keepdims=True)[None]


@functools.partial(jax.jit, static_argnames=("tile_q", "interpret"))
def hybrid_search(keymin, blocks, queries, *, tile_q: int = 128,
                  interpret: bool = True):
    """Batched DiLi lookup. See module docstring for layout contracts.

    ``queries`` may be ragged: batches are padded internally to the next
    ``tile_q`` multiple and the outputs sliced back, so hot-path callers
    never need to know the tile size.
    """
    b = queries.shape[0]
    m, c = blocks.shape
    bp = b + (-b) % tile_q
    q = jnp.zeros((bp,), jnp.int32).at[:b].set(queries)

    # stage 1: INT32_MAX padding never counts as below any query
    mc = min(KEYMIN_CHUNK, m + (-m) % 128)
    mp = m + (-m) % mc
    km = jnp.full((1, mp), INT_MAX, jnp.int32).at[0, :m].set(keymin)
    cnt = pl.pallas_call(
        _entry_kernel,
        grid=(bp // tile_q, mp // mc),
        in_specs=[pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, mc), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        interpret=interpret,
    )(q[:, None], km)
    entry = jnp.maximum(cnt[:, 0] - 1, 0)

    # stage 2: whole 8-row groups, so any C is one aligned tile per fetch
    if m % GROUP:
        blocks = jnp.pad(blocks, ((0, -m % GROUP), (0, 0)),
                         constant_values=INT_MAX)
    groups = blocks.reshape(-1, GROUP, c)
    out = jax.ShapeDtypeStruct((bp, 1, 1), jnp.int32)
    pos, found = pl.pallas_call(
        functools.partial(_sweep_kernel, tile_q=tile_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bp // tile_q, tile_q),
            in_specs=[pl.BlockSpec(
                (None, GROUP, c),
                lambda i, t, e, q: (e[i * tile_q + t] // GROUP, 0, 0))],
            out_specs=[pl.BlockSpec((tile_q, 1, 1),
                                    lambda i, t, e, q: (i, 0, 0))] * 2,
        ),
        out_shape=[out, out],
        interpret=interpret,
    )(entry, q, groups)
    slot = entry * c + pos[:, 0, 0]
    return slot[:b], found[:b, 0, 0]

"""Public jit'd entry points for the kernels package.

``interpret`` defaults to True on CPU (this container) and False when a real
TPU backend is present — the kernels are written for TPU BlockSpec tiling
and validated against ``ref.py`` in interpret mode.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref as ref_ops
from .hybrid_search import hybrid_search as _hybrid_search
from .paged_attention import paged_attention as _paged_attention

_INT32_MAX = jnp.iinfo(jnp.int32).max

_TRUTHY = ("1", "true", "on", "yes")
_FALSY = ("0", "false", "off", "no")


def _default_interpret() -> bool:
    """Platform default (interpret everywhere but TPU), overridable via
    the ``REPRO_INTERPRET`` env var — forcing interpret *on* reproduces a
    CI failure on a TPU host, forcing it *off* exercises the compiled
    kernel path regardless of platform. Unrecognized values raise rather
    than silently fall back (a typo like ``REPRO_INTERPRET=ture`` must
    not quietly change which code path a repro runs)."""
    env = os.environ.get("REPRO_INTERPRET")
    if env is not None:
        val = env.strip().lower()
        if val in _TRUTHY:
            return True
        if val in _FALSY:
            return False
        raise ValueError(
            f"REPRO_INTERPRET={env!r}: expected one of "
            f"{_TRUTHY + _FALSY}")
    return jax.default_backend() != "tpu"


def hybrid_search(keymin, blocks, queries, *, tile_q: int = 128,
                  interpret: bool | None = None):
    """Batched DiLi lookup (registry search + block sweep).

    Contract: ``keymin`` is sorted (the registry's invariant), and real
    keys are strictly below ``INT32_MAX`` — that value is
    the block/registry padding sentinel, so a query of ``INT32_MAX`` would
    compare equal to every padding cell and report a spurious hit. Such
    queries are masked here: their ``found`` is always False (their
    ``slot`` still points at the row's first padding cell, a correct
    insertion point for "past every real key"). Ragged batch sizes are
    handled internally (padded to the tile, outputs sliced back).
    """
    if interpret is None:
        interpret = _default_interpret()
    slot, found = _hybrid_search(keymin, blocks, queries, tile_q=tile_q,
                                 interpret=interpret)
    return slot, (found != 0) & (queries != _INT32_MAX)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *,
                    page_size: int, interpret: bool | None = None):
    if interpret is None:
        interpret = _default_interpret()
    return _paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                            page_size=page_size, interpret=interpret)


# re-exported oracles
def hybrid_search_ref(keymin, blocks, queries):
    """Oracle twin of ``hybrid_search`` above — same sentinel masking, so
    the public pair stays bit-identical on every int32 input."""
    slot, found = ref_ops.hybrid_search_ref(keymin, blocks, queries)
    return slot, found & (queries != _INT32_MAX)


paged_attention_ref = ref_ops.paged_attention_ref

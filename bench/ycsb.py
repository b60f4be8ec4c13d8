"""The benchmark's own copy of the YCSB key and op generators.

Copied from the program's ``repro.data.ycsb`` so that the yardstick does not
move when the program does. ``zipf_keys`` is YCSB's bounded Zipfian (Gray et
al., "Quickly generating billion-record synthetic databases"), with YCSB's
ScrambledZipfian variant (ranks FNV-hashed over the key space); θ = 0 is
YCSB's ``requestdistribution=uniform``. ``load_phase`` draws distinct keys;
``mixed_ops`` draws fig3a's mixes: reads, then writes split evenly between
insert and remove.
"""
from __future__ import annotations

import numpy as np

OP_FIND, OP_INSERT, OP_REMOVE = 1, 2, 3      # DiLi's client op codes

FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(0x100000001B3)


def _zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1) ** theta))


def zipf_keys(rng: np.random.Generator, n: int, key_space: int,
              theta: float = 0.99, scrambled: bool = False) -> np.ndarray:
    """``n`` draws of the bounded YCSB Zipfian(θ) over ``[1, key_space]``;
    rank 1 is the hottest key, θ = 0 is uniform."""
    if not 0.0 <= theta < 1.0:
        raise ValueError(f"YCSB theta must be in [0, 1), got {theta}")
    if theta == 0.0:
        ranks = rng.integers(1, key_space + 1, size=n)
    else:
        zetan = _zeta(key_space, theta)
        zeta2 = _zeta(2, theta)
        alpha = 1.0 / (1.0 - theta)
        eta = ((1.0 - (2.0 / key_space) ** (1.0 - theta))
               / (1.0 - zeta2 / zetan))
        u = rng.random(n)
        uz = u * zetan
        ranks = (1 + (key_space * (eta * u - eta + 1.0) ** alpha)).astype(
            np.int64)
        ranks = np.where(uz < 1.0, 1, ranks)
        ranks = np.where((uz >= 1.0) & (uz < 1.0 + 0.5 ** theta), 2, ranks)
        ranks = np.clip(ranks, 1, key_space)
    if scrambled:
        h = (FNV_OFFSET ^ ranks.astype(np.uint64)) * FNV_PRIME
        h ^= h >> np.uint64(27)
        h *= FNV_PRIME
        ranks = 1 + (h % np.uint64(key_space)).astype(np.int64)
    return ranks.astype(np.int32)


def load_phase(rng: np.random.Generator, n_keys: int,
               key_space: int) -> np.ndarray:
    """``n_keys`` distinct keys of ``[1, key_space]`` in random order."""
    return (rng.permutation(key_space)[:n_keys] + 1).astype(np.int32)


def mixed_ops(rng: np.random.Generator, n_ops: int, key_space: int,
              read_frac: float, theta: float, scrambled: bool):
    """``n_ops`` ops of a fig3a mix: ``read_frac`` FINDs, the writes split
    evenly between INSERT and REMOVE. Returns ``(kinds, keys)``."""
    keys = zipf_keys(rng, n_ops, key_space, theta=theta, scrambled=scrambled)
    r = rng.random(n_ops)
    w = (1.0 - read_frac) / 2.0
    kinds = np.where(r < read_frac, OP_FIND,
                     np.where(r < read_frac + w, OP_INSERT,
                              OP_REMOVE)).astype(np.int32)
    return kinds, keys

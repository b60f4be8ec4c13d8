"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<name>.json``) holds:

  * ``loop``: ``"closed"`` — ``clients`` virtual clients, each submitting
    its next op when its previous op resolves;
  * ``clients``: how many;
  * ``read_frac``: the share of FINDs; the writes split evenly between
    INSERT and REMOVE (fig3a's mixes);
  * ``theta`` and ``scrambled``: the key distribution, YCSB's bounded
    Zipfian (θ = 0 is uniform), optionally FNV-scrambled;
  * ``warmup_rounds``: rounds of this traffic run before the window.

The ops form one stream, drawn in chunks, so a run submits a prefix of the
same sequence whatever its timing: the clients take the next op of the
stream as they free up. Every seed gets the same sequence of op kinds and
key ranks (and the same load), drawn from ``STRUCTURE``; the seed draws
the keys' identities, a permutation of the key space that ranks map
through. So runs of different seeds do the same work on different keys:
under a Zipfian mix the order of FINDs and writes on the hottest key sets
the throughput, and a seed that changed it would change the work (on one
TPU v5e, seeds moved the Zipfian mix's ops/s by 15% where two runs of one
seed moved it by 1-4%).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterator, Tuple

import numpy as np

from .ycsb import load_phase, mixed_ops

CHUNK = 1 << 14
STRUCTURE = 20251007       # seeds the op and load sequence of every run
FIELDS = ("loop", "clients", "read_frac", "theta", "scrambled",
          "warmup_rounds")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    missing = [k for k in FIELDS if k not in mix]
    if missing:
        raise ValueError(f"{path}: traffic mix lacks {missing}")
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: only closed-loop traffic is generated, "
                         f"not {mix['loop']!r}")
    return mix


def key_labels(key_space: int, seed: int) -> np.ndarray:
    """The seed's identities of the keys: rank ``r`` is key ``labels[r]``
    (index 0 unused)."""
    perm = np.random.default_rng([seed, 3]).permutation(key_space) + 1
    return np.concatenate([[0], perm]).astype(np.int32)


def load_keys(n_keys: int, key_space: int, seed: int) -> np.ndarray:
    """The ``n_keys`` distinct keys a run loads, in load order."""
    ranks = load_phase(np.random.default_rng([STRUCTURE, 1]), n_keys,
                       key_space)
    return key_labels(key_space, seed)[ranks]


def op_stream(mix: dict, key_space: int, seed: int
              ) -> Iterator[Tuple[int, int]]:
    """Endless ``(kind, key)`` stream of ``mix`` over ``[1, key_space]``."""
    labels = key_labels(key_space, seed)
    for chunk in range(1 << 62):
        rng = np.random.default_rng([STRUCTURE, 2, chunk])
        kinds, ranks = mixed_ops(rng, CHUNK, key_space, mix["read_frac"],
                                 mix["theta"], mix["scrambled"])
        yield from zip(kinds.tolist(), labels[ranks].tolist())

"""The plain reference: an ordered set of integer keys.

It states DiLi's client semantics without any of the program's code: FIND
answers whether the key is present, INSERT adds an absent key and answers
whether it did, REMOVE deletes a present key and answers whether it did.
Linearizability with per-key submission order means that applying the ops
here in submission order gives every op's result, and the final key set.
"""
from __future__ import annotations

from bisect import bisect_left, insort
from typing import Iterable, List

from .ycsb import OP_FIND, OP_INSERT, OP_REMOVE


class SortedSet:
    """A sorted list of distinct keys."""

    def __init__(self, keys: Iterable[int] = ()):
        self._keys: List[int] = sorted(set(int(k) for k in keys))

    def _has(self, key: int) -> bool:
        i = bisect_left(self._keys, key)
        return i < len(self._keys) and self._keys[i] == key

    def apply(self, kind: int, key: int) -> bool:
        key = int(key)
        present = self._has(key)
        if kind == OP_FIND:
            return present
        if kind == OP_INSERT:
            if not present:
                insort(self._keys, key)
            return not present
        if kind == OP_REMOVE:
            if present:
                del self._keys[bisect_left(self._keys, key)]
            return present
        raise ValueError(f"unknown op kind {kind}")

    def keys(self) -> List[int]:
        return list(self._keys)

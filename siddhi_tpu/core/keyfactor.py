"""A column of a block, factored once: its distinct values and, for every
event, the place of its value among them.

Everything the keyed device runtimes do with a block's partition key —
the null-key test, ``str()`` of a non-string key, the key→lane lookup,
the string dictionary of a pattern that selects or compares the key — is
a function of the *value*, so it is done once per distinct value and
gathered through ``inv``; and the factorization itself is the same for
every query of a partition, so the first one that meets a chunk makes it
and leaves it on the chunk (``EventChunk.factors``) for the others
(core/partition.py ``_PartitionExecutor.factor``, and ``column_factor``
below for an encoded string column that is not the key).  Nothing here
knows lanes or codes: those stay each runtime's own.
"""
from __future__ import annotations

from typing import Any, Callable, Hashable, List, Optional, Tuple

import numpy as np

# typed array kinds, besides ``U``, whose distinct values are the distinct
# ``str()`` of the per-event path: floats are not among them (0.0 == -0.0,
# yet their strings differ)
_TYPED_KINDS = "Siub"


class Factor:
    """``uniq`` — the distinct non-null values as strings (the ``str()``
    the per-event path would take of each), sorted, a ``U`` array;
    ``inv`` — per event the index of its value in ``uniq``, -1 where the
    value is null; ``raw_str`` — every value was a ``str`` as it came, so
    ``uniq`` holds the column's own values and not a rendering of them;
    ``source`` — the name of the chunk's column the values were (by
    identity), if they were one.

    A partition executor's factor (``compressed``) drops the null events
    instead: ``keep`` is then their mask (None when every event has a
    key) and ``inv`` runs over the kept events only, with no -1."""

    __slots__ = ("uniq", "inv", "keep", "raw_str", "source")

    def __init__(self, uniq: np.ndarray, inv: np.ndarray, raw_str: bool):
        self.uniq = uniq
        self.inv = inv
        self.keep: Optional[np.ndarray] = None
        self.raw_str = raw_str
        self.source: Optional[str] = None

    def compressed(self) -> "Factor":
        keep = self.inv >= 0
        if not keep.all():
            self.keep = keep
            self.inv = self.inv[keep]
        return self

    def keys(self) -> np.ndarray:
        """The key of every (kept) event, as ``np.asarray`` of the
        per-event path's list would be."""
        return self.uniq[self.inv]


def _sorted(strs: List[str], inv: np.ndarray, raw_str: bool) -> Factor:
    """``strs``: one string per distinct value, in any order; ``inv``
    indexes them, -1 for null."""
    if not strs:
        return Factor(np.empty(0, "U1"), inv, raw_str)
    ua = np.asarray(strs)
    # a U array drops trailing NULs: such keys share a lane as they did,
    # but ``uniq`` is then no longer the values themselves
    raw_str = raw_str and ua.tolist() == strs
    uniq, place = np.unique(ua, return_inverse=True)
    return Factor(uniq, np.append(place.reshape(-1), -1)[inv], raw_str)


def factor_values(arr: np.ndarray, strings_only: bool = False
                  ) -> Optional[Factor]:
    """Factor a 1-D array of key (or string column) values; None where
    the per-event path has to do it (floats, mixed or unhashable
    objects, and with ``strings_only`` anything but strings and nulls)."""
    kind = arr.dtype.kind
    if kind == "U":
        uniq, inv = np.unique(arr, return_inverse=True)
        return Factor(uniq, inv.reshape(-1), True)
    if kind == "O":
        lst = arr.tolist()
        types = set(map(type, lst))
        types.discard(type(None))
        if types and types != {str} and (strings_only or types != {int}):
            return None
        seen = dict.fromkeys(lst)
        seen.pop(None, None)
        vals = list(seen)
        place = dict(zip(vals, range(len(vals))))
        place[None] = -1
        inv = np.fromiter(map(place.__getitem__, lst), np.intp, len(lst))
        raw_str = types == {str} or not types
        return _sorted(vals if raw_str else [str(v) for v in vals],
                       inv, raw_str)
    if kind in _TYPED_KINDS and not strings_only:
        uniq, inv = np.unique(arr, return_inverse=True)
        return _sorted([str(v) for v in uniq.tolist()], inv.reshape(-1),
                       False)
    return None


def factor_keys(keys: List[Any]) -> Factor:
    """Factor the per-event path's key list (strings and None)."""
    arr = np.empty(len(keys), object)
    arr[:] = keys
    return factor_values(arr)


def memoized(chunk, key: Hashable, make: Callable[[], Any]
             ) -> Tuple[Any, bool]:
    """``make()`` once per chunk and ``key``, kept on the chunk
    (``EventChunk.factors``, gone with it) -> (the product, whether it
    was already there).  The product is complete before it is kept, so
    two threads that meet one chunk at worst each make it."""
    memo = chunk.factors
    if memo is None:
        memo = chunk.factors = {}
    elif key in memo:
        return memo[key], True
    product = memo[key] = make()
    return product, False


def column_factor(chunk, name: str) -> Optional[Factor]:
    """The factor of a chunk's string column, made once per chunk and
    column; None where it is no string column (object values of other
    types, no such column)."""
    def make():
        col = chunk.columns.get(name)
        return None if col is None else factor_values(np.asarray(col), True)
    return memoized(chunk, ("col", name), make)[0]

"""A block's partition keys as ids that outlive the block, and a string
column of a block factored once.

Everything the keyed device runtimes do with a block's partition key —
the null-key test, ``str()`` of a non-string key, the key→lane lookup,
the string dictionary of a pattern that selects or compares the key — is
a function of the *value*.  So a partition interns its keys
(``KeyInterner``: value → id, dense from 0, only ever appended, for as
long as the partition lives), a block's keys are one dict probe per
event (``KeyIds``), and what a runtime knows of a key — its lane, its
dictionary code — is a gather from a table indexed by id (``IdTable``),
a cache of the runtime's durable dict.  Only a block's *new* keys are
type-checked, sorted and appended.  The ids are the same for every query
of a partition, so the first one that meets a chunk makes them and
leaves them on the chunk (``EventChunk.factors``) for the others
(core/partition.py ``_PartitionExecutor.factor``).

A string column that is not the key is factored per block as before
(``Factor``, ``column_factor``): its distinct values and each event's
place among them.  Nothing here knows lanes or codes: those stay each
runtime's own.
"""
from __future__ import annotations

import threading
from itertools import repeat
from operator import itemgetter
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import numpy as np

# typed array kinds, besides ``U``, whose distinct values are the distinct
# ``str()`` of the per-event path: floats are not among them (0.0 == -0.0,
# yet their strings differ)
_TYPED_KINDS = "Siub"


#: a null key's place among a block's ids, and a table's "not asked yet"
NULL = -1
_MISS = -2


class Factor:
    """``uniq`` — the distinct non-null values as strings (the ``str()``
    the per-event path would take of each), sorted, a ``U`` array;
    ``inv`` — per event the index of its value in ``uniq``, -1 where the
    value is null; ``raw_str`` — every value was a ``str`` as it came, so
    ``uniq`` holds the column's own values and not a rendering of them."""

    __slots__ = ("uniq", "inv", "raw_str")

    def __init__(self, uniq: np.ndarray, inv: np.ndarray, raw_str: bool):
        self.uniq = uniq
        self.inv = inv
        self.raw_str = raw_str


class KeyInterner:
    """value → id for as long as its partition lives: ids dense from 0
    and only ever appended, ``strings[id]`` the key as a string.  One per
    partition runtime, shared by the executors of its streams, so a key
    has one id from whichever stream it comes.

    Only ``str`` values (and None) are ever keys of the dict, so nothing
    else can find an id: ``True`` and ``1`` never meet ``"1"``'s.  A
    ``str`` that a ``U`` array would not hold as it is (trailing NULs)
    is not one either: its block goes the per-distinct way, as any value
    that is no ``str`` does."""

    def __init__(self):
        self._id_of: Dict[Any, int] = {None: NULL}
        self.strings: List[str] = []
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.strings)

    def probe(self, values: list) -> Optional[Tuple[np.ndarray, int]]:
        """One dict probe per value -> (its id, ``NULL`` for None; how
        many values were not known).  The unknown ones are the block's
        new keys: admitted where every one is a ``str`` that is its own
        key, else None and nothing admitted.  Known values take no lock:
        an id is never reassigned."""
        known = self._id_of
        try:
            if len(values) > 1:
                # all the lookups in one C call, twice as fast as ``map``
                # over a large dict; it gives up at the first unknown key
                try:
                    return np.fromiter(itemgetter(*values)(known), np.intp,
                                       len(values)), 0
                except KeyError:
                    pass
            ids = np.fromiter(map(known.get, values, repeat(_MISS)),
                              np.intp, len(values))
        except TypeError:               # an unhashable value
            return None
        missed = np.flatnonzero(ids == _MISS)
        if len(missed):
            new = [values[i] for i in missed.tolist()]
            distinct = dict.fromkeys(new)
            if any(type(v) is not str or v[-1:] == "\0" for v in distinct):
                return None
            with self._lock:
                # in the order of their strings; the strings first, so
                # whoever finds an id finds its string
                fresh = sorted(v for v in distinct if v not in self._id_of)
                n = len(self.strings)
                self.strings.extend(fresh)
                self._id_of.update(zip(fresh, range(n, n + len(fresh))))
            ids[missed] = np.fromiter(map(self._id_of.__getitem__, new),
                                      np.intp, len(new))
        return ids, len(missed)

    def intern(self, uniq: np.ndarray) -> np.ndarray:
        """The ids of a ``Factor``'s distinct strings."""
        return self.probe(uniq.tolist())[0]


class KeyIds:
    """A block's partition keys: ``ids`` — per event its key's id in
    ``interner``; ``keep`` — the mask of the events that have a key (None
    when every event has one), ``ids`` running over those only;
    ``raw_str`` — every value was a ``str`` as it came, so an id's string
    is the column's own value and not a rendering of it; ``source`` — the
    name of the chunk's column the values were (by identity), if they
    were one; ``hits`` — the events whose id the per-event probe gave
    (null events among them): neither a new key nor a block that went
    the per-distinct way."""

    __slots__ = ("interner", "ids", "keep", "raw_str", "source", "hits")

    def __init__(self, interner: KeyInterner, ids: np.ndarray,
                 raw_str: bool, source: Optional[str], hits: int):
        self.interner = interner
        self.keep: Optional[np.ndarray] = None
        keep = ids >= 0
        if not keep.all():
            self.keep = keep
            ids = ids[keep]
        self.ids = ids
        self.raw_str = raw_str
        self.source = source
        self.hits = hits

    def keys(self) -> np.ndarray:
        """The key of every kept event, as ``np.asarray`` of the
        per-event path's list would be."""
        uniq, inv = np.unique(self.ids, return_inverse=True)
        if not len(uniq):
            return np.empty(0, "U1")
        strings = self.interner.strings
        return np.asarray([strings[i] for i in uniq.tolist()])[
            inv.reshape(-1)]


def intern_values(interner: KeyInterner, arr: Optional[np.ndarray],
                  source: Optional[str],
                  per_event: Callable[[], List[Any]]) -> KeyIds:
    """A block's key values as ids of ``interner``.  Strings and nulls
    (an object column, a ``U`` array): one dict probe per event.
    Anything else (typed integers, ``{int}`` objects, floats, bools,
    mixed objects) is factored per distinct value where the values allow
    and from ``per_event()``, the per-event list of key strings, where
    they do not (or where ``arr`` is None), and the distinct strings are
    interned.  ``source``: the name of the chunk's column that ``arr``
    is, if it is one."""
    probed = None
    if arr is not None and arr.dtype.kind in "OU":
        probed = interner.probe(arr.tolist())
    if probed is not None:
        ids, missed = probed
        return KeyIds(interner, ids, True, source, len(ids) - missed)
    f = None if arr is None else factor_values(arr)
    if f is None:
        f, source = factor_keys(per_event()), None
    ids = np.append(interner.intern(f.uniq), NULL)[f.inv]
    return KeyIds(interner, ids, f.raw_str, source, 0)


class IdTable:
    """What one consumer knows of every key, by the key's id: a cache of
    the consumer's durable dict string → value (a runtime's ``key_lanes``,
    an automaton's ``str_encoder``), ``NULL`` where the key has not been
    asked for.  It grows with the interner and is made anew whenever the
    interner or the dict is another object (a restore), so nothing of it
    is persisted.  Values are >= 0."""

    __slots__ = ("vals", "_interner", "_durable")

    def __init__(self, dtype):
        self.vals = np.empty(0, dtype)
        self._interner = self._durable = None

    def gather(self, keys: KeyIds, durable: Dict[str, Any],
               admit: Callable[[str], Any], first_sight: bool = False
               ) -> np.ndarray:
        """The value of every event's key.  A key the dict does not hold
        gets its value from ``admit(string)``: the block's such keys in
        the order of their strings, or of their first events."""
        if keys.interner is not self._interner or \
                durable is not self._durable:
            self.vals = self.vals[:0]
            self._interner, self._durable = keys.interner, durable
        strings = keys.interner.strings
        have, n = len(self.vals), len(strings)
        if have < n:
            self.vals = np.concatenate([self.vals, np.fromiter(
                map(durable.get, strings[have:n], repeat(NULL)),
                self.vals.dtype, n - have)])
        out = self.vals[keys.ids]
        if len(out) and out.min() < 0:
            new = keys.ids[out < 0].tolist()
            for i in (dict.fromkeys(new) if first_sight else
                      sorted(set(new), key=strings.__getitem__)):
                self.vals[i] = admit(strings[i])
            out = self.vals[keys.ids]
        return out


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

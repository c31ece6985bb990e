"""Plain reference: a keyed inner join of two filtered sides of one event
stream, each side with `#window.time(t)` or no window.

Semantics (SiddhiQL `from S[f_l]#window.time(t_l) as l join
S[f_r]#window.time(t_r) as r on l.key == r.key [and l.x > r.y ...]`,
output current events).  The rules, an assumed reading of upstream's
`JoinProcessor` and `TimeWindowProcessor` (their source is not on this
machine):

  1. Events are taken in arrival order; timestamps are non-decreasing.
  2. A build event (i, t), kept by its side's window of `window_ms`, is
     live for a probing event (j, t') of the other side iff i < j and
     t' - t < window_ms: upstream expires at `ts + window <= now`, and
     equal milliseconds are decided by arrival.
  3. A probing event emits one row per live build event of its key that
     passes the residual comparisons, in build arrival order, stamped
     with the probing event's timestamp.
  4. A side without a window keeps nothing, so the other side's events
     find nothing there and emit nothing.  With a window on both sides
     the rules hold with the sides swapped; an event that passes both
     sides' filters acts as a left event first (it probes the right
     window, then enters the left one) and then as a right event (it
     probes the left window, itself included, then enters the right
     one).  `trigger` is `all`, `left` or `right`: the sides whose
     events probe.
  5. An event that passes neither side's filter does nothing.  Filters
     and residuals compare at `dtype` (the stream declares float = f32;
     the benchmark's control passes a lower precision), and the float
     columns of a row are carried at `dtype`.

`args`: `key` (the key column, integer ids here), `left` and `right`
({"where": [[column, op, constant], ...], "window_ms": n or null}),
`trigger`, `residual` ([[left column, op, right column], ...]), `out`
({row column: [side, input column]}).  Rows carry `__q` = 0 (one query).
Imports nothing of the program.
"""
import operator
from collections import deque

import numpy as np

_OPS = {">": operator.gt, ">=": operator.ge, "<": operator.lt,
        "<=": operator.le, "==": operator.eq, "!=": operator.ne}
#: the counters of `run_loop`'s tally, as the program keeps them
TALLY = ("probes", "probe_hits", "rows", "inserted", "expired")


def _typed(col, dtype):
    col = np.asarray(col)
    return col.astype(np.float32).astype(dtype) if col.dtype.kind == "f" \
        else col


def _const(value, col):
    """A filter's constant, at the precision of the column it meets."""
    return np.asarray(value, np.float32).astype(col.dtype) \
        if col.dtype.kind == "f" or col.dtype.name == "bfloat16" else value


def _passes(cols, where):
    ok = np.ones(len(next(iter(cols.values()))), bool)
    for name, op, value in where:
        ok &= _OPS[op](cols[name], _const(value, cols[name]))
    return ok


def _sides(cols, args, dtype):
    cols = {k: _typed(v, dtype) for k, v in cols.items()}
    trig = args.get("trigger", "all")
    return cols, {
        s: {"on": _passes(cols, args[s]["where"]),
            "window": args[s].get("window_ms"),
            "probes": trig in ("all", s)} for s in ("left", "right")}


def _table(args, cols, ts, left_i, right_i, probe_i):
    """Rows from the index of each row's left, right and probing event."""
    out = {"__ts": np.asarray(ts, np.int64)[probe_i],
           "__q": np.zeros(len(probe_i), np.int64)}
    for name, (side, col) in args["out"].items():
        v = cols[col][left_i if side == "left" else right_i]
        out[name] = v.astype(np.float32) if v.dtype.kind not in "iub" else v
    return out


def _may_find(key, t, b, p, window):
    """A sieve before the exact search, which is a binary search per
    probing event: mark per key the time buckets (a quarter of the
    window wide, 62 at most) that hold a build event of `b`, and keep
    the probing events of `p` whose key has a mark in a bucket that
    (t - window, t] touches.  It only ever lets through too many."""
    width = max(-(-window // 4), int(t[-1]) // 62 + 1)
    marks = np.zeros(int(key.max()) + 1, np.int64)
    np.bitwise_or.at(marks, key[b], np.int64(1) << (t[b] // width))
    hi = t[p] // width
    lo = np.maximum(t[p] - window + 1, 0) // width
    return (marks[key[p]] & ((np.int64(2) << hi) - (np.int64(1) << lo))) != 0


def run(cols, ts, args, dtype=np.float32):
    """cols: {name: ndarray over all events in arrival order}; the key
    column holds integer key ids.  -> the rows, by probing event in
    arrival order, and for one probing event its left-side rows before
    its right-side ones, each in build arrival order."""
    ts = np.asarray(ts, np.int64)
    if len(ts) > 1 and (np.diff(ts) < 0).any():
        raise ValueError("timestamps must be non-decreasing")
    cols, sides = _sides(cols, args, dtype)
    key = np.asarray(cols[args["key"]], np.int64)
    n = len(ts)
    parts = []
    # (probing side, build side, may an event find itself)
    for probe, build, own in (("left", "right", False),
                              ("right", "left", True)):
        window = sides[build]["window"]
        if window is None or not sides[probe]["probes"]:
            continue
        b = np.flatnonzero(sides[build]["on"])
        p = np.flatnonzero(sides[probe]["on"])
        if not len(b) or not len(p):
            continue
        t0 = int(ts[0])
        p = p[_may_find(key, ts - t0, b, p, window)]
        b = b[np.argsort(key[b], kind="stable")]   # per key, arrival order
        span = int(ts[-1]) - t0 + window + 2
        by_ts = key[b] * span + (ts[b] - t0 + window + 1)
        by_i = key[b] * (n + 1) + b
        # live: ts_b > ts_p - window, and b before p (or p itself)
        lo = np.searchsorted(by_ts, key[p] * span + (ts[p] - t0 + 1),
                             side="right")
        hi = np.searchsorted(by_i, key[p] * (n + 1) + p,
                             side="right" if own else "left")
        cnt = np.maximum(hi - lo, 0)
        pi = np.repeat(p, cnt)
        first = np.repeat(np.cumsum(cnt) - cnt, cnt)
        bi = b[np.repeat(lo, cnt) + (np.arange(len(pi)) - first)]
        li, ri = (pi, bi) if probe == "left" else (bi, pi)
        ok = np.ones(len(pi), bool)
        for lcol, op, rcol in args.get("residual", []):
            ok &= _OPS[op](cols[lcol][li], cols[rcol][ri])
        parts.append((pi[ok], np.full(int(ok.sum()), own), li[ok], ri[ok]))
    if not parts:
        empty = np.empty(0, np.int64)
        return _table(args, cols, ts, empty, empty, empty)
    pi, phase, li, ri = (np.concatenate(x) for x in zip(*parts))
    order = np.lexsort((phase, pi))     # stable: build order is kept
    return _table(args, cols, ts, li[order], ri[order], pi[order])


def run_loop(cols, ts, args, tally=None):
    """The same semantics event by event in plain Python, a dict of
    deques per windowed side: the tests hold `run` to it.  Far too slow
    for a run.  `tally`, if given, is filled with the counters of
    `TALLY`: probing events that met a windowed side, those of them that
    found a row, rows, events that entered a window, and events expired
    (at the next event of their key)."""
    cols, sides = _sides(cols, args, np.float32)
    key = np.asarray(cols[args["key"]], np.int64)
    kept = {s: {} for s in sides if sides[s]["window"] is not None}
    count = dict.fromkeys(TALLY, 0)
    rows = []
    for j in range(len(ts)):
        on = [s for s in ("left", "right") if sides[s]["on"][j]]
        if not on:
            continue
        k, t = int(key[j]), int(ts[j])
        for s, ring in kept.items():
            q = ring.get(k)
            while q and t - int(ts[q[0]]) >= sides[s]["window"]:
                q.popleft()
                count["expired"] += 1
        for s in on:
            other = "right" if s == "left" else "left"
            if sides[s]["probes"] and other in kept:
                count["probes"] += 1
                found = 0
                for i in kept[other].get(k, ()):
                    li, ri = (j, i) if s == "left" else (i, j)
                    if all(_OPS[op](cols[lc][li], cols[rc][ri])
                           for lc, op, rc in args.get("residual", [])):
                        rows.append((j, li, ri))
                        found += 1
                count["probe_hits"] += found > 0
                count["rows"] += found
            if s in kept:
                kept[s].setdefault(k, deque()).append(j)
                count["inserted"] += 1
    if tally is not None:
        tally.update(count)
    a = np.asarray(rows, np.int64).reshape(-1, 3)
    return _table(args, cols, ts, a[:, 1], a[:, 2], a[:, 0])

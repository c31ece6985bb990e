"""Plain reference: keyed `S[price > c]#window.length(L)` with
sum / avg / count, one row per admitted event.

Semantics: per key, a ring of the last L admitted prices; each admitted
event emits (key, sum of the ring, sum / count, count) at its own
timestamp, in arrival order.  The window's entries are held in `dtype`
(the stream declares float = f32; the benchmark's control passes a lower
precision) and summed in float64, so what differs from the program is only
what the held precision loses.  A deployment runs several such queries that differ only
in the constant `price_gt` (`args["queries"]`, one entry each); a query's
rows carry its index as `__q`.  Imports nothing of the program.
"""
import numpy as np


def run(cols, ts, args, dtype=np.float32):
    """cols: {name: ndarray over all events in arrival order}; the key
    column holds integer key ids.  -> one table over all queries, each
    query's rows in (key, arrival) order."""
    parts = [_one(cols, ts, dict(args, **q), dtype) for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one(cols, ts, args, dtype):
    out = args["out"]
    price = np.asarray(cols[args["price"]], np.float32).astype(dtype)
    ok = price > np.asarray(args["price_gt"], np.float32).astype(dtype)
    key = np.asarray(cols[args["key"]], np.int64)[ok]
    ts = np.asarray(ts, np.int64)[ok]
    val = price[ok].astype(np.float64)
    length = int(args["length"])

    small = len(key) == 0 or (0 <= key.min() and key.max() < 65536)
    order = np.argsort(key.astype(np.uint16) if small else key,
                       kind="stable")            # 16 bits sort by radix
    key, ts, val = key[order], ts[order], val[order]
    n = len(key)
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]]) if n else \
        np.empty(0, np.int64)
    sizes = np.diff(np.r_[first, n])
    start = np.repeat(first, sizes)              # a key's first row
    pos = np.arange(n) - start                   # admitted before it
    csum = np.cumsum(val)
    lo = start + np.maximum(pos - length, -1)    # last row that left
    before = np.where(lo >= 0, csum[np.maximum(lo, 0)], 0.0)
    total = csum - before
    count = np.minimum(pos + 1, length)
    return {"__ts": ts, out["key"]: key, out["sum"]: total,
            out["avg"]: total / count, out["count"]: count.astype(np.int64)}


def run_loop(cols, ts, args):
    """The same semantics event by event in plain Python (tests only)."""
    parts = [_one_loop(cols, ts, dict(args, **q)) for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one_loop(cols, ts, args):
    from collections import deque
    out = args["out"]
    rings = {}
    rows = []
    for i in range(len(ts)):
        p = np.float32(cols[args["price"]][i])
        if not p > np.float32(args["price_gt"]):
            continue
        k = int(cols[args["key"]][i])
        r = rings.setdefault(k, deque(maxlen=int(args["length"])))
        r.append(float(p))
        s = float(np.sum(np.asarray(r, np.float64)))
        rows.append((k, int(ts[i]), s, s / len(r), len(r)))
    rows.sort(key=lambda r: r[0])                # stable: arrival per key
    a = np.asarray(rows, np.float64).reshape(-1, 5)
    return {"__ts": a[:, 1].astype(np.int64), out["key"]: a[:, 0].astype(
        np.int64), out["sum"]: a[:, 2], out["avg"]: a[:, 3],
        out["count"]: a[:, 4].astype(np.int64)}

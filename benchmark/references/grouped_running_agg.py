"""Plain reference: an unpartitioned running (no-window) GROUP BY with one
updated row per event, as Nexmark q17 asks of every bid.

Semantics (SiddhiQL `from S[where] select <group columns>, count(),
sum(ifThenElse(lo <= v and v < hi, 1, 0)) ..., min(v), max(v), avg(v),
sum(v) group by <group columns>`, output current events):

  1. Events are taken in arrival order.  One that fails `where` does
     nothing.
  2. Every other event belongs to the group of its tuple of group
     values; a group column is an input column, or an integer input
     column divided by a constant with Java's `long / long` (truncation
     toward zero).
  3. The event adds itself to its group's aggregates, then emits one row
     of them at its own timestamp: the count; per named range the count
     of the group's values in [lo, hi) (an open end is null); the
     running min, max, sum and sum / count (a double) of `value`.

The value is held in `dtype` (the stream declares `price long`, so an
int64, exact; the benchmark's control passes a lower precision) and
summed in int64, or in float64 where `dtype` is a float.  Rows carry
`__q` = 0 (one query) and come in the order of the first group column's
values, and for one value in arrival order (per key as the program
delivers them, so a comparison that lines rows up by that column finds
this side in place).  `args`: `where`
([[column, op, constant], ...]), `group` ({row column: [input column,
divisor]}), `value`, `count`, `ranges` ({row column: [lo, hi]}), `min`,
`max`, `sum`, `avg` (row column names).  Imports nothing of the program.
"""
import operator

import numpy as np
import pandas as pd

_OPS = {">": operator.gt, ">=": operator.ge, "<": operator.lt,
        "<=": operator.le, "==": operator.eq, "!=": operator.ne}


def _java_div(x, d):
    """`long / long` as Java computes it: truncation toward zero."""
    if not len(x) or x.min() >= 0:
        return x // d
    q = np.abs(x) // d
    return np.where(x < 0, -q, q)


def _held(value, dtype):
    """The values as the reference holds them, and as it sums them."""
    held = np.asarray(value).astype(dtype)
    if np.dtype(dtype).kind in "iu":
        return held.astype(np.int64)
    return held.astype(np.float64)


def _group_code(keys):
    """One integer per event that two events share iff their group
    tuples are equal, ordered as the tuples are."""
    m = len(keys[0])
    code, span = np.zeros(m, np.int64), 1
    for k in keys:
        lo, hi = (int(k.min()), int(k.max())) if m else (0, 0)
        span *= hi - lo + 1
        if span >= 2 ** 62:             # too wide to pack: dense ids
            return np.unique(np.stack(keys, 1), axis=0,
                             return_inverse=True)[1].reshape(-1)
        code = code * (hi - lo + 1) + (k - lo)
    return code


def _stable_order(code):
    """np.argsort(code, kind="stable"); below 2^32 as two passes over
    16-bit digits, which numpy sorts by radix."""
    if not len(code) or code.max() >= 2 ** 32:
        return np.argsort(code, kind="stable")
    low = np.argsort((code & 0xFFFF).astype(np.uint16), kind="stable")
    high = np.argsort((code[low] >> 16).astype(np.uint16), kind="stable")
    return low[high]


def run(cols, ts, args, dtype=np.int64):
    """cols: {name: ndarray over all events in arrival order}; key
    columns hold integer ids.  -> one table, rows in the order of the
    first group column, for one value in arrival order."""
    n = len(ts)
    ok = np.ones(n, bool)
    for name, op, const in args["where"]:
        ok &= _OPS[op](np.asarray(cols[name]), const)
    at = np.flatnonzero(ok)
    keys = {}
    for out, (col, div) in args["group"].items():
        k = np.asarray(cols[col], np.int64)[at]
        keys[out] = k if div == 1 else _java_div(k, div)
    v = _held(np.asarray(cols[args["value"]])[at], dtype)
    m = len(at)
    # a group's events, in arrival order, one after the other, the
    # groups in the order of their tuples
    gid = _group_code(list(keys.values()))
    order = _stable_order(gid)
    g, vs = gid[order], v[order]
    first = np.r_[True, g[1:] != g[:-1]] if m else np.empty(0, bool)
    starts = np.flatnonzero(first)
    seg = np.cumsum(first) - 1                  # each row's group
    count = np.arange(1, m + 1) - starts[seg]

    def running_sum(x):
        c = np.cumsum(x)
        return c - np.r_[0, c][starts][seg]    # minus what came before
    res = {args["count"]: count}
    for out, (lo, hi) in args["ranges"].items():
        inside = np.ones(m, bool)
        if lo is not None:
            inside &= vs >= lo
        if hi is not None:
            inside &= vs < hi
        res[out] = running_sum(inside.astype(np.int64))
    total = running_sum(vs)
    res[args["sum"]] = total
    res[args["avg"]] = total.astype(np.float64) / res[args["count"]]
    by = pd.Series(vs).groupby(g, sort=False)
    res[args["min"]] = by.cummin().to_numpy()
    res[args["max"]] = by.cummax().to_numpy()
    first = keys[next(iter(keys))][order]
    # one value of the first group column in arrival order: the groups
    # of one such value follow each other there, and only where their
    # events interleave do the rows have to be put back in order
    again = first[1:] == first[:-1]
    if m and (order[1:][again] < order[:-1][again]).any():
        by = _stable_order(_group_code([keys[next(iter(keys))]]))
        back = np.empty(m, np.int64)
        back[order] = np.arange(m)
        order, pick = by, back[by]
        res = {k: v[pick] for k, v in res.items()}
    table = {"__ts": np.asarray(ts, np.int64)[at][order],
             "__q": np.zeros(m, np.int64),
             **{k: v[order] for k, v in keys.items()}}
    table.update(res)
    return table


def run_loop(cols, ts, args, dtype=np.int64):
    """The same semantics event by event in plain Python (tests only)."""
    groups = {}
    rows = []
    names = list(args["group"])
    held = _held(np.asarray(cols[args["value"]]), dtype).tolist()
    for i in range(len(ts)):
        if not all(_OPS[op](cols[c][i], k) for c, op, k in args["where"]):
            continue
        key = []
        for col, div in args["group"].values():
            x = int(cols[col][i])
            q = abs(x) // div
            key.append(-q if x < 0 else q)
        v = held[i]
        g = groups.get(tuple(key))
        if g is None:
            g = groups[tuple(key)] = {
                "n": 0, "sum": 0, "min": v, "max": v,
                "ranges": {r: 0 for r in args["ranges"]}}
        g["n"] += 1
        g["sum"] += v
        g["min"] = min(g["min"], v)
        g["max"] = max(g["max"], v)
        for r, (lo, hi) in args["ranges"].items():
            g["ranges"][r] += int((lo is None or v >= lo) and
                                  (hi is None or v < hi))
        row = {"__ts": int(ts[i]), "__q": 0, **dict(zip(names, key)),
               args["count"]: g["n"], args["sum"]: g["sum"],
               args["avg"]: g["sum"] / g["n"], args["min"]: g["min"],
               args["max"]: g["max"], **g["ranges"]}
        rows.append(row)
    rows.sort(key=lambda r: r[names[0]])       # stable: arrival per value
    cols_out = list(rows[0]) if rows else \
        ["__ts", "__q", *names, args["count"], args["sum"], args["avg"],
         args["min"], args["max"], *args["ranges"]]
    table = {c: np.asarray([r[c] for r in rows]) for c in cols_out}
    for c in ("__ts", "__q", *names, args["count"], *args["ranges"]):
        table[c] = table[c].astype(np.int64)
    table[args["avg"]] = table[args["avg"]].astype(np.float64)
    return table

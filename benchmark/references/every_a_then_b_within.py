"""Plain reference: keyed `every A -> B within t`.

Semantics (SiddhiQL `partition with (key) ... from every e1=S[A] ->
e2=S[B(e1)] within t`): per key, every event that satisfies A opens a
partial match of its own; a partial is completed, and consumed, by the
first later event of the same key that satisfies B against it, provided
that event's timestamp is at most `within_ms` after the A event's; one B
event completes every partial it satisfies.  The row (e1.price, e2.price)
carries the B event's timestamp.

Here A is `kind == a_kind and price > a_price_gt` and B is
`kind == b_kind and price > e1.price`.  A deployment runs several such
queries that differ only in the constant `a_price_gt` (`args["queries"]`,
one entry each); every query sees every event, and its rows carry its
index as `__q` and the key as `args["out_key"]`.  The comparisons are made in
`dtype` (the stream declares float = f32; the benchmark's control passes a
lower precision).  Imports nothing of the program.
"""
import numpy as np


def run(cols, ts, args, dtype=np.float32):
    """cols: {name: ndarray over all events in arrival order}; the key
    column holds integer key ids.  -> one table over all queries; within
    a query and key, rows in the order their B events arrived."""
    parts = [_one(cols, ts, dict(args, **q), dtype) for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one(cols, ts, args, dtype):
    key = np.asarray(cols[args["key"]], np.int64)
    kind = np.asarray(cols[args["kind"]])
    price = np.asarray(cols[args["price"]], np.float32).astype(dtype)
    ts = np.asarray(ts, np.int64)
    n1, n2 = args["out"]

    small = len(key) == 0 or (0 <= key.min() and key.max() < 65536)
    order = np.argsort(key.astype(np.uint16) if small else key,
                       kind="stable")            # per key, arrival order
    key, kind, price, ts = key[order], kind[order], price[order], ts[order]
    n = len(key)
    thr = np.asarray(args["a_price_gt"], np.float32).astype(dtype)
    is_b = kind == args["b_kind"]
    pend = np.flatnonzero((kind == args["a_kind"]) & (price > thr))
    out_ts, out_p1, out_p2, out_key = [], [], [], []
    d = 0
    while len(pend):
        d += 1                                   # look d events ahead
        nxt = pend + d
        live = nxt < n
        pend, nxt = pend[live], nxt[live]
        live = (key[nxt] == key[pend]) & \
            (ts[nxt] - ts[pend] <= args["within_ms"])
        pend, nxt = pend[live], nxt[live]
        hit = is_b[nxt] & (price[nxt] > price[pend])
        out_ts.append(ts[nxt[hit]])
        out_p1.append(price[pend[hit]])
        out_p2.append(price[nxt[hit]])
        out_key.append(key[nxt[hit]])
        pend = pend[~hit]
    if not out_ts:
        out_ts, out_key = [np.empty(0, np.int64)], [np.empty(0, np.int64)]
        out_p1 = out_p2 = [np.empty(0, dtype)]
    cat = np.concatenate
    rows = {"__ts": cat(out_ts), args["out_key"]: cat(out_key),
            n1: cat(out_p1).astype(np.float32),
            n2: cat(out_p2).astype(np.float32)}
    by = np.lexsort([rows["__ts"], rows[args["out_key"]]])
    return {k: v[by] for k, v in rows.items()}


def run_loop(cols, ts, args):
    """The same semantics event by event in plain Python: the tests hold
    `run` to it.  Far too slow for a run."""
    parts = [_one_loop(cols, ts, dict(args, **q)) for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one_loop(cols, ts, args):
    pending = {}
    rows = []
    n1, n2 = args["out"]
    thr = np.float32(args["a_price_gt"])
    for i in range(len(ts)):
        k = int(cols[args["key"]][i])
        p = np.float32(cols[args["price"]][i])
        t = int(ts[i])
        kd = int(cols[args["kind"]][i])
        if kd == args["b_kind"]:
            keep = []
            for (p1, t1) in pending.get(k, ()):
                if t - t1 > args["within_ms"]:
                    continue
                if p > p1:
                    rows.append((t, float(p1), float(p), k))
                else:
                    keep.append((p1, t1))
            pending[k] = keep
        elif kd == args["a_kind"] and p > thr:
            pending.setdefault(k, []).append((p, t))
    a = np.asarray(rows, np.float64).reshape(-1, 4)
    return {"__ts": a[:, 0].astype(np.int64),
            args["out_key"]: a[:, 3].astype(np.int64),
            n1: a[:, 1].astype(np.float32), n2: a[:, 2].astype(np.float32)}

"""Plain reference: keyed `every A -> B -> not C for f within w`.

Semantics (SiddhiQL `partition with (key) ... from every e1=S[A] ->
e2=S[B(e1)] -> not S[C] for f within w`), per query and key, over the
events in arrival order; the playback clock is the largest event
timestamp admitted so far, over all keys:

  - every event that satisfies A opens a partial of its own;
  - a partial is advanced by the first later event of its key that
    satisfies B against it and arrives within `within_ms` of its A; it
    then waits with the deadline d = B.ts + for_ms.  One B advances every
    partial it satisfies;
  - a waiting partial is killed by an event of its key that satisfies C
    before its deadline has passed;
  - when the clock reaches d with the partial alive and d within
    `within_ms` of its A, the row (e1.price, e2.price) is emitted WITH
    TIMESTAMP d.  A deadline later than the last event sent emits nothing.

So the rows are a function of the events and their timestamps alone, not
of how the stream is cut into sends.  With many events per millisecond
every tie is met all the time; each has one rule.  All four follow from
one order of operations, which is ASSUMED, not sourced: it is upstream
Siddhi's as recalled from siddhi-core 5.x (under `@app:playback`
`InputHandler.send` first sets the clock to the event's timestamp,
`TimestampGeneratorImpl` tells the `Scheduler`, which sends the TIMER
events due at or before it, stamped with the time they were asked for,
and only then is the event routed; `StreamPreStateProcessor.isExpired`,
SURVEY.md's `:102-113`, is `abs(start - now) > within`), but upstream's
source was not at hand to check file and line against, so the deployment
lists the rules under `assumed` (`configs/pattern_absent_10k.json`,
`ties`).  Whoever has the source: check those three places, and where
they say otherwise change this file and both engines together.

  1. `C.ts == d`: the row is emitted and the C kills nothing.  The clock
     reached d, and the deadline fired, before that C was routed.  A C
     kills exactly when C.ts < d.
  2. `d - A.ts == within_ms`: emitted; the expiry test is a strict `>`.
  3. `B.ts - A.ts == within_ms`: B advances the partial, by the same
     strict `>` (with for_ms > 0 its deadline then lies past `within`
     and emits nothing).
  4. a deadline against an event of ANOTHER key in the same millisecond:
     no interaction.  The deadline is due by event time alone; where in
     that millisecond the other key's event arrives changes neither the
     row nor its timestamp d, only (outside these semantics) how soon it
     is delivered.  Across keys no order is defined; per key rows leave
     in timestamp order.

Where this engine differed before PR 32: it routed an event first and
advanced the clock after it (`ops/nfa.py`'s trailing deadline pass,
`core/stream.py` `_send_chunk`), so at one event per send a C with
`C.ts == d` killed the partial if it was the first event of its
millisecond and did not if another key's event had come before it: rule
4 broken, and the rows a function of the cut.  Both engines are now held
to the rules above.

`run` assumes timestamps that do not decrease in arrival order (the
deployments' feeds; it raises otherwise); `run_loop` keeps the running
maximum as the clock and takes any order.

Here A is `kind == a_kind and price > a_price_gt`, B is `kind == b_kind
and price > e1.price`, C is `kind == c_kind`.  A deployment runs several
such queries that differ only in `a_price_gt` (`args["queries"]`); every
query sees every event, and its rows carry its index as `__q` and the key
as `args["out_key"]`.  Comparisons are made in `dtype` (the stream
declares float = f32; the benchmark's control passes a lower precision).
Imports nothing of the program.
"""
import heapq

import numpy as np


def run(cols, ts, args, dtype=np.float32):
    """cols: {name: ndarray over all events in arrival order}; the key
    column holds integer key ids.  -> one table over all queries; within
    a query and key, rows in timestamp (deadline) order."""
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
    n = len(key)
    if n and bool((ts[1:] < ts[:-1]).any()):
        raise ValueError("run() wants timestamps that do not decrease in "
                         "arrival order; run_loop takes any order")
    clock_end = int(ts[-1]) if n else 0

    small = n == 0 or (0 <= key.min() and key.max() < 65536)
    order = np.argsort(key.astype(np.uint16) if small else key,
                       kind="stable")            # per key, arrival order
    key, kind, price, ts = key[order], kind[order], price[order], ts[order]
    thr = np.asarray(args["a_price_gt"], np.float32).astype(dtype)
    is_b = kind == args["b_kind"]
    # position (in the key-sorted order) of the next C at or after i
    pos_c = np.where(kind == args["c_kind"], np.arange(n), n)
    next_c = np.minimum.accumulate(pos_c[::-1])[::-1] if n else pos_c

    pend = np.flatnonzero((kind == args["a_kind"]) & (price > thr))
    got_a, got_b = [], []
    d = 0
    while len(pend):
        d += 1                                   # look d events ahead
        nxt = pend + d
        live = nxt < n
        pend, nxt = pend[live], nxt[live]
        live = (key[nxt] == key[pend]) & \
            (ts[nxt] - ts[pend] <= args["within_ms"])      # rule 3
        pend, nxt = pend[live], nxt[live]
        hit = is_b[nxt] & (price[nxt] > price[pend])
        got_a.append(pend[hit])
        got_b.append(nxt[hit])
        pend = pend[~hit]
    a = np.concatenate(got_a) if got_a else np.empty(0, np.int64)
    b = np.concatenate(got_b) if got_b else np.empty(0, np.int64)
    dl = ts[b] + args["for_ms"]
    c = next_c[b] if len(b) else b              # first later C, any key
    c_ok = np.minimum(c, max(n - 1, 0))
    killed = (c < n) & (key[c_ok] == key[b]) & (ts[c_ok] < dl)   # rule 1
    keep = ~killed & (dl - ts[a] <= args["within_ms"]) & \
        (dl <= clock_end)                                        # rule 2
    a, b, dl = a[keep], b[keep], dl[keep]
    rows = {"__ts": dl, args["out_key"]: key[b],
            n1: price[a].astype(np.float32),
            n2: price[b].astype(np.float32)}
    by = np.lexsort([rows["__ts"], rows[args["out_key"]]])
    return {k: v[by] for k, v in rows.items()}


def run_loop(cols, ts, args, stats=None):
    """The same semantics event by event in plain Python: the tests hold
    `run` to it.  Far too slow for a run.  `stats`, a dict, receives per
    query the deadlines armed, fired (row or not), and killed while still
    inside `within`."""
    parts = [_one_loop(cols, ts, dict(args, **q), stats)
             for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one_loop(cols, ts, args, stats=None):
    waiting = {}            # key -> [(a_ts, p1)]: partials waiting for a B
    armed = {}              # key -> {id: (a_ts, p1, p2)}: waiting for d
    due = []                # heap of (d, id, key)
    rows = []
    n1, n2 = args["out"]
    thr = np.float32(args["a_price_gt"])
    within, for_ms = args["within_ms"], args["for_ms"]
    clock, serial = None, 0
    n_armed = n_fired = n_killed = 0
    for i in range(len(ts)):
        k = int(cols[args["key"]][i])
        p = np.float32(cols[args["price"]][i])
        t = int(ts[i])
        kd = int(cols[args["kind"]][i])
        clock = t if clock is None else max(clock, t)
        # the clock moves first: every deadline it has reached fires, in
        # deadline order, before the event is routed (rules 1 and 4)
        while due and due[0][0] <= clock:
            d, pid, dk = heapq.heappop(due)
            part = armed.get(dk, {}).pop(pid, None)
            if part is None:
                continue                         # killed by a C
            n_fired += 1
            a_ts, p1, p2 = part
            if d - a_ts <= within:               # rule 2
                rows.append((d, float(p1), float(p2), dk))
        if kd == args["c_kind"]:
            # (counted: the kills of partials still inside `within`; one
            # past it could not have emitted anyway)
            n_killed += sum(t - a_ts <= within
                            for a_ts, _, _ in armed.get(k, {}).values())
            armed[k] = {}
        elif kd == args["b_kind"]:
            keep = []
            for (a_ts, p1) in waiting.get(k, ()):
                if t - a_ts > within:            # rule 3
                    continue
                if p > p1:
                    serial += 1
                    n_armed += 1
                    armed.setdefault(k, {})[serial] = (a_ts, p1, p)
                    heapq.heappush(due, (t + for_ms, serial, k))
                else:
                    keep.append((a_ts, p1))
            waiting[k] = keep
        elif kd == args["a_kind"] and p > thr:
            waiting.setdefault(k, []).append((t, p))
    if stats is not None:
        stats.setdefault("armed", []).append(n_armed)
        stats.setdefault("fired", []).append(n_fired)
        stats.setdefault("killed", []).append(n_killed)
    a = np.asarray(rows, np.float64).reshape(-1, 4)
    out = {"__ts": a[:, 0].astype(np.int64),
           args["out_key"]: a[:, 3].astype(np.int64),
           n1: a[:, 1].astype(np.float32), n2: a[:, 2].astype(np.float32)}
    by = np.lexsort([out["__ts"], out[args["out_key"]]])
    return {k: v[by] for k, v in out.items()}

"""Plain reference: keyed `every A<m:n> -> B within t`.

Semantics (SiddhiQL `partition with (key) ... from every e1=S[A]<m:n> ->
e2=S[B(e1[0])] within t`, m >= 1), per key, over the key's events in
arrival order.  A *chain* is one `e1`: the A events one partial has
absorbed so far.  Where each rule comes from:

(a) upstream's `CountPatternTestCase`, which the repo carries as
`tests/test_ref_pattern_count_within.py` (what one chain does):

  - a chain below `m` events closes nothing (count_4);
  - a chain that reaches `m` events waits for a B from then on (closes at
    min: count_2; the B that came before it reached `m` closed nothing:
    count_3);
  - while it waits it goes on absorbing the key's later A events (count_1)
    until it holds `n`, and no more after that (count_5);
  - `e1[k]` beyond the chain's length is null (count_1, count_2); here
    `e1[last]` is the last event absorbed before the closing B.

(b) `WithinPatternTestCase` in the same file (expiry):

  - a waiting chain whose first event is more than `t` before an arriving
    event of its key is dead before that event is looked at; the test is a
    strict `>` (`StreamPreStateProcessor.isExpired`, SURVEY.md's
    `:102-113`), so a B exactly `t` after the first A still closes
    (within_1, within_2: said here with m = n = 1, where the chain is one
    event and the pattern is `every A -> B within t`);
  - a chain below `m`, in the start state, never expires (`isExpired`
    exempts the start state): an old first A stays the chain's first.

(c) what `every` does around a count unit has no upstream case at hand.
These rules are ASSUMED (`configs/kleene_100k.json`, `assumed.every`), as
PR 32's tie rules were; both engines of the program are held to them
(`core/pattern.py` `add_every_state`, `ops/nfa.py`'s arming) and each is
planted in `tests/test_kleene_partitioned.py`.  They follow from one
reading of upstream: `CountPostStateProcessor.processMinCountReached`
hands the chain to the next state AND calls `addEveryState`, whose clone
starts with this state's events cleared.

  1. The key's first A opens its first chain.  The next chain opens with
     the first A after the chain before it reached `m`: below `m` there is
     one chain per key, so chain c holds the key's A events number
     c*m + 1 ... whatever B events come between them.
  2. A chain that reached `m` and a younger chain still filling absorb the
     same A events: the A that opens chain c + 1 is also the (m + 1)-th
     event of chain c while that one waits.
  3. One B closes EVERY waiting chain of its key that it satisfies (price
     > that chain's `e1[0].price`) and leaves the others waiting; the rows
     of one B leave in the order their chains opened.
  4. A closed chain is gone; nothing re-opens it.  Without `every`
     (`args["every"]` false: upstream's own count cases) only the key's
     first chain is ever opened.
  5. Nothing else ends a chain: not a B it does not satisfy, not an A past
     `n` (it stops absorbing and keeps waiting), only a closing B or the
     expiry of (b).

A row (e1[0].price, e1[last].price, e2.price) carries the B event's
timestamp and the key as `args["out_key"]`.  Here A is `kind == a_kind and
price > a_price_gt` and B is `kind == b_kind and price > e1[0].price`, so
no event is both.  A deployment may run several such queries that differ
in `a_price_gt` (`args["queries"]`); a query's rows carry its index as
`__q`.  Comparisons are made in `dtype` (the stream declares float = f32;
the benchmark's control passes a lower precision).

`run` wants timestamps that do not decrease in arrival order (the
deployments' feeds; it raises otherwise), because it reads (b) as "the
closing B is at most `t` after the first A"; `run_loop` takes any order.
Imports nothing of the program.
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
    n0, nl, n2 = args["out"]
    lo, hi = int(args["min_count"]), int(args["max_count"])
    if not 1 <= lo <= hi:
        raise ValueError("the reference covers 1 <= min_count <= max_count")
    n = len(key)
    if n and bool((ts[1:] < ts[:-1]).any()):
        raise ValueError("run() wants timestamps that do not decrease in "
                         "arrival order; run_loop takes any order")

    order = np.argsort(key, kind="stable")       # per key, arrival order
    key, kind, price, ts = key[order], kind[order], price[order], ts[order]
    thr = np.asarray(args["a_price_gt"], np.float32).astype(dtype)
    is_a = (kind == args["a_kind"]) & (price > thr)
    is_b = kind == args["b_kind"]
    # the A events, in (key, arrival) order; each one's number in its key
    a_pos = np.flatnonzero(is_a)
    a_key = key[a_pos]
    a_num = np.arange(len(a_pos)) - np.searchsorted(a_key, a_key)
    # A events before position i (all keys; differences stay in one key)
    a_before = np.cumsum(is_a) - is_a

    # rules 1, 4: chain c of a key opens at its A number c*m and reaches m
    # at its A number c*m + m - 1, if the key has that many
    g0 = np.flatnonzero(a_num % lo == 0 if args.get("every", True)
                        else a_num == 0)
    g0 = g0[g0 + lo - 1 < len(a_pos)]
    g0 = g0[a_key[g0 + lo - 1] == a_key[g0]]
    first = a_pos[g0]
    pend = a_pos[g0 + lo - 1]                    # from here it waits
    got_g, got_b = [], []
    d = 0
    while len(pend):
        d += 1                                   # look d events ahead
        nxt = pend + d
        live = nxt < n
        g0, first, pend, nxt = g0[live], first[live], pend[live], nxt[live]
        live = (key[nxt] == key[pend]) & \
            (ts[nxt] - ts[first] <= args["within_ms"])       # (b)
        g0, first, pend, nxt = g0[live], first[live], pend[live], nxt[live]
        hit = is_b[nxt] & (price[nxt] > price[first])        # rules 3, 5
        got_g.append(g0[hit])
        got_b.append(nxt[hit])
        g0, first, pend = g0[~hit], first[~hit], pend[~hit]
    g = np.concatenate(got_g) if got_g else np.empty(0, np.int64)
    b = np.concatenate(got_b) if got_b else np.empty(0, np.int64)
    # rule 2: the last A absorbed is the key's last A before the B, or the
    # chain's n-th if that came earlier
    last = a_pos[np.minimum(g + hi - 1, a_before[b] - 1)] if len(b) else b
    rows = {"__ts": ts[b], args["out_key"]: key[b],
            n0: price[a_pos[g]].astype(np.float32),
            nl: price[last].astype(np.float32),
            n2: price[b].astype(np.float32)}
    by = np.lexsort([g, b])          # per key by B's arrival, then chain
    return {k: v[by] for k, v in rows.items()}


def run_loop(cols, ts, args, stats=None):
    """The same semantics event by event in plain Python: the tests hold
    `run` to it.  Far too slow for a run.  `stats`, a dict, receives per
    query the chains opened, the events absorbed (over all chains: rule
    2 counts an A once per chain that takes it), the chains that reached
    `min_count`, the chains that reached `max_count`, and the most
    chains one key held at a time."""
    parts = [_one_loop(cols, ts, dict(args, **q), stats)
             for q in args["queries"]]
    for i, p in enumerate(parts):
        p["__q"] = np.full(len(p["__ts"]), i, np.int64)
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _one_loop(cols, ts, args, stats=None):
    filling = {}            # key -> [t0, p0, pl, count]: the chain below m
    waiting = {}            # key -> [[t0, p0, pl, count], ...] in opening order
    opened = {}             # key -> chains opened so far (rule 4)
    rows = []
    n0, nl, n2 = args["out"]
    lo, hi = int(args["min_count"]), int(args["max_count"])
    thr = np.float32(args["a_price_gt"])
    within, every = args["within_ms"], args.get("every", True)
    n_open = n_abs = n_min = n_max = most = 0
    for i in range(len(ts)):
        k = int(cols[args["key"]][i])
        p = np.float32(cols[args["price"]][i])
        t = int(ts[i])
        kd = int(cols[args["kind"]][i])
        # (b): a waiting chain is dead before the event is looked at
        wait = [c for c in waiting.get(k, ()) if t - c[0] <= within]
        if kd == args["b_kind"]:
            keep = []
            for c in wait:
                if p > c[1]:                                 # rule 3
                    rows.append((t, float(c[1]), float(c[2]), float(p), k))
                else:
                    keep.append(c)                           # rule 5
            wait = keep
        elif kd == args["a_kind"] and p > thr:
            for c in wait:                                   # rule 2
                if c[3] < hi:
                    c[2], c[3] = p, c[3] + 1
                    n_abs += 1
                    n_max += c[3] == hi
            c = filling.get(k)
            if c is None and (every or not opened.get(k)):   # rules 1, 4
                c = filling[k] = [t, p, p, 0]
                opened[k] = opened.get(k, 0) + 1
                n_open += 1
            if c is not None:
                c[2], c[3] = p, c[3] + 1
                n_abs += 1
                if c[3] == lo:
                    n_min += 1
                    n_max += lo == hi
                    wait.append(filling.pop(k))
        waiting[k] = wait
        most = max(most, len(wait) + (k in filling))
    if stats is not None:
        for name, v in (("opened", n_open), ("absorbed", n_abs),
                        ("reached_min", n_min), ("reached_max", n_max),
                        ("most_chains", most)):
            stats.setdefault(name, []).append(int(v))
    a = np.asarray(rows, np.float64).reshape(-1, 5)
    return {"__ts": a[:, 0].astype(np.int64),
            args["out_key"]: a[:, 4].astype(np.int64),
            n0: a[:, 1].astype(np.float32), nl: a[:, 2].astype(np.float32),
            n2: a[:, 3].astype(np.float32)}

"""`every A -> B -> not C for 1 sec within 2 sec` in a partition: absent
deadlines fired by event time inside a block (PR 32).

The rows are held to the benchmark's plain reference
(`benchmark/references/every_a_then_b_not_c_for.py`, imported by path:
numpy only, nothing of the program), which makes them a function of the
events and their timestamps alone.  So one seeded stream has to give the
same rows however it is cut into sends, on the device path (on one device
every pattern automaton is a tenant of the gang step, `plan/xtenant.py`),
under `@Async` and synchronously, and on the host engine; each row carries
its deadline as its timestamp; and under playback no send steps a host
TIMER row.  The ties the reference rules on are planted one by one.
"""
import functools
import importlib.util
import os
import time

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.ledger import ABSENT_COUNTERS, ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    path = os.path.join(REPO, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("every_a_then_b_not_c_for")
KEYS = 64
THRESHOLDS = (50.0, 70.25)
ARGS = {"key": "sym", "kind": "kind", "price": "price", "a_kind": 0,
        "b_kind": 1, "c_kind": 2, "within_ms": 2000, "for_ms": 1000,
        "out": ["p1", "p2"], "out_key": "sym",
        "queries": [{"a_price_gt": t} for t in THRESHOLDS]}
ABSENT = ("from every e1=S[kind == 0 and price > {thr}] "
          "-> e2=S[kind == 1 and price > e1.price] "
          "-> not S[kind == 2] for 1 sec within 2 sec\n"
          "select e1.sym as sym, e1.price as p1, e2.price as p2 "
          "insert into Out{q};\n")
PLAIN = ("from every e1=S[kind == 0 and price > {thr}] "
         "-> e2=S[kind == 1 and price > e1.price] within 1 sec\n"
         "select e1.sym as sym, e1.price as p1, e2.price as p2 "
         "insert into Out{q};\n")


#: a second input besides S.  `beside`: a stream of its own that no
#: pattern reads.  `upstream`: S is fed from it through a filter that
#: keeps kinds 0 to 2.  Either way its events move the app's clock and
#: (those of kind 3) never reach the pattern's runtime.
OTHER = {"beside": "define stream Other (sym string, price float, kind int);\n"
                   "from Other select sym insert into OtherOut;\n",
         "upstream": "define stream Other (sym string, price float, kind int);"
                     "\nfrom Other[kind < 3] select * insert into S;\n"}


def app_text(name, queries, engine=None, async_=False,
             playback="@app:playback", other=None):
    """queries: [(template, threshold)]; query q inserts into Out<q>."""
    body = "".join(f"@info(name='q{q}')\n" + tpl.format(thr=thr, q=q)
                   for q, (tpl, thr) in enumerate(queries))
    return ((f"@app:engine('{engine}') " if engine else "") +
            f"@app:name('{name}') {playback}\n" +
            ("@Async(buffer.size='64', batch.size.max='65536')\n"
             if async_ else "") +
            "define stream S (sym string, price float, kind int);\n" +
            (OTHER[other] if other else "") +
            "partition with (sym of S) begin\n" + body + "end;\n")


@pytest.fixture(autouse=True)
def _one_device(request, monkeypatch):
    """The served path of one chip: every pattern automaton a tenant of
    the gang step (`nfa.xstep`).  Cases of engine `mesh` keep conftest's 8
    virtual devices, where the lanes are mesh-sharded (`nfa.mesh_step`)
    and nothing gangs."""
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    if params.get("engine") != "mesh":
        monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


class Serving:
    """One running app with a collecting callback per output stream."""

    def __init__(self, text, n_queries):
        self.rt = SiddhiManager().create_siddhi_app_runtime(text)
        self.rows = []          # (q, key id, ts, p1, p2) in delivery order
        for q in range(n_queries):
            self.rt.add_callback(f"Out{q}", ColumnarStreamCallback(
                functools.partial(self._receive, q)))
        self.rt.start()

    def _receive(self, q, chunk):
        c = chunk.columns
        for j, t in enumerate(chunk.timestamps):
            self.rows.append((q, int(c["sym"][j][1:]), int(t),
                              float(c["p1"][j]), float(c["p2"][j])))

    def send(self, cols, ts, cut=None, to="S"):
        n = len(ts)
        names = np.asarray([f"k{i}" for i in range(int(cols["sym"].max())
                                                   + 1)], object)
        handler = self.rt.get_input_handler(to)
        for i in range(0, n, cut or n):
            sl = slice(i, i + (cut or n))
            handler.send_batch(
                {"sym": names[cols["sym"][sl]], "price": cols["price"][sl],
                 "kind": cols["kind"][sl]}, timestamps=ts[sl])

    def device_queries(self):
        return {name: qr for pr in self.rt.partition_runtimes
                if pr.device_mode
                for name, qr in pr.device_query_runtimes.items()}

    def backends(self):
        """{query: 'host', or on the device 'gang' / 'mesh'}"""
        if not all(pr.device_mode for pr in self.rt.partition_runtimes):
            return {"partition": "host"}
        return {name: ("mesh" if qr.device_runtime.nfa.mesh is not None
                       else "gang" if getattr(qr.device_runtime.nfa,
                                              "_tenant_bucket", None)
                       else "device")
                for name, qr in self.device_queries().items()}

    def shutdown(self):
        from siddhi_tpu.plan.xtenant import tenant_packer
        queries = self.device_queries()
        self.rt.shutdown()
        # a partition's device queries are not shut down with their app:
        # take their automata out of the process-wide gang
        for qr in queries.values():
            tenant_packer().evict(qr.device_runtime.nfa)


def stream(seed, n, rate=330):
    rng = np.random.default_rng(seed)
    cols = {"sym": rng.integers(0, KEYS, n),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 3, n)}
    return cols, 1_000_000 + (np.arange(n) * 1000) // rate


def table(rows):
    return sorted(zip(rows["__q"].tolist(), rows["sym"].tolist(),
                      rows["__ts"].tolist(),
                      rows["p1"].astype(float).tolist(),
                      rows["p2"].astype(float).tolist()))


def in_key_order(rows):
    last = {}
    for q, k, t, _p1, _p2 in rows:
        if last.get((q, k), -1) > t:
            return False
        last[(q, k)] = t
    return True


def counters(app):
    snap = ledger().snapshot(app)["apps"].get(app, {})
    return np.asarray([snap.get(k, 0) for k in ABSENT_COUNTERS], np.int64)


@functools.lru_cache(maxsize=None)
def seeded(n):
    cols, ts = stream(20261002, n)
    stats = {}
    want = table(REF.run_loop(cols, ts, ARGS, stats))
    assert table(REF.run(cols, ts, ARGS)) == want
    return cols, ts, want, stats


# ------------------------------------------------- the same rows at every cut

CUTS = [("gang", True, 64, 1500), ("gang", True, 1024, 3000),
        ("gang", True, None, 3000),
        ("gang", False, 1, 700), ("gang", False, 64, 3000),
        ("gang", False, 1024, 3000), ("gang", False, None, 3000),
        ("host", False, 1, 3000), ("host", False, 64, 3000),
        ("host", False, 1024, 3000), ("host", False, None, 3000),
        ("mesh", False, 64, 1500), ("mesh", True, 1024, 1500)]


@pytest.mark.parametrize(
    "engine,async_,cut,n", CUTS,
    ids=[f"{e}-{'async' if a else 'sync'}-{c or 'whole'}"
         for e, a, c, _n in CUTS])
def test_rows_are_the_references_at_every_cut(engine, async_, cut, n,
                                              monkeypatch):
    from siddhi_tpu.core.stream import StreamJunction
    cols, ts, want, stats = seeded(n)
    assert len(want) >= (5 if n < 1000 else 15)
    name = f"cut_{engine}_{int(async_)}_{cut}"
    before = counters(name)
    calls, call_in_order = [], StreamJunction.call_in_order
    monkeypatch.setattr(StreamJunction, "call_in_order",
                        lambda j, fn: (calls.append(fn),
                                       call_in_order(j, fn))[1])
    s = Serving(app_text(name, [(ABSENT, t) for t in THRESHOLDS],
                         engine="host" if engine == "host" else None,
                         async_=async_), len(THRESHOLDS))
    s.send(cols, ts, cut)
    s.rt.flush()
    backends = s.backends()
    grew = counters(name) - before
    s.shutdown()
    assert set(backends.values()) == {engine}, backends
    assert sorted(s.rows) == want           # each row's __ts its deadline
    assert in_key_order(s.rows)
    if engine != "host":
        armed, fired, inblock, killed, timer_rows = grew
        # every deadline was worked off by a block's own clock: no TIMER
        # row was stepped, whatever the cut
        assert timer_rows == 0 and fired == inblock == len(want)
        assert armed == sum(stats["armed"])
        assert killed == sum(stats["killed"])
    if async_:
        # nor does a TIMER ride the queue send by send (a queue item ends
        # the worker's coalescing of the sends behind it): the blocks in
        # flight bring the time themselves
        assert len(calls) <= -(-n // (cut or n)) // 2


def test_gang_two_absent_tenants_and_a_plain_one():
    """Three tenants of the gang step off one junction: the two absent
    automata share a bucket (and so one gang executable, each with its own
    block clock); the plain `every A -> B` cannot join them, an absent
    unit has no capture row and the shape class counts rows, so it steps
    in a bucket of its own at every flush, unchanged."""
    from siddhi_tpu.plan.xtenant import tenant_packer
    cols, ts, want, _ = seeded(3000)
    s = Serving(app_text("gang3", [(ABSENT, THRESHOLDS[0]),
                                   (ABSENT, THRESHOLDS[1]),
                                   (PLAIN, THRESHOLDS[0])], async_=True), 3)
    buckets = [b["tenants"] for b in tenant_packer().snapshot()["buckets"]
               if any(t.startswith("gang3/") for t in b["tenants"])]
    s.send(cols, ts, 512)
    s.rt.flush()
    s.shutdown()
    assert sorted(map(sorted, buckets)) == [["gang3/q0", "gang3/q1"],
                                            ["gang3/q2"]]
    assert sorted(r for r in s.rows if r[0] < 2) == want
    plain = _reference("every_a_then_b_within")
    pargs = dict(ARGS, within_ms=1000,
                 queries=[{"a_price_gt": THRESHOLDS[0]}])
    got = sorted((k, t, p1, p2) for q, k, t, p1, p2 in s.rows if q == 2)
    assert got == [r[1:] for r in table(plain.run(cols, ts, pargs))]
    assert len(got) > 100


# ----------------------------------------------------------- planted ties

def ev(key, kind, ts, price=0.0):
    return key, kind, ts, price


# case -> (events in arrival order, the rows it must give as (key, ts,
# p1, p2)).  Each case has keys of its own; all ride one stream.  A is
# kind 0 over 50.0, B kind 1 over e1.price, C kind 2.
PLANTED = {
    # tie 1: a C at the deadline's own millisecond kills nothing
    "c_at_deadline": ([ev(0, 0, 1000, 60.0), ev(0, 1, 1100, 61.0),
                       ev(0, 2, 2100)], [(0, 2100, 60.0, 61.0)]),
    "c_before_deadline": ([ev(1, 0, 1000, 60.0), ev(1, 1, 1100, 61.0),
                           ev(1, 2, 2099), ev(1, 2, 2100)], []),
    # tie 2: the deadline exactly `within` after the A still emits
    "deadline_at_within": ([ev(2, 0, 1000, 60.0), ev(2, 1, 2000, 61.0),
                            ev(3, 2, 3000)], [(2, 3000, 60.0, 61.0)]),
    "deadline_past_within": ([ev(4, 0, 1000, 60.0), ev(4, 1, 2001, 61.0),
                              ev(5, 2, 3001)], []),
    # tie 4: another key's event in the deadline's millisecond, before or
    # after the key's own C, changes nothing
    "other_key_first": ([ev(6, 0, 1000, 60.0), ev(6, 1, 1200, 61.0),
                         ev(7, 2, 2200), ev(6, 2, 2200)],
                        [(6, 2200, 60.0, 61.0)]),
    "own_c_first": ([ev(8, 0, 1000, 60.0), ev(8, 1, 1200, 61.0),
                     ev(8, 2, 2200), ev(9, 2, 2200)],
                    [(8, 2200, 60.0, 61.0)]),
    # a C after the deadline, in the same block as the A and the B
    "c_after_deadline": ([ev(10, 0, 1000, 60.0), ev(10, 1, 1300, 61.0),
                          ev(10, 2, 2305)], [(10, 2300, 60.0, 61.0)]),
    # one B advances every partial it satisfies, each with its own row
    "one_b_two_partials": ([ev(11, 0, 1000, 60.0), ev(11, 0, 1050, 62.0),
                            ev(11, 1, 1400, 70.0), ev(12, 0, 2400, 1.0)],
                           [(11, 2400, 60.0, 70.0), (11, 2400, 62.0, 70.0)]),
}
# tie 3 emits nothing either way; what tells it is whether the B armed a
# deadline (a B exactly `within` after its A still advances the partial)
ARMS = {"b_at_within": ([ev(13, 0, 1000, 60.0), ev(13, 1, 3000, 61.0)], 1),
        "b_past_within": ([ev(14, 0, 1000, 60.0), ev(14, 1, 3001, 61.0)], 0)}


def _columns(events):
    events = sorted(events, key=lambda e: e[2])     # stable: ties keep order
    return ({"sym": np.asarray([e[0] for e in events]),
             "kind": np.asarray([e[1] for e in events]),
             "price": np.asarray([e[3] for e in events], np.float32)},
            np.asarray([e[2] for e in events], np.int64))


@functools.lru_cache(maxsize=None)
def planted_rows(engine, cut):
    """All planted cases as one stream (closed by an event far past every
    deadline), served once per engine and cut."""
    events = [e for evs, _ in PLANTED.values() for e in evs] + [ev(15, 2, 9000)]
    cols, ts = _columns(events)
    args = dict(ARGS, queries=[{"a_price_gt": 50.0}])
    want = [r[1:] for r in table(REF.run_loop(cols, ts, args))]
    s = Serving(app_text(f"ties_{engine}_{cut}", [(ABSENT, 50.0)],
                         engine="host" if engine == "host" else None), 1)
    s.send(cols, ts, cut)
    s.rt.flush()
    s.shutdown()
    return sorted(r[1:] for r in s.rows), want


@pytest.mark.parametrize("engine,cut", [("gang", 1), ("gang", None),
                                        ("host", 1), ("host", None)])
@pytest.mark.parametrize("case", list(PLANTED))
def test_planted_tie(case, engine, cut):
    got, want = planted_rows(engine, cut)
    keys = {e[0] for e in PLANTED[case][0]}
    mine = [r for r in got if r[0] in keys]
    assert mine == sorted(PLANTED[case][1])
    assert mine == [r for r in want if r[0] in keys]    # the reference too


@pytest.mark.parametrize("case", list(ARMS))
def test_b_exactly_within_after_a_still_advances(case):
    events, armed = ARMS[case]
    cols, ts = _columns(events + [ev(15, 2, 9000)])
    stats = {}
    REF.run_loop(cols, ts, dict(ARGS, queries=[{"a_price_gt": 50.0}]), stats)
    name = f"arms_{case}"
    before = counters(name)
    s = Serving(app_text(name, [(ABSENT, 50.0)]), 1)
    s.send(cols, ts)
    s.rt.flush()
    grew = counters(name) - before
    s.shutdown()
    assert s.rows == []
    assert grew[0] == armed == stats["armed"][0]


def test_deadline_inside_a_block_without_an_event_of_its_key():
    """The key's A and B come in one send; the next holds other keys'
    events only, from before its deadline to past it.  The row leaves with
    that second block, carrying the deadline, and no TIMER row is
    stepped."""
    first = _columns([ev(0, 0, 1000, 60.0), ev(0, 1, 1100, 61.0)])
    second = _columns([ev(1, 2, 1500), ev(2, 2, 2099), ev(3, 2, 2500),
                       ev(4, 0, 2600, 99.0)])
    name = "quiet_key"
    before = counters(name)
    s = Serving(app_text(name, [(ABSENT, 50.0)]), 1)
    s.send(*first)
    s.rt.flush()
    assert s.rows == []
    s.send(*second)
    s.rt.flush()
    grew = counters(name) - before
    s.shutdown()
    assert s.rows == [(0, 0, 2100, 60.0, 61.0)]
    assert list(grew) == [1, 1, 1, 0, 0]


# ------------------------- a stream the pattern does not read moves the clock

TWO_INPUTS = [("gang", False, "beside"), ("gang", True, "beside"),
              ("host", False, "beside"), ("gang", False, "upstream"),
              ("gang", True, "upstream"), ("host", False, "upstream")]
two_inputs = pytest.mark.parametrize(
    "engine,async_,other", TWO_INPUTS,
    ids=[f"{e}-{'async' if a else 'sync'}-{o}" for e, a, o in TWO_INPUTS])


def _two_input_app(name, engine, async_, other, queries):
    return Serving(app_text(name, queries, async_=async_, other=other,
                            engine="host" if engine == "host" else None),
                   len(queries))


@pytest.mark.parametrize("armed_first", [True, False],
                         ids=["armed-first", "clock-first"])
@two_inputs
def test_a_silent_stream_still_alerts_when_another_moves_the_clock(
        engine, async_, other, armed_first):
    """The key's A and B arrive and its stream falls silent, the case an
    absence alert exists for.  An event the pattern never sees (kind 3)
    carries the app's clock past the deadline: the row leaves then, at
    the deadline, as the reference (whose clock is the largest timestamp
    admitted, on any stream) and the host engine give it.  clock-first:
    the other stream's send comes while the A and the B are still in
    flight, so the clock stands past the deadline before the host knows
    of it, and no later advance comes."""
    events = [ev(0, 0, 1000, 60.0), ev(0, 1, 1100, 61.0)]
    tick = [ev(1, 3, 5000)]
    args = dict(ARGS, queries=[{"a_price_gt": 50.0}])
    want = table(REF.run_loop(*_columns(events + tick), args))
    name = f"silent_{engine}_{int(async_)}_{other}_{int(armed_first)}"
    before = counters(name)
    s = _two_input_app(name, engine, async_, other, [(ABSENT, 50.0)])
    s.send(*_columns(events), to="S" if other == "beside" else "Other")
    if armed_first:
        s.rt.flush()
        assert s.rows == []
    s.send(*_columns(tick), to="Other")
    s.rt.flush()
    grew = counters(name) - before
    s.shutdown()
    assert s.rows == want == [(0, 0, 2100, 60.0, 61.0)]
    if engine != "host":
        armed, fired, inblock, _killed, timer_rows = grew
        assert (armed, fired, inblock) == (1, 1, 0) and timer_rows > 0


@two_inputs
def test_two_inputs_give_the_references_rows(engine, async_, other):
    """One seeded stream of kinds 0 to 3, sent 96 events at a time: the
    events of kind 3, which no pattern matches, go in by the other
    stream, and that stream runs on for three seconds after the last
    event the pattern sees.  The rows are the reference's over all the
    events: whichever stream brought the clock to a deadline, it fired
    there."""
    n, tail = 2400, 1000
    rng = np.random.default_rng(20261003)
    cols = {"sym": rng.integers(0, KEYS, n + tail),
            "price": rng.uniform(0, 100, n + tail).astype(np.float32),
            "kind": np.concatenate([rng.integers(0, 4, n),
                                    np.full(tail, 3)])}
    ts = 1_000_000 + (np.arange(n + tail) * 1000) // 330
    want = table(REF.run_loop(cols, ts, ARGS))
    name = f"two_{engine}_{int(async_)}_{other}"
    before = counters(name)
    s = _two_input_app(name, engine, async_, other,
                       [(ABSENT, t) for t in THRESHOLDS])
    for i in range(0, n + tail, 96):
        sl = slice(i, i + 96)
        noise = cols["kind"][sl] == 3
        for to, pick in ([("Other", slice(None))] if other == "upstream"
                         else [("S", ~noise), ("Other", noise)]):
            if len(ts[sl][pick]):
                s.send({k: v[sl][pick] for k, v in cols.items()},
                       ts[sl][pick], to=to)
    s.rt.flush()
    grew = counters(name) - before
    s.shutdown()
    assert len(want) > 15
    assert sorted(s.rows) == want
    assert in_key_order(s.rows)
    if engine != "host":
        _armed, fired, inblock, _killed, timer_rows = grew
        # the pattern's own blocks fired what their events reached, the
        # TIMER the rest: the deadlines the other stream's time brought
        assert fired == len(want) and 0 < inblock < fired
        assert timer_rows > 0


# ----------------------------------------- the junction's call in its order

@pytest.mark.parametrize("async_", [True, False], ids=["async", "sync"])
def test_a_call_in_order_runs_behind_the_chunks_already_sent(async_):
    """What the TIMER rides: under @Async the call waits its turn behind
    the queued chunks, and never for room (the queue here is full while
    the worker is held in a delivery); synchronously it runs at once."""
    import threading

    from siddhi_tpu import StreamCallback
    rt = SiddhiManager().create_siddhi_app_runtime(
        "@app:name('in_order')\n" +
        ("@Async(buffer.size='1')\n" if async_ else "") +
        "define stream S (x int);\n")
    seen, hold = [], threading.Event()

    def receive(events):
        if async_:
            hold.wait(20)
        seen.extend(e.data[0] for e in events)
    rt.add_callback("S", StreamCallback(receive))
    rt.start()
    h, j = rt.get_input_handler("S"), rt.junction_of("S")
    h.send([1])
    if async_:
        assert _wait(lambda: j.queue_depth() == 0)   # the worker holds 1
        h.send([2])                                  # and the queue is full
    j.call_in_order(lambda: seen.append("call"))
    assert seen == ([] if async_ else [1, "call"])
    hold.set()
    j.flush()
    rt.shutdown()
    assert seen == ([1, 2, "call"] if async_ else [1, "call"])


# -------------------------------------- where time passes with no event

def _timer_ns():
    return ledger().stage_ns()["device.timer"]


def _wait(cond, seconds=20):
    t_end = time.time() + seconds
    while not cond() and time.time() < t_end:
        time.sleep(0.05)
    return cond()


def test_idle_heartbeat_fires_through_the_timer_and_lands_at_the_deadline():
    """Playback's idle heartbeat moves the clock with no event: the host
    TIMER is still there for it, under the span `device.timer`, and the
    slot lands at its deadline, not at the heartbeat's time."""
    name = "heartbeat"
    before, ns = counters(name), _timer_ns()
    s = Serving(app_text(
        name, [(ABSENT, 50.0)],
        playback="@app:playback(idle.time='100 millisec', "
                 "increment='1500 millisec')"), 1)
    s.send(*_columns([ev(0, 0, 1000, 60.0), ev(0, 1, 1100, 61.0)]))
    # (the rows leave inside the span: wait for its end too)
    assert _wait(lambda: s.rows and _timer_ns() > ns)
    grew = counters(name) - before
    s.shutdown()
    assert s.rows == [(0, 0, 2100, 60.0, 61.0)]
    armed, fired, inblock, _killed, timer_rows = grew
    assert (armed, fired, inblock) == (1, 1, 0) and timer_rows > 0


def test_wall_clock_app_fires_through_the_timer_and_lands_at_the_deadline():
    name = "wallclock"
    before, ns = counters(name), _timer_ns()
    s = Serving(app_text(
        name, [(ABSENT.replace("for 1 sec within 2 sec",
                               "for 200 millisec within 2 sec"), 50.0)],
        playback=""), 1)
    t0 = int(time.time() * 1000)
    s.send(*_columns([ev(0, 0, t0, 60.0), ev(0, 1, t0 + 1, 61.0)]))
    assert _wait(lambda: s.rows and _timer_ns() > ns)
    grew = counters(name) - before
    s.shutdown()
    assert s.rows == [(0, 0, t0 + 201, 60.0, 61.0)]
    assert grew[1] == 1 and grew[2] == 0 and grew[4] > 0


def test_an_explicit_advance_still_fires_a_pending_deadline():
    """The clock moved by no send at all reaches the TIMER as well."""
    s = Serving(app_text("advance", [(ABSENT, 50.0)]), 1)
    s.send(*_columns([ev(0, 0, 1000, 60.0), ev(0, 1, 1100, 61.0)]))
    s.rt.flush()
    assert s.rows == []
    ctx = s.rt.app_ctx
    ctx.timestamp_generator.observe_event_time(5000)
    ctx.scheduler.advance_to(5000)
    s.shutdown()
    assert s.rows == [(0, 0, 2100, 60.0, 61.0)]


# ------------------------------------------------------------- checkpoint

def test_restore_between_two_sends_keeps_pending_deadlines():
    cols, ts, want, _ = seeded(3000)
    text = app_text("ckpt", [(ABSENT, t) for t in THRESHOLDS])
    half = 1500
    first = Serving(text, len(THRESHOLDS))
    first.send({k: v[:half] for k, v in cols.items()}, ts[:half], 512)
    first.rt.flush()
    snap = first.rt.snapshot()
    first.shutdown()
    second = Serving(text, len(THRESHOLDS))
    second.rt.restore(snap)
    second.send({k: v[half:] for k, v in cols.items()}, ts[half:], 512)
    second.rt.flush()
    second.shutdown()
    assert first.rows and second.rows
    # deadlines armed before the checkpoint fire after the restore
    assert any(t - 1000 < ts[half - 1] for _q, _k, t, *_ in second.rows)
    assert sorted(first.rows + second.rows) == want


# ---------------------------------- a pattern without an absent unit

#: jax version -> sha256 of the lowered text of `pattern_10k`'s step alone,
#: and of the step with its egress pack as the gang runs them, at 16 lanes
#: x 8 rows.  The first was taken on the commit before PR 32 (54007590)
#: and has not changed since: the step is the program it was.  The second
#: was re-pinned by PR 35, which replaced the pack's `jnp.nonzero` by a
#: search over prefix counts (`ops/compact.py`) and the tail's
#: `.at[].set` by a pad, and again by PR 36, whose pack gathers the rows'
#: captures from the slab as it lies (no `[-1, R*C]` row cut first); both
#: left the first as it was, and a change
#: that moves the first has changed the step.  Under a jax that is not
#: listed only the structure is compared (no clock leaf, no counter leaf,
#: the carry's keys, the registry's key): a change of jax's lowering is no
#: change of the program.
PLAIN_SHA = {"0.9.0": (
    "0e20f583a6b2993197da9053d80ee3e152df9548326048a65ca55783adc2f598",
    "a978b80b3e35cc065ae07ee25769a5aadf22517b0a5540a45a59c9f68ffc4bcf")}


def test_a_pattern_without_an_absent_unit_compiles_to_the_parents_program():
    """`s.deadline is None` stays a trace-time branch: `pattern_10k`'s
    step has no clock leaf, no counter leaf and the lowered text it had,
    under the registry key it had, so its persistent-cache entries still
    hit."""
    import hashlib
    import json

    import jax

    from siddhi_tpu.compiler import SiddhiCompiler
    from siddhi_tpu.ops.nfa import CLOCK_KEY, build_block_step, make_carry
    from siddhi_tpu.plan.nfa_compiler import CompiledPatternNFA
    from siddhi_tpu.plan.shapes import nfa_shape_dims
    with open(os.path.join(REPO, "benchmark", "configs",
                           "pattern_10k.json")) as f:
        app = SiddhiCompiler.parse(json.load(f)["app"])
    part = [e for e in app.execution_elements if hasattr(e, "queries")][0]
    nfa = CompiledPatternNFA(app, n_partitions=16, n_slots=8,
                             query=part.queries[0], mesh=None)
    assert not nfa.has_absent
    assert nfa_shape_dims(nfa.spec, 16, nfa.batch_b) == {
        "S": 2, "K": 8, "P": 16, "B": 4, "R": 2, "C": 2, "telem": False,
        "donate": False}
    carry = make_carry(nfa.spec, 16)
    assert sorted(carry) == ["arm_seq", "captures", "dropped", "slot_enter",
                             "slot_seq", "slot_start", "slot_state"]
    block = {a: np.zeros((16, 8), np.float32) for a in nfa.attr_names}
    block.update(__ts=np.zeros((16, 8), np.int32),
                 __stream=np.zeros((16, 8), np.int32),
                 __valid=np.zeros((16, 8), bool))
    step, pack = build_block_step(nfa.spec), nfa._egress_pack_fn()

    def gang(c, b):
        nc, (mask, cp, ts, enter, seq) = step(c, b)
        return nc, pack(mask, cp, ts, enter, seq, nc["dropped"], None, None,
                        1024)

    def sha(fn):
        text = jax.jit(fn).lower(carry, block).as_text()
        assert CLOCK_KEY not in text
        return hashlib.sha256(text.encode()).hexdigest()
    shas = sha(step), sha(gang)
    assert shas == PLAIN_SHA.get(jax.__version__, shas)
    # and what its dispatch hands the step carries no clock
    h = nfa.dispatch_events(np.zeros(4, np.int64),
                            {"sym": np.asarray(["a"] * 4, object),
                             "price": np.ones(4, np.float32),
                             "kind": np.zeros(4, np.int64)},
                            np.arange(4) + 1000, pad_t_pow2=True)
    assert CLOCK_KEY not in h["block"]

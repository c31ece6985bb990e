"""`every e1=A<3:10> -> e2=B within 1 min` in a partition: a kleene count
unit on the served path (PR 36, `BASELINE.json` configs[3]).

The rows are held to the benchmark's plain reference
(`benchmark/references/every_kleene_then_b_within.py`, imported by path:
numpy only, nothing of the program).  One seeded stream has to give the
same rows however it is cut into sends, on the gang step (on one device
every pattern automaton is a tenant of `nfa.xstep`, `plan/xtenant.py`), on
the per-automaton jit (`nfa.step` + `nfa.egress_pack`), under `@Async`
and synchronously, on the host engine, with the lanes declared
(`@app:lanes`) and grown key by key, and across a growth of the slot
ring.  Upstream's count and within cases and the reference's own numbered
`every` rules are planted one by one on both engines; the device's four
count counters are held to the reference's own tally, the two packing
counters to a block counted by hand.
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.ledger import COUNT_COUNTERS, PACK_COUNTERS, ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    path = os.path.join(REPO, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("every_kleene_then_b_within")
KEYS = 48


def ref_args(thr=50.0, lo=3, hi=10, within=60000, every=True):
    return {"key": "sym", "kind": "kind", "price": "price", "a_kind": 0,
            "b_kind": 1, "min_count": lo, "max_count": hi,
            "within_ms": within, "every": every,
            "out": ["p0", "pl", "p2"], "out_key": "sym",
            "queries": [{"a_price_gt": thr}]}


def app_text(name, engine=None, async_=False, lanes=None, thr=50.0, lo=3,
             hi=10, within=60000, every=True):
    return ((f"@app:engine('{engine}') " if engine else "") +
            f"@app:name('{name}') @app:playback" +
            (f" @app:lanes('{lanes}')" if lanes else "") + "\n" +
            ("@Async(buffer.size='64', batch.size.max='65536')\n"
             if async_ else "") +
            "define stream S (sym string, price float, kind int);\n"
            "partition with (sym of S) begin\n@info(name='q0')\n"
            f"from {'every ' if every else ''}"
            f"e1=S[kind == 0 and price > {thr}]<{lo}:{hi}>\n"
            f"    -> e2=S[kind == 1 and price > e1[0].price] "
            f"within {within} milliseconds\n"
            "select e1[0].sym as sym, e1[0].price as p0, "
            "e1[last].price as pl, e2.price as p2\ninsert into Out0;\nend;\n")


@pytest.fixture(autouse=True)
def _one_device(request, monkeypatch):
    """The served path of one chip: the automaton a tenant of the gang
    step; cases of engine `jit` take it out of the gang, as a shard-out or
    `SIDDHI_TPU_XTENANT=0` does."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    if params.get("engine") == "jit":
        monkeypatch.setenv("SIDDHI_TPU_XTENANT", "0")


class Serving:
    """One running app with a collecting callback on its output stream."""

    def __init__(self, text):
        self.rt = SiddhiManager().create_siddhi_app_runtime(text)
        self.rows = []          # (key id, ts, p0, pl, p2) in delivery order
        self.rt.add_callback("Out0", ColumnarStreamCallback(self._receive))
        self.rt.start()

    def _receive(self, chunk):
        c = chunk.columns
        for j, t in enumerate(chunk.timestamps):
            self.rows.append((int(c["sym"][j][1:]), int(t),
                              float(c["p0"][j]), float(c["pl"][j]),
                              float(c["p2"][j])))

    def send(self, cols, ts, cut=None):
        n = len(ts)
        names = np.asarray([f"k{i}" for i in range(int(cols["sym"].max())
                                                   + 1)], object)
        handler = self.rt.get_input_handler("S")
        for i in range(0, n, cut or n):
            sl = slice(i, i + (cut or n))
            handler.send_batch(
                {"sym": names[cols["sym"][sl]], "price": cols["price"][sl],
                 "kind": cols["kind"][sl]}, timestamps=ts[sl])

    def nfa(self):
        (pr,) = self.rt.partition_runtimes
        return pr.device_query_runtimes["q0"].device_runtime.nfa \
            if pr.device_mode else None

    def backend(self):
        nfa = self.nfa()
        if nfa is None:
            return "host"
        return "gang" if getattr(nfa, "_tenant_bucket", None) else "jit"

    def shutdown(self):
        from siddhi_tpu.plan.xtenant import tenant_packer
        nfa = self.nfa()
        self.rt.shutdown()
        if nfa is not None:     # out of the process-wide gang
            tenant_packer().evict(nfa)


def stream(seed, n, keys=KEYS, rate=24):
    """`rate` events per event-second over `keys` keys: a key sees one
    every two seconds, so a minute holds ~30, as in the deployment."""
    rng = np.random.default_rng(seed)
    cols = {"sym": rng.integers(0, keys, n),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 2, n)}
    return cols, 1_000_000 + (np.arange(n) * 1000) // rate


def table(rows):
    return sorted(zip(rows["sym"].tolist(), rows["__ts"].tolist(),
                      rows["p0"].astype(float).tolist(),
                      rows["pl"].astype(float).tolist(),
                      rows["p2"].astype(float).tolist()))


def in_key_order(rows):
    last = {}
    for k, t, *_ in rows:
        if last.get(k, -1) > t:
            return False
        last[k] = t
    return True


def counters(app, names=COUNT_COUNTERS):
    snap = ledger().snapshot(app)["apps"].get(app, {})
    return np.asarray([snap.get(k, 0) for k in names], np.int64)


def tally(stats):
    """The reference loop's own count of what COUNT_COUNTERS count."""
    return [stats[k][0] for k in ("opened", "absorbed", "reached_min",
                                  "reached_max")]


@functools.lru_cache(maxsize=None)
def seeded(n, thr=50.0):
    cols, ts = stream(20261004, n)
    stats = {}
    want = table(REF.run_loop(cols, ts, ref_args(thr), stats))
    assert table(REF.run(cols, ts, ref_args(thr))) == want
    return cols, ts, want, stats


# ------------------------------------------------- the same rows at every cut

CUTS = [("gang", True, 64, 1500), ("gang", True, 1024, 3000),
        ("gang", True, None, 3000),
        ("gang", False, 1, 600), ("gang", False, 64, 3000),
        ("gang", False, 1024, 3000), ("gang", False, None, 3000),
        ("jit", True, 1024, 3000), ("jit", False, 64, 1500),
        ("jit", False, None, 3000),
        ("host", False, 1, 3000), ("host", False, 64, 3000),
        ("host", False, 1024, 3000), ("host", False, None, 3000)]


@pytest.mark.parametrize(
    "engine,async_,cut,n", CUTS,
    ids=[f"{e}-{'async' if a else 'sync'}-{c or 'whole'}"
         for e, a, c, _n in CUTS])
def test_rows_are_the_references_at_every_cut(engine, async_, cut, n):
    cols, ts, want, stats = seeded(n)
    assert len(want) >= (10 if n < 1000 else 50)
    name = f"kcut_{engine}_{int(async_)}_{cut}"
    before = counters(name)
    s = Serving(app_text(name, engine="host" if engine == "host" else None,
                         async_=async_, lanes=KEYS))
    s.send(cols, ts, cut)
    s.rt.flush()
    backend = s.backend()
    grew = counters(name) - before
    s.shutdown()
    assert backend == engine
    assert sorted(s.rows) == want
    assert in_key_order(s.rows)
    if engine != "host":
        # chains started, events appended, chains that reached 3 and
        # chains that reached 10, summed over the lanes on the device
        assert grew.tolist() == tally(stats)
        assert grew[0] > grew[2] > len(want) and (grew[3] > 0 or n < 1000)


# ------------------------------------------------- lanes declared or grown

@pytest.mark.parametrize("lanes", [None, KEYS], ids=["grown", "declared"])
def test_lanes_declared_or_grown_give_the_same_rows(lanes):
    """`@app:lanes` allocates the lanes once; without it they double from
    8 as keys are admitted (three growths to 64 here), and the carry,
    its count planes and counters among them, moves with them."""
    cols, ts, want, stats = seeded(3000)
    name = f"klanes_{lanes}"
    before = counters(name)
    s = Serving(app_text(name, async_=True, lanes=lanes))
    first = s.nfa().n_partitions
    s.send(cols, ts, 256)
    s.rt.flush()
    last = s.nfa().n_partitions
    grew = counters(name) - before
    s.shutdown()
    assert (first, last) == ((64, 64) if lanes else (8, 64))
    assert sorted(s.rows) == want
    assert grew.tolist() == tally(stats)


def test_slot_ring_growth_keeps_rows_and_counters():
    """Forty A events of a key above every B of its minute: thirteen
    chains wait at a time, the slot ring doubles (grow-and-replay from
    the block's pre-carry), no row is lost and no chain counted twice."""
    rng = np.random.default_rng(36)
    n = 3 * 60
    cols = {"sym": np.arange(n) % 3,
            "price": rng.uniform(90, 99, n).astype(np.float32),
            "kind": np.zeros(n, np.int64)}
    low = rng.random(n) < 0.3                   # a B that closes nothing
    cols["kind"][low] = 1
    cols["price"][low] = rng.uniform(10, 50, low.sum()).astype(np.float32)
    cols["kind"][-3:], cols["price"][-3:] = 1, 99.5     # closes them all
    ts = 1_000_000 + 100 * np.arange(n)
    stats = {}
    want = table(REF.run_loop(cols, ts, ref_args(), stats))
    assert stats["most_chains"][0] > 8
    before = counters("kslots")
    s = Serving(app_text("kslots", async_=True, lanes=3))
    s.send(cols, ts, 16)
    s.rt.flush()
    slots = s.nfa().spec.n_slots
    grew = counters("kslots") - before
    s.shutdown()
    assert slots > 8
    assert sorted(s.rows) == want and len(want) > 30
    assert grew.tolist() == tally(stats)


# ------------------------------------------- planted: upstream's and the rules'

def ev(kind, price, ts=None):
    return (0 if kind == "A" else 1, float(price), ts)


#: name -> (bounds, every, within ms, events of one key, rows (ts, p0, pl,
#: p2)).  Events without a timestamp come 10 ms apart from 1000.  The
#: count_* and within_* cases are upstream's (CountPatternTestCase and
#: WithinPatternTestCase, `tests/test_ref_pattern_count_within.py`) said
#: over one stream: Stream1 is kind A, Stream2 kind B; the others are the
#: reference's (b) and its numbered rules (c).
PLANTED = {
    "count_1_gap_in_run": (
        (2, 5), False, 60000,
        [ev("A", 25.6), ev("A", 47.6), ev("A", 13.7), ev("A", 47.8),
         ev("B", 45.7), ev("B", 55.7)], [(1040, 25.6, 47.8, 45.7)]),
    "count_2_closes_at_min": (
        (2, 5), False, 60000,
        [ev("A", 25.6), ev("A", 47.6), ev("A", 13.7), ev("B", 45.7),
         ev("A", 47.8), ev("B", 55.7)], [(1030, 25.6, 47.6, 45.7)]),
    "count_3_min_reached_after_first_close_attempt": (
        (2, 5), False, 60000,
        [ev("A", 25.6), ev("B", 45.7), ev("A", 47.8), ev("B", 55.7)],
        [(1030, 25.6, 47.8, 55.7)]),
    "count_4_below_min_no_match": (
        (2, 5), False, 60000, [ev("A", 25.6), ev("B", 45.7)], []),
    "count_5_max_stops_absorbing": (
        (2, 5), False, 60000,
        [ev("A", 25.6), ev("A", 47.6), ev("A", 23.7), ev("A", 24.7),
         ev("A", 25.7), ev("A", 27.6), ev("B", 45.7), ev("A", 47.8),
         ev("B", 55.7)], [(1060, 25.6, 25.7, 45.7)]),
    "within_1_first_partial_expires": (
        (1, 1), True, 1000,
        [ev("A", 55.6, 1000), ev("A", 54.0, 2500), ev("B", 55.7, 2600)],
        [(2600, 54.0, 54.0, 55.7)]),
    "b_exactly_within_still_closes": (
        (3, 10), True, 60000,
        [ev("A", 30, 1000), ev("A", 31, 1001), ev("A", 32, 1002),
         ev("B", 99, 61000)], [(61000, 30.0, 32.0, 99.0)]),
    "b_one_past_within_is_dead": (
        (3, 10), True, 60000,
        [ev("A", 30, 1000), ev("A", 31, 1001), ev("A", 32, 1002),
         ev("B", 99, 61001)], []),
    "b_a_filling_chain_never_expires": (
        (3, 10), True, 60000,
        [ev("A", 30, 1000), ev("A", 31, 500000), ev("A", 32, 999000),
         ev("B", 99, 999001), ev("A", 40, 999002), ev("A", 41, 999003),
         ev("A", 42, 999004), ev("B", 99, 999005)],
        [(999005, 40.0, 42.0, 99.0)]),
    "rule_1_next_chain_opens_after_min_whatever_b_between": (
        (3, 10), True, 60000,
        [ev("A", 30), ev("B", 99), ev("A", 31), ev("B", 99), ev("A", 32),
         ev("A", 40), ev("A", 41), ev("A", 42), ev("B", 99)],
        [(1080, 30.0, 42.0, 99.0), (1080, 40.0, 42.0, 99.0)]),
    "rule_2_waiting_and_filling_absorb_the_same_a": (
        (3, 10), True, 60000,
        [ev("A", 30), ev("A", 31), ev("A", 32), ev("A", 40), ev("B", 35),
         ev("A", 41), ev("A", 42), ev("B", 99)],
        [(1040, 30.0, 40.0, 35.0), (1070, 40.0, 42.0, 99.0)]),
    "rule_3_one_b_closes_every_chain_it_satisfies": (
        (3, 10), True, 60000,
        [ev("A", 60), ev("A", 31), ev("A", 32), ev("A", 30), ev("A", 41),
         ev("A", 42), ev("B", 50), ev("B", 99)],
        [(1060, 30.0, 42.0, 50.0), (1070, 60.0, 42.0, 99.0)]),
    "rule_4_a_closed_chain_is_gone": (
        (3, 10), True, 60000,
        [ev("A", 30), ev("A", 31), ev("A", 32), ev("B", 99), ev("B", 99)],
        [(1030, 30.0, 32.0, 99.0)]),
    "rule_5_an_a_past_max_or_a_b_too_low_changes_nothing": (
        (3, 4), True, 60000,
        [ev("A", 30), ev("A", 31), ev("A", 32), ev("A", 33), ev("A", 34),
         ev("B", 20), ev("B", 99)], [(1060, 30.0, 33.0, 99.0)]),
}


@pytest.mark.parametrize("case", sorted(PLANTED))
@pytest.mark.parametrize("engine", ["gang", "host"])
def test_planted_cases_on_both_engines(case, engine):
    (lo, hi), every, within, events, want = PLANTED[case]
    n = len(events)
    cols = {"sym": np.zeros(n, np.int64),
            "price": np.asarray([e[1] for e in events], np.float32),
            "kind": np.asarray([e[0] for e in events])}
    ts = np.asarray([e[2] if e[2] is not None else 1000 + 10 * i
                     for i, e in enumerate(events)], np.int64)
    args = ref_args(20.0, lo, hi, within, every)
    rnd = lambda rows: [(k, t) + tuple(round(p, 1) for p in ps)
                        for k, t, *ps in rows]
    for ref in (REF.run, REF.run_loop):
        assert rnd(table(ref(cols, ts, args))) == \
            sorted((0,) + w for w in want)
    s = Serving(app_text(f"kplant_{engine}_{case[:12]}",
                         engine="host" if engine == "host" else None,
                         thr=20.0, lo=lo, hi=hi, within=within,
                         every=every))
    s.send(cols, ts, 1)                  # event by event
    s.rt.flush()
    one_by_one, s.rows = rnd(s.rows), []
    backend = s.backend()
    s.shutdown()
    assert backend == engine
    assert one_by_one == [(0,) + w for w in want]


# ------------------------------------------------------------ the counters

def test_pack_counters_against_a_block_counted_by_hand():
    """Ten events on three keys, the fullest with four: one block of
    T = 4 over the 16 declared lanes, 64 cells for 10 events; then five
    events on one key: T = 8 (five rounded up), 128 cells."""
    name = "kpack"
    before = counters(name, PACK_COUNTERS)
    s = Serving(app_text(name, lanes=16))
    cols = {"sym": np.asarray([0, 1, 0, 2, 0, 1, 2, 0, 1, 2]),
            "price": np.full(10, 90.0, np.float32),
            "kind": np.zeros(10, np.int64)}
    s.send(cols, 1000 + np.arange(10))
    s.rt.flush()
    first = counters(name, PACK_COUNTERS) - before
    cols = {"sym": np.zeros(5, np.int64),
            "price": np.full(5, 90.0, np.float32),
            "kind": np.zeros(5, np.int64)}
    s.send(cols, 2000 + np.arange(5))
    s.rt.flush()
    both = counters(name, PACK_COUNTERS) - before
    lanes = s.nfa().n_partitions
    s.shutdown()
    assert lanes == 16
    assert first.tolist() == [10, 16 * 4]
    assert both.tolist() == [15, 16 * 4 + 16 * 8]


def test_counters_are_on_every_surface_and_absent_without_a_count_unit():
    from siddhi_tpu.core.statistics import LEDGER_TYPES
    cols, ts, _want, stats = seeded(1500)
    s = Serving(app_text("ksurf", lanes=KEYS))
    s.send(cols, ts, 500)
    s.rt.flush()
    s.shutdown()
    entry = ledger().snapshot("ksurf")["apps"]["ksurf"]
    assert [entry[k] for k in COUNT_COUNTERS] == tally(stats)
    assert entry["pack_events_total"] == 1500
    assert entry["pack_cells_total"] >= 3 * 64
    text = "\n".join(ledger().prometheus_lines())
    for k, v in zip(COUNT_COUNTERS, tally(stats)):
        assert f'siddhi_{k}{{app="ksurf"}} {v}' in text
    assert 'siddhi_pack_events_total{app="ksurf"} 1500' in text
    assert {f"siddhi_{k}" for k in COUNT_COUNTERS + PACK_COUNTERS} <= \
        {name for name, _kind, _text in LEDGER_TYPES}
    # an automaton without a count unit has neither the leaf nor the row
    plain = ("@app:name('kplain') @app:playback\n"
             "define stream S (sym string, price float, kind int);\n"
             "partition with (sym of S) begin\n@info(name='q0')\n"
             "from every e1=S[kind == 0] -> e2=S[kind == 1] within 1 sec\n"
             "select e1.sym as sym, e1.price as p0, e1.price as pl, "
             "e2.price as p2 insert into Out0;\nend;\n")
    s = Serving(plain)
    s.send(cols, ts, 500)
    s.rt.flush()
    nfa = s.nfa()
    assert "count_ctr" not in nfa.carry and not nfa.has_count
    s.shutdown()
    entry = ledger().snapshot("kplain")["apps"]["kplain"]
    assert not set(COUNT_COUNTERS) & set(entry)
    assert entry["pack_events_total"] == 1500

"""One block per partition and send (PR 39): the pattern queries of a
partition receive the same chunk, so its dense ``[P, T]`` planes are
scattered once for all of them (``ops/nfa.SharedPlanes``, kept on the
chunk) and the gang step (``plan/xtenant.py``) uploads each distinct
array once.

Sharing is observed, never assumed: a plane is another query's only where
what it is scattered from is the same object or compares equal.  So the
rows are held to the benchmark's plain references whatever is shared
(four equal queries; one that reads a column more; one whose lanes differ;
one that overflows its slots alone; an absent bank with a clock per
tenant; tenants of two apps, which share nothing), and the blocks the gang
was handed are looked at, array by array.
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.ledger import ABSENT_COUNTERS, PLANE_COUNTERS, ledger
from siddhi_tpu.ops.nfa import CLOCK_KEY, SharedPlanes, pack_blocks
from siddhi_tpu.plan import xtenant
from siddhi_tpu.plan.xtenant import tenant_packer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = 48
THRESHOLDS = (50.0, 60.25, 70.5, 80.75)
PLAIN = ("from every e1=S[kind == 0 and price > {thr}] "
         "-> e2=S[kind == 1 and price > e1.price] within 1 sec\n"
         "select e1.sym as sym, e1.price as p1, e2.price as p2 "
         "insert into Out{q};\n")
ABSENT = ("from every e1=S[kind == 0 and price > {thr}] "
          "-> e2=S[kind == 1 and price > e1.price] "
          "-> not S[kind == 2] for 1 sec within 2 sec\n"
          "select e1.sym as sym, e1.price as p1, e2.price as p2 "
          "insert into Out{q};\n")


def _reference(name):
    path = os.path.join(REPO, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("every_a_then_b_within")
REF_ABSENT = _reference("every_a_then_b_not_c_for")


def ref_args(thresholds, **more):
    return dict({"key": "sym", "kind": "kind", "price": "price",
                 "a_kind": 0, "b_kind": 1, "within_ms": 1000,
                 "out": ["p1", "p2"], "out_key": "sym",
                 "queries": [{"a_price_gt": t} for t in thresholds]}, **more)


def app_text(name, queries, engine=None, async_=True):
    """queries: [(template, threshold)]; query q inserts into Out<q>.
    Under `@Async`, as the deployments run, the queries' blocks of a
    chunk wait for one gang call; without it every query's ingest steps
    its own."""
    body = "".join(f"@info(name='q{q}')\n" + tpl.format(thr=thr, q=q)
                   for q, (tpl, thr) in enumerate(queries))
    return ((f"@app:engine('{engine}') " if engine else "") +
            f"@app:name('{name}') @app:playback\n" +
            ("@Async(buffer.size='64', batch.size.max='65536')\n"
             if async_ else "") +
            "define stream S (sym string, price float, kind int, "
            "tag string);\n" +
            "partition with (sym of S) begin\n" + body + "end;\n")


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    """The served path of one chip: every pattern automaton a tenant of
    the gang step, the gang empty when a test starts."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    packer = tenant_packer()
    for row in list(packer.buckets.values()):
        for bucket in list(row):
            for nfa in list(bucket.tenants):
                packer.evict(nfa)


@pytest.fixture
def flushes(monkeypatch):
    """Every gang flush of the test, as it was handed to the step:
    [[(automaton, block), ...], ...]."""
    seen = []
    step = xtenant.TenantBucket._gang_step

    def spy(self, entries):
        seen.append([(e[0], dict(e[1])) for e in entries])
        return step(self, entries)
    monkeypatch.setattr(xtenant.TenantBucket, "_gang_step", spy)
    return seen


class Serving:
    """One running app with a collecting callback per output stream."""

    def __init__(self, text, n_queries):
        self.rt = SiddhiManager().create_siddhi_app_runtime(text)
        self.name = self.rt.name
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

    def send(self, cols, ts, cut=None):
        n = len(ts)
        names = np.asarray([f"k{i}" for i in range(KEYS)], object)
        handler = self.rt.get_input_handler("S")
        for i in range(0, n, cut or n):
            sl = slice(i, i + (cut or n))
            handler.send_batch(
                {"sym": names[cols["sym"][sl]], "price": cols["price"][sl],
                 "kind": cols["kind"][sl], "tag": cols["tag"][sl]},
                timestamps=ts[sl])
            self.rt.flush()     # a chunk per send, under @Async too

    def runtimes(self):
        """{query: its device pattern runtime}"""
        return {name: qr.device_runtime
                for pr in self.rt.partition_runtimes
                for name, qr in sorted(pr.device_query_runtimes.items())}

    def planes(self):
        snap = ledger().snapshot(self.name)["apps"].get(self.name, {})
        return [snap.get(k, 0) for k in PLANE_COUNTERS]

    def shutdown(self):
        runtimes = self.runtimes()
        self.rt.shutdown()
        # a partition's device queries are not shut down with their app:
        # take their automata out of the process-wide gang
        for dr in runtimes.values():
            tenant_packer().evict(dr.nfa)


def stream(seed, n, kinds=2, rate=330):
    rng = np.random.default_rng(seed)
    cols = {"sym": rng.integers(0, KEYS, n),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, kinds, n),
            "tag": np.asarray(["a", "b"], object)[rng.integers(0, 2, n)]}
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


def by_key(rows):
    """{(query, key): its rows in delivery order}"""
    out = {}
    for r in rows:
        out.setdefault(r[:2], []).append(r)
    return out


def dense(block):
    """A block's ``[P, T]`` planes (all but an absent automaton's
    ``[P, 1]`` clock)."""
    return {k: v for k, v in block.items() if k != CLOCK_KEY}


def chunks(flushes, n_queries):
    """The flushes' (automaton, block) entries, a list per chunk: one
    gang call of all the queries under `@Async`, a call per query
    without."""
    flat = [entry for flush in flushes for entry in flush]
    assert len(flat) % n_queries == 0
    return [flat[i:i + n_queries] for i in range(0, len(flat), n_queries)]


def held_by(entries):
    """How many of the entries' blocks hold each distinct plane."""
    count = {}
    for _nfa, block in entries:
        for arr in dense(block).values():
            count[id(arr)] = count.get(id(arr), 0) + 1
    return sorted(count.values())


# ------------------------------------------------------------ the memo alone

def _batch(seed=5, n=600, lanes=64):
    rng = np.random.default_rng(seed)
    pids = rng.integers(0, 40, n)
    cols = {"price": rng.uniform(0, 100, n).astype(np.float32),
            "sym": rng.integers(1, 41, n).astype(np.float32)}
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    return pids, cols, ts, np.zeros(n, np.int32), lanes


def test_a_second_pack_of_equal_inputs_gets_the_planes_that_are_there():
    pids, cols, ts, codes, lanes = _batch()
    shared = SharedPlanes()
    first, found = shared.pack(pids, cols, ts, codes, lanes,
                               base_ts=1_000_000, pad_t_pow2=True)
    assert found == 0 and sorted(first) == [
        "__stream", "__ts", "__valid", "price", "sym"]
    # equal, not the same: the lanes and the codes of a second query are
    # gathers of its own
    again, found = shared.pack(pids.copy(), {k: v.copy()
                                             for k, v in cols.items()},
                               ts, codes.copy(), lanes, base_ts=1_000_000,
                               pad_t_pow2=True)
    assert found == 5 and again is not first
    for name, plane in first.items():
        assert again[name] is plane and not plane.flags.writeable
    alone = pack_blocks(pids, cols, ts, codes, lanes, base_ts=1_000_000,
                        pad_t_pow2=True)
    for name, plane in alone.items():
        assert plane.flags.writeable and plane.dtype == first[name].dtype
        assert np.array_equal(plane, first[name])


@pytest.mark.parametrize("what", ["column", "one_more_column", "base_ts",
                                  "stream", "lanes", "n_partitions",
                                  "pad_t_pow2"])
def test_an_input_that_differs_gets_a_plane_of_its_own(what):
    pids, cols, ts, codes, lanes = _batch()
    shared = SharedPlanes()
    first, _ = shared.pack(pids, cols, ts, codes, lanes, base_ts=1_000_000,
                           pad_t_pow2=True)
    base_ts, pad, own = 1_000_000, True, set()
    cols = dict(cols)
    if what == "column":
        cols["sym"] = cols["sym"] + 1        # another dictionary
        own = {"sym"}
    elif what == "one_more_column":
        cols["vol"] = np.ones(len(pids), np.float32)
        own = {"vol"}
    elif what == "base_ts":
        base_ts = 999_000                    # a rebase the others made not
        own = {"__ts"}
    elif what == "stream":
        codes = codes + 1
        own = {"__stream"}
    elif what == "lanes":
        pids = (pids + 1) % 40               # a key map of another order
        own = set(first)
    elif what == "n_partitions":
        lanes = 128                          # grown, the others not
        own = set(first)
    else:
        pad = False
        own = set(first)
    mine, found = shared.pack(pids, cols, ts, codes, lanes, base_ts=base_ts,
                              pad_t_pow2=pad)
    assert found == len(mine) - len(own)
    for name, plane in mine.items():
        assert (plane is first.get(name)) == (name not in own), name
    alone = pack_blocks(pids, cols, ts, codes, lanes, base_ts=base_ts,
                        pad_t_pow2=pad)
    assert sorted(alone) == sorted(mine)
    for name, plane in alone.items():
        assert np.array_equal(plane, mine[name]), name
    # and what the first caller holds is as it was
    assert np.array_equal(first["__ts"],
                          pack_blocks(*_batch()[:4], _batch()[4],
                                      base_ts=1_000_000,
                                      pad_t_pow2=True)["__ts"])


# ------------------------------------------------------------ four queries

@functools.lru_cache(maxsize=None)
def seeded(n, thresholds=THRESHOLDS):
    cols, ts = stream(20261005, n)
    return cols, ts, table(REF.run(cols, ts, ref_args(thresholds)))


@pytest.mark.parametrize("async_", [False, True], ids=["sync", "async"])
def test_four_queries_of_a_partition_hold_one_block(flushes, async_):
    cols, ts, want = seeded(4000)
    s = Serving(app_text(f"shared4_{async_}",
                         [(PLAIN, t) for t in THRESHOLDS], async_=async_),
                4)
    before = s.planes()
    s.send(cols, ts, 1000)
    planes = np.subtract(s.planes(), before)
    s.shutdown()
    assert sorted(s.rows) == want and in_key_order(s.rows)
    host = Serving(app_text(f"shared4_host_{async_}",
                            [(PLAIN, t) for t in THRESHOLDS],
                            engine="host", async_=False), 4)
    host.send(cols, ts, 1000)
    host.shutdown()
    # row for row and in order, query by query and key by key
    assert by_key(s.rows) == by_key(host.rows)
    assert [len(f) for f in flushes] == ([4] * 4 if async_ else [1] * 16)
    for entries in chunks(flushes, 4):
        assert len({id(nfa) for nfa, _block in entries}) == 4
        blocks = [block for _nfa, block in entries]
        assert len({id(b) for b in blocks}) == 4     # a dict each
        for name, plane in blocks[0].items():
            assert plane.shape[0] == 64 and not plane.flags.writeable
            assert all(b[name] is plane for b in blocks), name
        assert sorted(blocks[0]) == ["__stream", "__ts", "__valid",
                                     "kind", "price", "sym"]
    # (g) per chunk 24 planes, 18 of them made by an earlier query
    assert planes.tolist() == [4 * 24, 4 * 18]


def test_one_query_shares_nothing(flushes):
    cols, ts, want = seeded(2000, THRESHOLDS[:1])
    s = Serving(app_text("shared1", [(PLAIN, THRESHOLDS[0])]), 1)
    before = s.planes()
    s.send(cols, ts, 1000)
    planes = np.subtract(s.planes(), before)
    s.shutdown()
    assert sorted(s.rows) == want
    assert [len(f) for f in flushes] == [1, 1]
    assert planes.tolist() == [2 * 6, 0]


@pytest.mark.parametrize("test,own,held,found", [
    # a string order test is a 0/1 lane computed on the host: one plane
    # more, the dictionary as the others'
    ("tag > 'a'", 1, [1, 4, 4, 4, 4, 4, 4], 18),
    # an equality test puts `tag`'s values into the automaton's one
    # dictionary, so its codes of `sym` are no longer the others'
    ("tag == 'b'", 2, [1, 1, 3, 4, 4, 4, 4, 4], 17)],
    ids=["order", "equality"])
def test_a_query_that_reads_one_more_column_owns_that_plane(
        flushes, test, own, held, found):
    cols, ts = stream(20261006, 3000)
    want = table(REF.run(cols, ts, ref_args(THRESHOLDS[:3])))
    # the fourth query's rows: the reference's over the events that pass
    # its B's second test, an A being kind 0 and so never cut
    keep = (cols["kind"] == 0) | (cols["tag"] == "b")
    sub = {k: v[keep] for k, v in cols.items()}
    want += [(3,) + r[1:] for r in table(REF.run(
        sub, ts[keep], ref_args(THRESHOLDS[3:])))]
    with_tag = PLAIN.replace("price > e1.price",
                             f"price > e1.price and {test}")
    s = Serving(app_text(f"shared_tag_{own}",
                         [(PLAIN, t) for t in THRESHOLDS[:3]]
                         + [(with_tag, THRESHOLDS[3])]), 4)
    before = s.planes()
    s.send(cols, ts, 1500)
    planes = np.subtract(s.planes(), before)
    s.shutdown()
    assert sorted(s.rows) == sorted(want) and in_key_order(s.rows)
    assert [len(f) for f in flushes] == [4, 4]
    for flush in flushes:
        blocks = [block for _nfa, block in flush]
        assert [len(b) for b in blocks] == [6, 6, 6, 7]
        mine = [name for name, plane in blocks[3].items()
                if plane is not blocks[0].get(name)]
        assert len(mine) == own and not set(mine) & {
            "__stream", "__ts", "__valid", "kind", "price"}
        assert held_by(flush) == held
    # per chunk 6 + 6 + 6 + 7 planes: the first query made 6
    assert planes.tolist() == [2 * 25, 2 * found]


def test_lanes_that_differ_fall_back_to_a_block_of_their_own(flushes):
    cols, ts, want = seeded(4000)
    s = Serving(app_text("shared_lanes", [(PLAIN, t) for t in THRESHOLDS]),
                4)
    # the third query has met the keys before, in another order (as after
    # a restore of its state alone): its lanes are not the others'
    odd = s.runtimes()["q2"]
    for i in reversed(range(KEYS)):
        odd.key_lanes[f"k{i}"] = len(odd.key_lanes)
    before = s.planes()
    s.send(cols, ts, 2000)
    planes = np.subtract(s.planes(), before)
    s.shutdown()
    assert sorted(s.rows) == want and in_key_order(s.rows)
    assert len(flushes) == 2
    for flush in flushes:
        own = [block for nfa, block in flush if nfa is odd.nfa]
        rest = [block for nfa, block in flush if nfa is not odd.nfa]
        assert len(own) == 1 and len(rest) == 3
        for name, plane in rest[0].items():
            assert all(b[name] is plane for b in rest)
            assert own[0][name] is not plane
        assert not np.array_equal(own[0]["__valid"], rest[0]["__valid"])
        assert held_by(flush) == [1] * 6 + [3] * 6
    assert planes.tolist() == [2 * 24, 2 * 12]


def test_slot_overflow_rewinds_one_of_four_sharing_tenants(flushes):
    """One key's burst of A events overflows the 8 slots of the query
    with the lowest threshold alone: it is rewound, grown and replayed
    from its own complete block, and all four give the reference's
    rows."""
    thresholds = (1.0, 97.0, 98.0, 99.0)
    n = 240
    cols = {"sym": np.arange(n) % 4,
            "price": np.linspace(2.0, 96.0, n).astype(np.float32),
            "kind": np.zeros(n, np.int64),
            "tag": np.full(n, "a", object)}
    cols["kind"][-8:] = 1               # the Bs that complete them
    cols["price"][-8:] = 99.5
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    want = table(REF.run(cols, ts, ref_args(thresholds)))
    assert len(want) > 100
    s = Serving(app_text("shared_grow", [(PLAIN, t) for t in thresholds]),
                4)
    runtimes = s.runtimes()
    s.send(cols, ts, 120)
    slots = {name: dr.nfa.spec.n_slots for name, dr in runtimes.items()}
    s.shutdown()
    assert sorted(s.rows) == want and in_key_order(s.rows)
    assert slots["q0"] > 8 and \
        [slots[q] for q in ("q1", "q2", "q3")] == [8, 8, 8]
    # the block q0 was replayed from was the one all four had held
    first = flushes[0]
    assert len(first) == 4 and held_by(first) == [4] * 6


# ------------------------------------------------------------ an absent bank

def test_absent_bank_shares_the_block_and_keeps_a_clock_each(flushes):
    thresholds = THRESHOLDS[:3]
    cols, ts = stream(20261007, 3000, kinds=3)
    args = ref_args(thresholds, c_kind=2, within_ms=2000, for_ms=1000)
    stats = {}
    want = table(REF_ABSENT.run_loop(cols, ts, args, stats))
    s = Serving(app_text("shared_absent",
                         [(ABSENT, t) for t in thresholds]), 3)
    s.send(cols, ts, 1000)
    snap = ledger().snapshot("shared_absent")["apps"]["shared_absent"]
    s.shutdown()
    assert len(want) > 20
    assert sorted(s.rows) == want and in_key_order(s.rows)
    for flush in flushes:
        assert len(flush) == 3 and held_by(flush) == [3] * 6
        clocks = [block[CLOCK_KEY] for _nfa, block in flush]
        assert len({id(c) for c in clocks}) == 3
        assert all(c.shape == (64, 1) for c in clocks)
    # the deadlines fired inside the blocks, none by a host TIMER
    counted = dict(zip(ABSENT_COUNTERS, (snap[k] for k in ABSENT_COUNTERS)))
    assert counted["absent_fired_total"] >= len(want)
    assert counted["absent_timer_rows_total"] == 0
    assert counted["absent_fired_inblock_total"] == \
        counted["absent_fired_total"]


# ------------------------------------------------------------ two apps

def test_tenants_of_two_apps_share_nothing(flushes, monkeypatch):
    """Two apps' automata of one shape class are tenants of one bucket,
    and each holds a block of its own chunk: every plane is its own gang
    argument, as before there was anything to share."""
    calls = []
    build = xtenant._build_gang

    def spy(nfas, reads, trigger="build"):
        calls.append(reads)
        return build(nfas, reads, trigger=trigger)
    monkeypatch.setattr(xtenant, "_build_gang", spy)
    cols, ts = stream(20261008, 1200)
    args = ref_args(THRESHOLDS[:1])
    want = table(REF.run(cols, ts, args))
    text = ("@app:name('{name}') @app:playback @app:pipeline('4')\n"
            "define stream S (sym string, price float, kind int, "
            "tag string);\npartition with (sym of S) begin\n"
            "@info(name='q0')\n" + PLAIN.format(thr=THRESHOLDS[0], q=0) +
            "end;\n")
    a = Serving(text.format(name="two_apps_a"), 1)
    b = Serving(text.format(name="two_apps_b"), 1)
    names = np.asarray([f"k{i}" for i in range(KEYS)], object)
    for i in range(0, 1200, 400):
        sl = slice(i, i + 400)
        for s in (a, b):
            s.rt.get_input_handler("S").send_batch(
                {"sym": names[cols["sym"][sl]], "price": cols["price"][sl],
                 "kind": cols["kind"][sl], "tag": cols["tag"][sl]},
                timestamps=ts[sl])
    for s in (a, b):
        s.rt.flush()
        s.shutdown()
        assert sorted(s.rows) == want
    both = [f for f in flushes if len(f) == 2]
    assert both, [len(f) for f in flushes]
    for flush in both:
        assert held_by(flush) == [1] * 12
    assert any(reads == tuple(
        tuple((name, 6 * i + j) for j, name in enumerate(
            ["__stream", "__ts", "__valid", "kind", "price", "sym"]))
        for i in range(2)) for reads in calls), calls


def test_counters_are_on_every_surface():
    from siddhi_tpu.core.statistics import LEDGER_TYPES
    cols, ts, _want = seeded(4000)
    s = Serving(app_text("shared_surf", [(PLAIN, t) for t in THRESHOLDS]),
                4)
    s.send(cols, ts, 2000)
    s.shutdown()
    entry = ledger().snapshot("shared_surf")["apps"]["shared_surf"]
    assert [entry[k] for k in PLANE_COUNTERS] == [48, 36]
    text = "\n".join(ledger().prometheus_lines())
    assert 'siddhi_pack_planes_total{app="shared_surf"} 48' in text
    assert 'siddhi_pack_planes_shared_total{app="shared_surf"} 36' in text
    assert {f"siddhi_{k}" for k in PLANE_COUNTERS} <= \
        {name for name, _kind, _text in LEDGER_TYPES}

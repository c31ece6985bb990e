"""A block's partition keys are factored once for all the queries of a
partition (PR 33; core/keyfactor.py, `_PartitionExecutor.factor`).

The first query of a partition that meets a chunk factors its keys and
leaves the product on the chunk; the others take it.  What every query
then does with it (the lane lookup, the string dictionary) works over the
distinct keys and gathers to events.  So: the rows are the host engine's
at any number of queries, cut and junction; the counters say who made and
who found a factor; lanes are handed out in the parent's order, which a
frozen copy of the parent's code holds; snapshots decode the same strings
whichever order their dictionary was filled in.

Since PR 37 the product is per-event ids of an interner that lives as
long as the partition (one dict probe per event; `KeyIds`), and a key's
lane and dictionary code are gathers from tables by id.  So besides: a
key has one id in whatever form and from whichever stream it comes;
values that are no strings never find a string's id; new keys mid-stream
get the parent's lanes and codes, also where the partition already knew
them from another stream, also across a restore; two threads admit a new
key once; and the two `key_intern_*` counters say how many events the
probe answered.
"""
import functools
import sys
import threading

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.event import EventChunk
from siddhi_tpu.core.keyfactor import (KeyIds, KeyInterner, column_factor,
                                       factor_keys, factor_values)
from siddhi_tpu.core.ledger import (KEY_FACTOR_COUNTERS, KEY_INTERN_COUNTERS,
                                    ledger)
from siddhi_tpu.plan.planner import KeyLanes, map_keys_to_lanes

KEYS = 48
STREAM = "define stream S (sym string, price float, kind int);\n"
PATTERN = ("from every e1=S[kind == 0 and price > {thr}] "
           "-> e2=S[kind == 1 and price > e1.price] within 1 sec\n"
           "select e1.sym as sym, e1.price as p1, e2.price as p2 "
           "insert into Out{q};\n")
LENGTH = ("from S[price > {thr}]#window.length(5)\n"
          "select sym, sum(price) as p1, count() as p2 "
          "group by sym insert into Out{q};\n")
KINDS = {"pattern": PATTERN, "length": LENGTH}


def app_text(name, kind, n_queries, engine=None, async_=False, by="sym",
             stream=STREAM):
    body = "".join(f"@info(name='q{q}')\n" +
                   KINDS[kind].format(thr=40.0 + 5 * q, q=q)
                   for q in range(n_queries))
    return ((f"@app:engine('{engine}') " if engine else "") +
            f"@app:name('{name}') @app:playback\n" +
            ("@Async(buffer.size='64', batch.size.max='65536')\n"
             if async_ else "") + stream +
            f"partition with ({by} of S) begin\n" + body + "end;\n")


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    """The served path of one chip: pattern automata gang, nothing is
    mesh-sharded over conftest's 8 virtual devices."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


class Serving:
    """One running app with a collecting callback per output stream."""

    def __init__(self, text, n_queries):
        self.rt = SiddhiManager().create_siddhi_app_runtime(text)
        self.name = self.rt.name
        self.rows = []          # (q, key, ts, p1, p2) in delivery order
        for q in range(n_queries):
            self.rt.add_callback(f"Out{q}", ColumnarStreamCallback(
                functools.partial(self._receive, q)))
        self.rt.start()

    def _receive(self, q, chunk):
        c = chunk.columns
        for j, t in enumerate(chunk.timestamps):
            self.rows.append((q, str(c["sym"][j]), int(t),
                              float(c["p1"][j]), float(c["p2"][j])))

    def send(self, cols, ts, cut=None, to="S"):
        handler = self.rt.get_input_handler(to)
        n = len(ts)
        for i in range(0, n, cut or n):
            sl = slice(i, i + (cut or n))
            handler.send_batch({k: v[sl] for k, v in cols.items()},
                               timestamps=ts[sl])

    def device_runtimes(self):
        return {name: qr.device_runtime
                for pr in self.rt.partition_runtimes if pr.device_mode
                for name, qr in pr.device_query_runtimes.items()}

    def on_device(self):
        return bool(self.rt.partition_runtimes) and \
            all(pr.device_mode for pr in self.rt.partition_runtimes)

    def counters(self, family=KEY_FACTOR_COUNTERS):
        snap = ledger().snapshot(self.name)["apps"].get(self.name, {})
        return tuple(snap.get(k, 0) for k in family)

    def intern_counters(self):
        return self.counters(KEY_INTERN_COUNTERS)

    def finish(self):
        self.rt.flush()
        rows = sorted(self.rows)
        self.shutdown()
        return rows

    def shutdown(self):
        from siddhi_tpu.plan.xtenant import tenant_packer
        devs = self.device_runtimes()
        self.rt.shutdown()
        for dev in devs.values():
            if hasattr(dev, "nfa"):
                tenant_packer().evict(dev.nfa)


def stream(seed, n, keys=KEYS, names=None, rate=400):
    rng = np.random.default_rng(seed)
    if names is None:
        names = np.asarray([f"k{i}" for i in range(keys)], object)
    return ({"sym": names[rng.integers(0, len(names), n)],
             "price": rng.uniform(0, 100, n).astype(np.float32),
             "kind": rng.integers(0, 2, n)},
            1_000_000 + (np.arange(n) * 1000) // rate)


_APP = iter(range(10 ** 6))


def fresh(prefix):
    return f"{prefix}_{next(_APP)}"


@functools.lru_cache(maxsize=None)
def oracle(kind, n_queries, n):
    """The host engine's rows over the seeded stream, one send."""
    s = Serving(app_text(fresh("kf_host"), kind, n_queries, engine="host"),
                n_queries)
    assert not s.on_device()
    s.send(*stream(33, n))
    return s.finish()


def same_rows(got, want):
    """Exact but for `p1`, which a `length` window sums in f32 on the
    device and in f64 on the host."""
    return [r[:3] + r[4:] for r in got] == [r[:3] + r[4:] for r in want] \
        and np.allclose([r[3] for r in got], [r[3] for r in want],
                        rtol=1e-5, atol=0)


# ------------------------------------------------------- rows = the oracle

#: (runtime, queries, cut, @Async): every count at one whole send, every
#: cut and junction at 4 queries, the other counts at one more cut each
MATRIX = [(kind, q, cut, async_)
          for kind in ("pattern", "length")
          for (q, cut, async_) in (
              [(q, None, False) for q in (1, 4, 8)] +
              [(4, cut, async_) for cut in (1, 64, 1024)
               for async_ in (False, True)] +
              [(4, None, True), (1, 64, True), (8, 1024, True)])]


@pytest.mark.parametrize("kind,n_queries,cut,async_", MATRIX)
def test_rows_are_the_host_engines(kind, n_queries, cut, async_):
    n = 600 if cut == 1 else 2400
    want = oracle(kind, n_queries, n)
    assert len(want) > 20 * n_queries
    s = Serving(app_text(fresh("kf"), kind, n_queries, async_=async_),
                n_queries)
    assert s.on_device()
    s.send(*stream(33, n), cut=cut)
    asked, reused = None, None
    if not async_:
        asked, reused = s.counters()
    got = s.finish()
    assert same_rows(got, want)
    if not async_:
        sends = -(-n // (cut or n))
        assert (asked, reused) == (sends * n_queries,
                                   sends * (n_queries - 1))


# ------------------------------------------------------------ the counters

def _run(text, n_queries, cols_ts, cut=None):
    s = Serving(text, n_queries)
    s.send(*cols_ts, cut=cut)
    s.rt.flush()
    return s.counters(), sorted(s.rows), s


def test_one_query_of_a_partition_makes_every_factor_itself():
    (asked, reused), _rows, s = _run(
        app_text(fresh("kf"), "pattern", 1), 1, stream(5, 900), cut=300)
    s.shutdown()
    assert (asked, reused) == (3, 0)


@pytest.mark.parametrize("family,note,calls,want", [
    (KEY_FACTOR_COUNTERS, "note_key_factor",
     [(False,), (True,), (True,), (True,)], (4, 3)),
    (KEY_INTERN_COUNTERS, "note_key_intern",
     [(600, 0), (600, 600), (300, 299)], (1500, 899)),
], ids=["key_factor", "key_intern"])
def test_counters_are_on_the_snapshot_and_the_metrics_page(
        family, note, calls, want):
    from siddhi_tpu.core.ledger import LatencyLedger
    from siddhi_tpu.core.statistics import LEDGER_TYPES
    led = LatencyLedger()
    assert "kf" not in led.snapshot()["apps"]
    for args in calls:
        getattr(led, note)("kf", *args)
    assert "kf" in led.snapshot()["apps"]
    entry = led.snapshot("kf")["apps"]["kf"]
    assert tuple(entry[k] for k in family) == want
    text = "\n".join(led.prometheus_lines())
    for k, v in zip(family, want):
        assert f'siddhi_{k}{{app="kf"}} {v}' in text
    assert {f"siddhi_{k}" for k in family} <= \
        {name for name, _kind, _text in LEDGER_TYPES}
    led.reset()
    assert "kf" not in led.snapshot()["apps"]


def test_the_probe_answers_known_keys_and_not_new_ones():
    """`key_intern_events_total` counts a block's events once, whatever
    the number of queries; `key_intern_hits_total` all of them on a block
    of known keys (its null events too) and fewer on a block that brings
    a new key or whose values are no strings."""
    s = Serving(app_text(fresh("kf"), "pattern", 3), 3)

    later = iter(range(0, 10 ** 6, 10_000))

    def block(cols_ts, **edits):
        cols, ts = cols_ts
        before = np.asarray(s.intern_counters())
        s.send(dict(cols, **edits), ts + next(later))
        return tuple(np.asarray(s.intern_counters()) - before)

    assert s.intern_counters() == (0, 0)
    assert block(stream(41, 500)) == (500, 0)          # every key is new
    assert block(stream(42, 700)) == (700, 700)
    cols, ts = stream(43, 400)
    sym = cols["sym"].copy()
    sym[::9] = None
    assert block((cols, ts), sym=sym) == (400, 400)
    sym = cols["sym"].copy()
    sym[7] = sym[300] = "a new key"
    assert block((cols, ts), sym=sym) == (400, 398)
    assert block((cols, ts), sym=sym) == (400, 400)
    sym[5] = 17                     # no string: the per-event way
    assert block((cols, ts), sym=sym) == (400, 0)
    asked, reused = s.counters()
    assert (asked, reused) == (6 * 3, 6 * 2)
    for dev in s.device_runtimes().values():
        assert len(dev.key_lanes) == KEYS + 2 and "17" in dev.key_lanes
    s.shutdown()


def test_a_pattern_outside_a_partition_asks_for_no_factor():
    """P = 1: one lane, no key, no ask; its string column is still
    factored once per chunk for the two queries that encode it, and
    their block's planes are kept beside it (ops/nfa.SharedPlanes)."""
    name = fresh("kf")
    text = (f"@app:name('{name}') @app:playback\n" + STREAM +
            "".join(f"@info(name='q{q}')\n" + PATTERN.format(thr=90, q=q)
                    for q in range(2)))
    s = Serving(text, 2)
    seen = []
    s.rt.add_callback("S", ColumnarStreamCallback(seen.append))
    s.send(*stream(6, 500))
    s.rt.flush()
    assert s.counters() == (0, 0)
    assert len(s.rows) > 0
    assert [set(c.factors) for c in seen] == [{("col", "sym"), "planes"}]
    s.rt.shutdown()


def test_two_partitions_over_one_stream_share_nothing():
    """Each partition has its executor: a chunk factored by one is not
    factored for the other, whatever their key expressions."""
    name = fresh("kf")
    text = (f"@app:name('{name}') @app:playback\n" + STREAM +
            "partition with (sym of S) begin\n" +
            "".join(f"@info(name='q{q}')\n" + PATTERN.format(thr=40, q=q)
                    for q in (0, 1)) + "end;\n" +
            "partition with (kind of S) begin\n" +
            "".join(f"@info(name='q{q}')\n" + LENGTH.format(thr=40, q=q)
                    for q in (2, 3)) + "end;\n")
    s = Serving(text, 4)
    assert s.on_device()
    s.send(*stream(7, 1200), cut=400)
    s.rt.flush()
    assert s.counters() == (3 * 4, 3 * 2)
    lanes = {n: dict(d.key_lanes) for n, d in s.device_runtimes().items()}
    assert lanes["q0"] == lanes["q1"] and len(lanes["q0"]) == KEYS
    assert lanes["q2"] == lanes["q3"] == {"0": 0, "1": 1}
    assert {r[0] for r in s.rows} == {0, 1, 2, 3}
    s.shutdown()


# ------------------------------------------------- null keys, growth, ints

def test_a_block_with_null_keys_drops_them_and_shares_the_factor():
    """The executor's factor carries the mask; every query takes its own
    masked copy of the chunk (a copy is no shared object: its string
    columns are factored per query), and the rows are the host's."""
    cols, ts = stream(8, 1500)
    cols["sym"] = cols["sym"].copy()
    cols["sym"][::7] = None
    want = _run(app_text(fresh("kf_host"), "pattern", 2, engine="host"),
                2, (cols, ts))
    want[2].shutdown()
    (asked, reused), rows, s = _run(app_text(fresh("kf"), "pattern", 2), 2,
                                    (cols, ts), cut=500)
    assert rows == want[1] and len(rows) > 20
    assert (asked, reused) == (6, 3)
    for dev in s.device_runtimes().values():
        assert None not in dev.key_lanes and "None" not in dev.key_lanes
        assert "None" not in dev.nfa.str_encoder
    s.shutdown()


@pytest.mark.parametrize("kind", ["pattern", "length"])
def test_new_keys_grow_the_slab_with_blocks_in_flight(kind):
    """8 lanes to begin with; every send brings keys the slab has no lane
    for, under `@Async` with earlier blocks still in flight: the rows stay
    the host's and every query ends with the same lanes."""
    n, keys = 3000, 200
    rng = np.random.default_rng(9)
    names = np.asarray([f"k{i}" for i in range(keys)], object)
    upto = np.minimum(keys, 10 + (np.arange(n) // 300) * 25)
    cols = {"sym": names[(rng.random(n) * upto).astype(int)],
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 2, n)}
    ts = 1_000_000 + (np.arange(n) * 1000) // 2000
    want = _run(app_text(fresh("kf_host"), kind, 4, engine="host"), 4,
                (cols, ts))
    want[2].shutdown()
    _c, rows, s = _run(app_text(fresh("kf"), kind, 4, async_=True), 4,
                       (cols, ts), cut=300)
    assert same_rows(rows, want[1]) and len(rows) > 100
    lanes = [dict(d.key_lanes) for d in s.device_runtimes().values()]
    assert all(m == lanes[0] for m in lanes) and len(lanes[0]) > 150
    s.shutdown()


@pytest.mark.parametrize("kind,by,stream_def,values", [
    ("length", "kind", STREAM, "int"),
    ("length", "acct", "define stream S (sym string, price float, kind int, "
                       "acct long);\n", "int"),
    ("pattern", "sym", STREAM, "U"),
    ("pattern", "sym", STREAM, "mixed"),
    ("length", "sym", STREAM, "mixed"),
], ids=["int-kind", "long-acct", "U-sym", "mixed-sym-pattern",
        "mixed-sym-length"])
def test_integer_and_stringified_keys(kind, by, stream_def, values):
    """Keys of an int column are its values' `str()`; a `U` array and an
    object array of mixed types (which goes event by event) give the rows
    of the object array of strings."""
    cols, ts = stream(10, 1300)
    if by == "acct":
        cols["acct"] = np.random.default_rng(11).integers(-5, 120, len(ts))
    if values == "U":
        cols["sym"] = cols["sym"].astype("U")
    if values == "mixed":
        cols["sym"] = cols["sym"].copy()
        cols["sym"][::5] = [int(k[1:]) for k in cols["sym"][::5]]
    host = dict(cols)
    if values == "mixed":       # the same keys, as the strings they become
        host["sym"] = np.asarray([str(k) for k in cols["sym"]], object)
    want = _run(app_text(fresh("kf_host"), kind, 2, engine="host",
                         by=by, stream=stream_def), 2, (host, ts))
    want[2].shutdown()
    (asked, reused), rows, s = _run(
        app_text(fresh("kf"), kind, 2, by=by, stream=stream_def), 2,
        (cols, ts), cut=650)
    assert s.on_device()
    got = [(q, str(k), t, p1, p2) for q, k, t, p1, p2 in rows]
    assert same_rows(sorted(got), want[1]) and len(rows) > 100
    assert (asked, reused) == (4, 2)
    for dev in s.device_runtimes().values():
        assert all(type(k) is str for k in dev.key_lanes)
        if by != "sym":
            assert set(dev.key_lanes) == {str(v) for v in set(
                cols[by].tolist())}
    s.shutdown()


# --------------------------------------- the parent's lanes and snapshots

def _parent_keys(arr):
    """`_PartitionExecutor.keys` as PR 32 had it, for a value array."""
    return [None if x is None else str(x) for x in
            (x.item() if isinstance(x, np.generic) else x for x in arr)]


def _parent_map_keys_to_lanes(key_lanes, keys, capacity, grow_fn):
    """`map_keys_to_lanes` as PR 32 had it, frozen here: lanes in the
    order of the sorted distinct keys of a batch over 64, of first sight
    otherwise."""
    arr = np.asarray(keys)
    if arr.dtype.kind in "USiu" and len(keys) > 64:
        uniq, inv = np.unique(arr, return_inverse=True)
        lane_of = None
        if isinstance(key_lanes, KeyLanes):
            lane_of = key_lanes.lookup(uniq)
        if lane_of is None:
            lane_of = np.empty(len(uniq), np.int64)
            for i, k in enumerate(uniq.tolist()):
                lane = key_lanes.get(k)
                if lane is None:
                    lane = len(key_lanes)
                    key_lanes[k] = lane
                lane_of[i] = lane
        lanes = lane_of[inv.reshape(-1)]
    else:
        lanes = np.empty(len(keys), np.int64)
        for i, k in enumerate(keys):
            lane = key_lanes.get(k)
            if lane is None:
                lane = len(key_lanes)
                key_lanes[k] = lane
            lanes[i] = lane
    if key_lanes and len(key_lanes) > capacity:
        cap = capacity
        while cap < len(key_lanes):
            cap *= 2
        grow_fn(cap)
    return lanes


def _values(kind, rng, n):
    if kind == "str":
        return np.asarray([f"s{i}" for i in range(300)], object)[
            rng.integers(0, 300, n)]
    if kind == "str+null":
        v = _values("str", rng, n).copy()
        v[rng.random(n) < 0.1] = None
        return v
    if kind == "U":
        return _values("str", rng, n).astype("U")
    if kind == "S":
        return _values("str", rng, n).astype("S")
    if kind == "int":
        return rng.integers(-50, 250, n)
    if kind == "bool":
        return rng.integers(0, 2, n).astype(bool)
    if kind == "object-int":
        v = np.empty(n, object)
        v[:] = rng.integers(-50, 250, n).tolist()
        return v
    if kind == "float":
        return rng.integers(-3, 3, n) / 2.0            # 0.0 and -0.0 too
    if kind == "mixed":
        v = np.empty(n, object)
        v[:] = [(1, 1.0, "1", True, None, "x")[i] for i in
                rng.integers(0, 6, n)]
        return v
    raise KeyError(kind)


@pytest.mark.parametrize("kind", ["str", "str+null", "U", "S", "int", "bool",
                                  "object-int", "float", "mixed"])
@pytest.mark.parametrize("sizes", [(1, 3, 64, 65, 700, 40, 2000),
                                   (3000, 5, 5, 900)])
def test_lanes_are_handed_out_as_the_parent_did(kind, sizes):
    """The executor's factor through `map_keys_to_lanes` against the
    frozen parent over the same batches: the same lane for every event,
    the same map in the same order, the same growth."""
    from siddhi_tpu.core.partition import _PartitionExecutor
    ex = _PartitionExecutor.__new__(_PartitionExecutor)
    ex.ranges = None
    ex.value_expr = type("E", (), {"fn": staticmethod(
        lambda ctx: ctx.columns["k"])})()
    rng = np.random.default_rng(17)
    mine, theirs = KeyLanes(), KeyLanes()
    grown = ([], [])
    for n in sizes:
        vals = _values(kind, rng, n)
        chunk = EventChunk.from_columns(["k"], np.arange(n), {"k": vals})
        keys = _parent_keys(vals)
        assert ex.keys(chunk) == keys
        keep = np.asarray([k is not None for k in keys], bool)
        want = _parent_map_keys_to_lanes(
            theirs, [k for k in keys if k is not None], 8, grown[1].append)
        f, reused = ex.factor(chunk)
        assert not reused and ex.factor(chunk) == (f, True)
        assert (f.keep is None and keep.all()) or (f.keep == keep).all()
        assert f.keys().tolist() == [k for k in keys if k is not None]
        got = map_keys_to_lanes(mine, f, 8, grown[0].append)
        assert got.tolist() == want.tolist()
        assert list(mine.items()) == list(theirs.items())
    assert grown[0] == grown[1]
    # what went event by event is a list of renderings, not the column
    assert f.source == (None if kind in ("float", "mixed") else "k")
    assert f.raw_str == (kind in ("str", "str+null", "U", "float", "mixed"))


def _executor(interner=None):
    """A partition executor over the column `k`, outside any runtime."""
    from siddhi_tpu.core.partition import _PartitionExecutor
    ex = _PartitionExecutor.__new__(_PartitionExecutor)
    ex.ranges = None
    ex.interner = interner
    ex.value_expr = type("E", (), {"fn": staticmethod(
        lambda ctx: ctx.columns["k"])})()
    return ex


def _ids(ex, vals):
    f, _reused = ex.factor(EventChunk.from_columns(
        ["k"], np.arange(len(vals)), {"k": vals}))
    return f


@pytest.mark.parametrize("form", ["shared", "fresh", "U", "np.str_"])
def test_a_key_has_one_id_in_whatever_form_it_comes(form):
    """The probe hashes by value: the pool's own `str` objects, `str`
    objects made anew, a `U` array and `np.str_` values of an object
    array all find the id the key was given first."""
    ex = _executor()
    pool = [f"s{i:02d}" for i in range(90)]
    base = np.asarray(pool, object)
    first = _ids(ex, base)      # new keys get their ids in sorted order
    assert first.ids.tolist() == list(range(90)) and first.hits == 0
    pick = np.random.default_rng(23).integers(0, 90, 500)
    if form == "shared":
        vals = base[pick]
    elif form == "fresh":
        vals = np.empty(500, object)
        vals[:] = [("s%02d" % i).encode().decode() for i in pick]
        assert vals[0] is not pool[pick[0]]
    elif form == "U":
        vals = base[pick].astype("U")
    else:
        vals = np.empty(500, object)
        vals[:] = [np.str_(pool[i]) for i in pick]
        assert type(vals[0]) is np.str_
    f = _ids(ex, vals)
    assert f.ids.tolist() == pick.tolist() and f.hits == 500
    assert f.keep is None and f.raw_str and f.source == "k"
    assert f.keys().tolist() == [pool[i] for i in pick]
    assert len(ex.interner) == 90


@pytest.mark.parametrize("kind", ["int", "bool", "object-int", "float",
                                  "mixed", "S"])
def test_values_that_are_no_strings_never_find_a_strings_id(kind):
    """`"1"`, `"True"`, `"1.0"` are known keys; then `1`, `True`, `1.0`
    arrive.  Each finds the id of its `str()`, which is the key it had a
    lane for at the parent, and none the id of a string it merely equals
    or hashes like (`True == 1`); no such block counts a hit."""
    ex = _executor()
    known = np.asarray(["1", "True", "1.0", "x", "0", "0.0", "-0.0",
                        "False"] * 10, object)
    assert _ids(ex, known).hits == 0 and _ids(ex, known).hits == 80
    vals = _values(kind, np.random.default_rng(29), 300)
    f = _ids(ex, vals)
    want = _parent_keys(vals)
    assert f.hits == 0
    assert f.keys().tolist() == [k for k in want if k is not None]
    strings = ex.interner.strings
    assert len(set(strings)) == len(strings)
    assert all(type(k) is str for k in ex.interner._id_of if k is not None)
    if kind == "mixed":
        by_value = dict(zip(
            [repr(v) for v, k in zip(vals, want) if k is not None],
            f.ids.tolist()))
        assert by_value["1"] == by_value["'1'"] == strings.index("1")
        assert by_value["True"] == strings.index("True")
        assert by_value["1.0"] == strings.index("1.0")


def test_a_new_key_sent_by_two_threads_at_once_is_admitted_once():
    """Two threads probe blocks that bring the same new keys, round after
    round: every key gets one id, both threads read the same id for it,
    and an id's string is the key."""
    interner = KeyInterner()
    rounds, per_round = 8, 5000
    start = threading.Barrier(2)
    seen = ([], [])

    def prober(me):
        for r in range(rounds):
            vals = [f"r{r}_k{i}" for i in range(per_round)]
            if me:
                vals.reverse()
            start.wait(timeout=60)
            ids, _missed = interner.probe(vals)
            seen[me].append(dict(zip(vals, ids.tolist())))

    threads = [threading.Thread(target=prober, args=(me,)) for me in (0, 1)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    strings = interner.strings
    assert len(strings) == rounds * per_round == len(set(strings))
    for mine, theirs in zip(*seen):
        assert mine == theirs
        assert all(strings[i] == k for k, i in mine.items())


def test_factor_values_leaves_what_it_cannot_order():
    assert factor_values(np.asarray([0.0, -0.0])) is None
    mixed = np.empty(3, object)
    mixed[:] = [1, 1.0, "1"]
    assert factor_values(mixed) is None
    assert factor_values(np.asarray([1, 2]), strings_only=True) is None
    ints = np.empty(2, object)
    ints[:] = [7, 7]
    assert factor_values(ints, strings_only=True) is None
    f = factor_values(np.asarray(["b", None, "a", "b\0"], object))
    assert f.uniq.tolist() == ["a", "b"] and f.inv.tolist() == [1, -1, 0, 1]
    assert not f.raw_str       # "b\0" is not what `uniq` holds
    f = factor_keys([None, None])
    assert len(f.uniq) == 0 and f.inv.tolist() == [-1, -1]
    ids = KeyIds(KeyInterner(), f.inv, f.raw_str, None, 0)
    assert len(ids.ids) == 0 and ids.keep.tolist() == [False, False]
    assert ids.keys().tolist() == []
    empty = factor_values(np.empty(0, object))
    assert len(empty.uniq) == 0 and len(empty.inv) == 0


def test_a_string_column_is_factored_once_per_chunk():
    chunk = EventChunk.from_columns(
        ["a", "b"], np.arange(3),
        {"a": np.asarray(["x", None, "x"], object), "b": np.arange(3)})
    f = column_factor(chunk, "a")
    assert f.uniq.tolist() == ["x"] and f.inv.tolist() == [0, -1, 0]
    assert column_factor(chunk, "a") is f
    assert column_factor(chunk, "b") is None        # no string column
    assert column_factor(chunk, "nope") is None
    assert column_factor(chunk.mask(np.asarray([True, False, True])),
                         "a") is not f              # a copy: a miss


def _golden():
    import gzip
    import os
    import pickle
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "golden", "keyed_snapshot_pr32.pkl.gz")
    with gzip.open(path, "rb") as f:
        return pickle.load(f)


def _golden_app(gold):
    s = Serving(gold["app"], 4)
    assert s.on_device()
    return s


def _send_golden(s, gold, start, stop):
    for i in range(start, stop, gold["cut"]):
        sl = slice(i, i + gold["cut"])
        s.rt.get_input_handler("S").send_batch(
            {k: v[sl] for k, v in gold["cols"].items()},
            timestamps=gold["ts"][sl])
    s.rt.flush()


def _lanes(s):
    return {n: dict(d.key_lanes) for n, d in s.device_runtimes().items()}


def test_lane_maps_and_rows_are_the_parents_for_the_same_input():
    """tests/golden/keyed_snapshot_pr32.pkl.gz was written by PR 32's
    tree (tests/golden/make_keyed_snapshot.py): two pattern and two
    `length` queries of one partition, 2,400 events in sends of 600."""
    gold = _golden()
    s = _golden_app(gold)
    _send_golden(s, gold, 0, gold["snap_at"])
    assert _lanes(s) == gold["lanes_at_snap"]
    assert [list(m.items()) for m in _lanes(s).values()] == \
        [list(m.items()) for m in gold["lanes_at_snap"].values()]
    mine = s.rt.snapshot()
    _send_golden(s, gold, gold["snap_at"], len(gold["ts"]))
    assert _lanes(s) == gold["lanes"]
    assert same_rows(sorted(s.rows),
                     sorted(gold["rows_before"] + gold["rows_after"]))
    for name, dev in s.device_runtimes().items():
        if hasattr(dev, "nfa"):
            # the same strings have codes; the codes are this tree's own
            assert sorted(dev.nfa.str_decoder) == \
                sorted(gold["decoders"][name])
    s.shutdown()
    # a snapshot written before a block, restored after it, elsewhere
    t = _golden_app(gold)
    t.rt.restore(mine)
    assert _lanes(t) == gold["lanes_at_snap"]
    _send_golden(t, gold, gold["snap_at"], len(gold["ts"]))
    assert same_rows(sorted(t.rows), gold["rows_after"])
    t.shutdown()


def test_a_parent_written_snapshot_restores_and_is_extended():
    """The parent filled its dictionary in the order of the events; the
    blocks after the restore meet old and new strings, which take their
    codes in the order of each block's distinct values."""
    gold = _golden()
    s = _golden_app(gold)
    s.rt.restore(gold["snapshot"])
    assert _lanes(s) == gold["lanes_at_snap"]
    old = {n: list(d.nfa.str_decoder)
           for n, d in s.device_runtimes().items() if hasattr(d, "nfa")}
    assert old["q0"][:3] == gold["decoders"]["q0"][:3] and \
        old["q0"] != sorted(old["q0"])
    _send_golden(s, gold, gold["snap_at"], len(gold["ts"]))
    assert same_rows(sorted(s.rows), gold["rows_after"])
    assert _lanes(s) == gold["lanes"]
    for name, dev in s.device_runtimes().items():
        if hasattr(dev, "nfa"):
            assert dev.nfa.str_decoder[:len(old[name])] == old[name]
            assert len(dev.nfa.str_decoder) > len(old[name])
            assert sorted(dev.nfa.str_decoder) == \
                sorted(gold["decoders"][name])
    s.shutdown()


# ------------------------- new keys mid-stream, two streams of a partition

def _two_stream_app(name, engine=None):
    def on(text, sid):
        return text.replace("S[", sid + "[")

    return ((f"@app:engine('{engine}') " if engine else "") +
            f"@app:name('{name}') @app:playback\n" +
            STREAM.replace("stream S", "stream A") +
            STREAM.replace("stream S", "stream B") +
            "partition with (sym of A, sym of B) begin\n"
            "@info(name='q0')\n" + on(PATTERN, "A").format(thr=40.0, q=0) +
            "@info(name='q1')\n" + on(PATTERN, "B").format(thr=45.0, q=1) +
            "@info(name='q2')\n" + on(LENGTH, "A").format(thr=50.0, q=2) +
            "end;\n")


#: (stream, events, first and last key): B brings keys before any query
#: that reads A has met them; the third block is small enough (<= 64) for
#: lanes in the order of first sight; every block brings new keys
_BLOCKS = [("B", 300, 0, 40), ("A", 300, 20, 60), ("A", 50, 50, 80),
           ("B", 300, 30, 100), ("A", 400, 0, 120)]


def _two_stream_blocks():
    names = np.random.default_rng(5).permutation(
        np.asarray([f"k{i:03d}" for i in range(120)], object))
    out = []
    for i, (sid, n, lo, hi) in enumerate(_BLOCKS):
        cols, ts = stream(50 + i, n, names=names[lo:hi])
        out.append((sid, cols, ts + 10_000 * i))
    return out


def _parent_codes(decoder, keys):
    """`encode_column` as PR 36 had it over the key's factor: the block's
    distinct values in sorted order, the new ones appended."""
    for v in np.unique(np.asarray(keys)).tolist():
        if v not in decoder:
            decoder.append(v)


@pytest.mark.parametrize("restore_after", [None, 3],
                         ids=["straight", "restored"])
def test_new_keys_mid_stream_get_the_parents_lanes_and_codes(restore_after):
    """Three queries of one partition over two streams: q0 and q2 read
    only A, q1 only B.  Keys that B brought are known to the partition's
    interner, and new to q0 and q2, when A brings them: each runtime
    still hands its lanes and codes out over its own blocks as the frozen
    parent does, and the rows are the host's.  Restored half way into an
    app of its own (another interner, other ids), nothing changes: the
    tables by id are made again from `key_lanes` and `str_encoder`."""
    blocks = _two_stream_blocks()
    host = Serving(_two_stream_app(fresh("kf_host"), engine="host"), 3)
    for sid, cols, ts in blocks:
        host.send(cols, ts, to=sid)
    want_rows = host.finish()
    lanes = {"A": KeyLanes(), "B": KeyLanes()}
    codes = {"A": [], "B": []}
    for sid, cols, _ts in blocks:
        keys = cols["sym"].tolist()
        _parent_map_keys_to_lanes(lanes[sid], keys, 8, lambda cap: None)
        _parent_codes(codes[sid], keys)
    name = fresh("kf")
    s = Serving(_two_stream_app(name), 3)
    assert s.on_device()
    rows = []
    for i, (sid, cols, ts) in enumerate(blocks):
        if i == restore_after:
            s.rt.flush()
            snap = s.rt.snapshot()
            rows += s.rows
            before = _lanes(s)
            s.shutdown()
            s = Serving(_two_stream_app(name), 3)
            s.rt.restore(snap)
            assert [list(m.items()) for m in _lanes(s).values()] == \
                [list(m.items()) for m in before.values()]
        s.send(cols, ts, to=sid)
    devs = s.device_runtimes()
    for q, sid in (("q0", "A"), ("q1", "B"), ("q2", "A")):
        assert list(devs[q].key_lanes.items()) == list(lanes[sid].items())
        if hasattr(devs[q], "nfa"):
            assert devs[q].nfa.str_decoder == codes[sid]
    interner = s.rt.partition_runtimes[0].key_interner
    seen = {k for _sid, cols, _ts in blocks[restore_after or 0:]
            for k in cols["sym"].tolist()}
    assert sorted(interner.strings) == sorted(seen)
    assert all(ex.interner is interner for ex in
               s.rt.partition_runtimes[0].executors.values())
    s.rt.flush()
    assert same_rows(sorted(rows + s.rows), want_rows)
    assert len(want_rows) > 100
    s.shutdown()


# ------------------------------------------------------- two sender threads

def test_two_sender_threads_on_a_synchronous_junction():
    """Two threads send their own chunks into one synchronous junction:
    each chunk carries its own factor, so no query ever takes another
    chunk's.  Keys of the two senders are disjoint, so the rows of each
    are those of its stream alone."""
    n_queries = 3
    halves = []
    for seed, prefix in ((21, "a"), (22, "b")):
        names = np.asarray([f"{prefix}{i}" for i in range(40)], object)
        halves.append(stream(seed, 1600, names=names))
    want = []
    for cols_ts in halves:
        _c, rows, s = _run(app_text(fresh("kf_host"), "pattern", n_queries,
                                    engine="host"), n_queries, cols_ts)
        s.shutdown()
        want += rows
    s = Serving(app_text(fresh("kf"), "pattern", n_queries), n_queries)
    start = threading.Barrier(2)

    def sender(cols_ts):
        start.wait()
        s.send(*cols_ts, cut=100)

    threads = [threading.Thread(target=sender, args=(h,)) for h in halves]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)         # hand the interpreter over often
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    s.rt.flush()
    asked, reused = s.counters()
    assert sorted(s.rows) == sorted(want) and len(want) > 50
    assert asked == 2 * 16 * n_queries
    assert reused == 2 * 16 * (n_queries - 1)
    s.shutdown()

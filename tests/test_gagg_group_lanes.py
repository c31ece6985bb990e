"""Group lanes: an unpartitioned grouped query stepped one lane per group
(Nexmark q17).

`plan/gagg_compiler.py` gives a `group by` query with no window, `time`
or `externalTime` one device lane per group tuple (G = 1 per lane):
`plan/planner.py` GroupLanes factors a block's group columns once and
gathers their lanes, and a running query's step takes its events as
compact rows and gives back one row per event (`ops/grouped_agg.py`
build_lane_group_step).  The rows are held three ways, row for row and
in order: the device path, the host engine, and the benchmark's plain
reference's event-by-event loop (`benchmark/references/
grouped_running_agg.py`, imported by path: numpy and pandas only,
nothing of the program) — however the stream is cut into sends.  A
length window, whose eviction crosses groups, keeps one lane.
"""
import importlib.util
import os

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.ledger import GAGG_COUNTERS, ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    path = os.path.join(REPO, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("grouped_running_agg")
NAMES = np.asarray([f"auct{i}" for i in range(64)], object)
DAY = 86_400_000

Q17 = """@app:name('{name}') @app:playback {lanes}
{asy}
define stream S (sym string, price long, kind int, dateTime long);
@info(name='bid')
from S[kind >= 4] select sym, price, dateTime / 86400000L as day
insert into Bid;
@info(name='q')
from Bid
select sym, day, count() as total_bids,
       sum(ifThenElse(price < 10000L, 1L, 0L)) as rank1_bids,
       sum(ifThenElse(price >= 10000L and price < 1000000L, 1L, 0L))
           as rank2_bids,
       sum(ifThenElse(price >= 1000000L, 1L, 0L)) as rank3_bids,
       min(price) as min_price, max(price) as max_price,
       avg(price) as avg_price, sum(price) as sum_price
group by sym, day insert into Out;
"""
ARGS = {"where": [["kind", ">=", 4]],
        "group": {"sym": ["sym", 1], "day": ["dateTime", DAY]},
        "value": "price", "count": "total_bids",
        "ranges": {"rank1_bids": [None, 10000],
                   "rank2_bids": [10000, 1000000],
                   "rank3_bids": [1000000, None]},
        "min": "min_price", "max": "max_price", "sum": "sum_price",
        "avg": "avg_price"}
ASYNC = "@Async(buffer.size='64', batch.size.max='65536')"


def q17(name, lanes="@app:lanes('16')", asy=ASYNC):
    return Q17.format(name=name, lanes=lanes, asy=asy)


def events(seed, n=600, keys=20, days=4):
    """Seeded bids and others as the benchmark draws them, with
    dateTimes across `days` days (in arrival order)."""
    rng = np.random.default_rng(seed)
    cols = {"sym": rng.integers(0, keys, n),
            "price": rng.integers(100, 1_500_000, n).astype(np.int64),
            "kind": rng.integers(0, 50, n).astype(np.int32),
            "dateTime": np.sort(rng.integers(0, days * DAY, n))}
    return cols, 1_000_000 + np.arange(n, dtype=np.int64) * 7


class Serving:
    """One running app with a collecting callback on `Out`."""

    def __init__(self, app):
        self.rt = SiddhiManager().create_siddhi_app_runtime(app)
        self.chunks = []
        self.rt.add_callback("Out", ColumnarStreamCallback(
            lambda c: self.chunks.append(
                (np.array(c.timestamps),
                 {k: np.array(v) for k, v in c.columns.items()}))))
        self.rt.start()

    def send(self, cols, ts, at):
        self.rt.get_input_handler("S").send_batch(
            {k: (NAMES[v[at]] if k == "sym" else v[at])
             for k, v in cols.items()}, timestamps=ts[at])

    def rows(self):
        self.rt.flush()
        if not self.chunks:
            return {"__ts": np.empty(0, np.int64)}
        out = {"__ts": np.concatenate([c[0] for c in self.chunks])}
        for k in self.chunks[0][1]:
            out[k] = np.concatenate([c[1][k] for c in self.chunks])
        return out

    def close(self):
        rows = self.rows()
        self.rt.shutdown()
        return rows


def cuts(n, seed, pieces=9):
    """`n` events as sends of random sizes."""
    rng = np.random.default_rng(seed)
    edges = np.r_[0, np.sort(rng.choice(np.arange(1, n), pieces - 1,
                                        replace=False)), n]
    return [slice(a, b) for a, b in zip(edges[:-1], edges[1:])]


def serve(app, cols, ts, sends):
    sv = Serving(app)
    for at in sends:
        sv.send(cols, ts, at)
    return sv.close(), sv


def by_key(rows, ids):
    """The rows stably ordered by their key's id: per key, in the order
    they came."""
    order = np.argsort(ids, kind="stable")
    return {k: np.asarray(v)[order] for k, v in rows.items()}


def same_rows(got, want, rel=0.0):
    """Per key row for row and in order; `want`'s sym holds ids.
    avg_price may differ by `rel` (relative): both sides divide an exact
    integer sum by a count in double, so 0 is expected everywhere."""
    assert len(got["__ts"]) == len(want["__ts"]) > 0, \
        (len(got["__ts"]), len(want["__ts"]))
    index = {name: i for i, name in enumerate(NAMES)}
    got = by_key(got, [index[s] for s in got["sym"]])
    want = by_key(want, want["sym"])
    for k, v in want.items():
        if k == "__q":
            continue
        mine = np.asarray(got[k])
        v = NAMES[v] if k == "sym" else np.asarray(v)
        if k == "avg_price":
            assert np.allclose(mine, v, rtol=rel, atol=0), k
        else:
            assert mine.dtype == object or mine.dtype.kind == v.dtype.kind
            assert (mine == v).all(), k


def report(sv):
    return sv.rt.query_runtimes["q"]


# ------------------------------------------------------ (a) q17 as written

@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_q17_equals_the_reference_and_the_host_engine(seed):
    cols, ts = events(seed)
    want = REF.run_loop(cols, ts, ARGS)
    assert len(set(want["day"].tolist())) >= 3      # across several days
    got, sv = serve(q17(f"q17_{seed % 97}"), cols, ts, cuts(len(ts), seed))
    same_rows(got, want)
    qr = report(sv)
    assert qr.backend == "device"
    assert sv.rt.query_runtimes["bid"].backend == "device"
    dev = qr.device_runtime
    assert dev.cga.group_lanes and dev.cga.lane_rows
    assert dev.group_lanes.n == len(set(zip(want["sym"].tolist(),
                                            want["day"].tolist())))
    assert dev.cga.n_groups == 1 and len(dev.cga.gid_map) == 0
    host, _ = serve("@app:engine('host') " + q17("q17h", asy=""),
                    cols, ts, cuts(len(ts), seed))
    same_rows(host, want)


@pytest.mark.parametrize("interleaved", [False, True])
def test_the_vectorised_reference_equals_its_loop(interleaved):
    for seed in (3, 4, 2**31 + 11):
        cols, ts = events(seed, n=2000, keys=50, days=3)
        if interleaved:     # a key's days come in any order
            cols["dateTime"] = np.random.default_rng(seed).permutation(
                cols["dateTime"])
        fast = REF.run(cols, ts, ARGS)
        slow = REF.run_loop(cols, ts, ARGS)
        assert set(fast) == set(slow)
        for k in slow:
            assert fast[k].dtype.kind == slow[k].dtype.kind, k
            assert (fast[k] == slow[k]).all(), k


# -------------------------------------------- (b) sends cut at random sizes

@pytest.mark.parametrize("seed", [5, 6, 7])
def test_any_cut_into_sends_gives_the_same_rows(seed):
    cols, ts = events(seed, n=500, keys=12, days=2)
    if seed == 7:           # a key's days in any order
        cols["dateTime"] = np.random.default_rng(seed).permutation(
            cols["dateTime"])
    want = REF.run_loop(cols, ts, ARGS)
    one_by_one = [slice(i, i + 1) for i in range(40)] + \
        [slice(40, len(ts))]
    for sends in (cuts(len(ts), seed, pieces=17), one_by_one,
                  [slice(0, len(ts))]):
        got, _ = serve(q17(f"cut{seed}"), cols, ts, sends)
        same_rows(got, want)


def test_groups_past_the_declared_lanes_grow_them():
    """No @app:lanes: the lanes start at 8 and double as groups come."""
    cols, ts = events(8, n=400, keys=60, days=2)
    want = REF.run_loop(cols, ts, ARGS)
    got, sv = serve(q17("grow", lanes=""), cols, ts, cuts(len(ts), 8))
    same_rows(got, want)
    dev = report(sv).device_runtime
    assert dev.cga.n_lanes >= dev.group_lanes.n > 64


# ------------------------------------------------ (c) persist and restore

def test_snapshot_half_way_restores_groups_and_lanes():
    cols, ts = events(9, n=400, keys=15, days=3)
    want = REF.run_loop(cols, ts, ARGS)
    app = q17("snap")
    half = slice(0, 200)
    first = Serving(app)
    for at in cuts(200, 9, pieces=5):
        first.send(cols, ts, at)
    head = first.rows()
    snap = first.rt.snapshot()
    lanes = dict(report(first).device_runtime.group_lanes.lanes)
    first.rt.shutdown()
    second = Serving(app)
    second.rt.restore(snap)
    assert report(second).device_runtime.group_lanes.lanes == lanes
    for at in cuts(200, 10, pieces=5):
        second.send(cols, ts, slice(at.start + 200, at.stop + 200))
    tail = second.close()
    assert len(head["__ts"]) == len(REF.run_loop(
        {k: v[half] for k, v in cols.items()}, ts[half], ARGS)["__ts"])
    same_rows({k: np.concatenate([head[k], tail[k]]) for k in head}, want)
    # the same second half on fresh lanes starts every group again
    fresh = Serving(app)
    fresh.send(cols, ts, slice(200, len(ts)))
    rows = fresh.close()
    assert rows["total_bids"].sum() < tail["total_bids"].sum()


# ------------------------------------- (d) a time window on group lanes

TIMED = """@app:name('{name}') @app:playback
define stream S (sym string, price long, kind int, dateTime long);
@info(name='q')
from S[kind >= 4]#window.time(40 milliseconds)
select sym, count() as n, sum(price) as total, min(price) as lo,
       max(price) as hi
group by sym insert into Out;
"""


def timed_loop(cols, ts, window_ms=40):
    """Sliding time(t) per group, event by event: an entry counts for an
    event while ts_entry > ts_event - t."""
    live = {}
    rows = []
    for i in range(len(ts)):
        if cols["kind"][i] < 4:
            continue
        k = int(cols["sym"][i])
        q = live.setdefault(k, [])
        q.append((int(ts[i]), int(cols["price"][i])))
        q[:] = [(t, p) for t, p in q if t > ts[i] - window_ms]
        ps = [p for _t, p in q]
        rows.append((int(ts[i]), k, len(ps), sum(ps), min(ps), max(ps)))
    a = np.asarray(rows, np.int64).reshape(-1, 6)
    return {"__ts": a[:, 0], "sym": a[:, 1], "n": a[:, 2],
            "total": a[:, 3], "lo": a[:, 4], "hi": a[:, 5]}


def test_a_time_window_steps_on_group_lanes_equal_to_the_reference():
    cols, ts = events(11, n=300, keys=6)
    want = timed_loop(cols, ts)
    # one event a send: the host ring expires per chunk, the device per
    # event, and they agree by law one event at a time
    one_by_one = [slice(i, i + 1) for i in range(len(ts))]
    got, sv = serve(TIMED.format(name="timed"), cols, ts, one_by_one)
    same_rows(got, want)
    cga = report(sv).device_runtime.cga
    assert cga.group_lanes and not cga.lane_rows
    assert cga.window_kind == "time" and cga.n_groups == 1
    host, _ = serve("@app:engine('host') " + TIMED.format(name="timedh"),
                    cols, ts, one_by_one)
    same_rows(host, want)
    # and in sends of many events, where the ring grows past 64 entries
    # of a group only if one holds that many: here the cut changes nothing
    got, _ = serve(TIMED.format(name="timed2"), cols, ts,
                   cuts(len(ts), 11))
    same_rows(got, want)


# --------------------------------------- (e) a length window keeps one lane

def test_a_length_window_keeps_one_lane():
    app = """@app:name('{name}') @app:playback
define stream S (sym string, price long, kind int, dateTime long);
@info(name='q')
from S[kind >= 4]#window.length(5)
select sym, count() as n, sum(price) as total
group by sym insert into Out;
"""
    cols, ts = events(12, n=200, keys=5)
    got, sv = serve(app.format(name="len"), cols, ts, cuts(len(ts), 12))
    cga = report(sv).device_runtime.cga
    assert not cga.group_lanes and cga.n_lanes == 1
    assert report(sv).device_runtime.group_lanes is None
    assert len(cga.gid_map) == 5
    host, _ = serve("@app:engine('host') " + app.format(name="lenh"),
                    cols, ts, cuts(len(ts), 12))
    assert len(got["__ts"]) == len(host["__ts"]) > 0
    for k in host:
        assert (np.asarray(got[k]) == np.asarray(host[k])).all(), k


# --------------------------------------------- (f) what the planner takes

@pytest.mark.parametrize("arg,taken", [
    ("ifThenElse(price < 10000L, 1L, 0L)", True),
    ("ifThenElse(kind == 5, 7, -3)", True),
    ("price * 2L", False),
    ("ifThenElse(price < 10000L, price, 0L)", False),
    ("ifThenElse(price < 10000L, 3000000000L, 0L)", False),
])
def test_conditional_counts_are_taken_and_other_integer_args_refused(
        arg, taken):
    app = f"""@app:name('arg') @app:playback
define stream S (sym string, price long, kind int, dateTime long);
@info(name='q')
from S select sym, sum({arg}) as s group by sym insert into Out;
"""
    rt = SiddhiManager().create_siddhi_app_runtime(app)
    qr = rt.query_runtimes["q"]
    if taken:
        assert qr.backend == "device", qr.backend_reason
    else:
        assert qr.backend == "host"
        assert "INT/LONG aggregate arguments must be plain attributes " \
            "or ifThenElse of two integer constants" in qr.backend_reason
    rt.shutdown()


def test_int_and_null_group_keys_take_lanes_like_the_host():
    """A non-string key goes the per-distinct way, a null key is a group
    of its own, a second key column with several values a block takes
    one table each: the rows equal the host engine's."""
    app = """@app:name('{name}') @app:playback
define stream T (k long, s string, v int);
@info(name='q')
from T select k, s, count() as n, sum(v) as total, max(v) as top
group by k, s having n < 100 insert into Out;
"""
    plain = app.replace(" having n < 100", "")
    rng = np.random.default_rng(14)
    n = 300
    s = np.asarray(["x", "y", None], object)[rng.integers(0, 3, n)]
    cols = {"k": rng.integers(-3, 4, n).astype(np.int64), "s": s,
            "v": rng.integers(-50, 50, n).astype(np.int32)}
    ts = 1_000 + np.arange(n, dtype=np.int64)
    for text in (plain, app):
        out = {}
        for mode in ("device", "host"):
            rt = SiddhiManager().create_siddhi_app_runtime(
                f"@app:engine('{mode}') " + text.format(name=mode))
            got = []
            rt.add_callback("Out", ColumnarStreamCallback(
                lambda c, got=got: got.append(
                    (np.array(c.timestamps),
                     {k: np.array(v) for k, v in c.columns.items()}))))
            rt.start()
            if mode == "device":
                dev = rt.query_runtimes["q"].device_runtime
                assert dev.cga.group_lanes
                assert dev.group_lanes.key_attr == "s"
            h = rt.get_input_handler("T")
            for at in cuts(n, 14, pieces=6):
                h.send_batch({k: v[at] for k, v in cols.items()},
                             timestamps=ts[at])
            rt.flush()
            rt.shutdown()
            out[mode] = {"__ts": np.concatenate([g[0] for g in got]),
                         **{k: np.concatenate([g[1][k] for g in got])
                            for k in got[0][1]}}
        assert len(out["device"]["__ts"]) == len(out["host"]["__ts"]) > 0
        for k in out["host"]:
            assert list(out["device"][k]) == list(out["host"][k]), k


def test_group_lanes_counters_reach_the_snapshot_and_metrics():
    ledger().reset()
    cols, ts = events(15, n=300, keys=10)
    _, sv = serve(q17("books"), cols, ts, cuts(len(ts), 15))
    app = ledger().snapshot("books")["apps"]["books"]
    bids = int((cols["kind"] >= 4).sum())
    assert app["gagg_events_total"] == app["gagg_lane_events_total"] == bids
    assert app["gagg_cells_total"] >= bids
    # /stats shows the app's ledger row
    assert sv.rt.statistics["ledger"]["apps"]["books"][
        "gagg_lane_events_total"] == bids
    lines = "\n".join(ledger().prometheus_lines())
    for name in GAGG_COUNTERS:
        assert f'siddhi_{name}{{app="books"}}' in lines
    # the interner's probe counts as a keyed join's does
    assert app["key_intern_events_total"] == bids
    assert app["key_factor_total"] >= 1

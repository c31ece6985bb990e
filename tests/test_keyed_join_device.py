"""Keyed window joins on the device ring step (PR 38, Nexmark q20).

`plan/planner.py` DeviceKeyedJoinRuntime takes an inner join of two
filtered stream sides, each under `#window.time(t)` or no window, whose
`on` holds a key equality; its window state lives on the device
(`ops/keyed_join.py`).  The rows are held three ways, row for row and in
order: the device runtime, the host engine (`core/join.py`) and the
benchmark's plain reference's event-by-event loop
(`benchmark/references/keyed_window_join.py`, imported by path: numpy
only, nothing of the program) — however the stream is cut into sends,
since per-event order is the semantics.  The joins the runtime does not
take stay on `core/join.py` with their reasons.
"""
import importlib.util
import os

import numpy as np
import pytest

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
from siddhi_tpu.core.ledger import JOIN_COUNTERS, ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    path = os.path.join(REPO, "benchmark", "references", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("keyed_window_join")
KEYS = 12
NAMES = np.asarray([f"a{i}" for i in range(64)], object)
STREAM = "define stream S (sym string, price float, kind int);\n"


def events(seed, n=160, keys=KEYS, rate=40, kinds=50):
    """A seeded stream as the benchmark's generator draws it: uniform
    keys, f32 prices in [0, 100), `kind` 0 a person, 1-3 an auction,
    4-49 a bid; `rate` events per event-second."""
    rng = np.random.default_rng(seed)
    cols = {"sym": rng.integers(0, keys, n),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, kinds, n)}
    return cols, 1_000_000 + (np.arange(n, dtype=np.int64) * 1000) // rate


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
        self.qr = self.rt.query_runtimes["q"]

    def send(self, stream, cols, ts):
        self.rt.get_input_handler(stream).send_batch(cols, timestamps=ts)

    def rows(self):
        """Everything delivered so far, in delivery order."""
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


def cut(n, chunk):
    return [slice(i, min(i + chunk, n)) for i in range(0, n, chunk)]


def serve_one_stream(app, cols, ts, chunk, keyed=("sym",)):
    """The stream `S` in sends of `chunk` events -> (rows, Serving)."""
    sv = Serving(app)
    for sl in cut(len(ts), chunk):
        sv.send("S", {k: (NAMES[v[sl]] if k in keyed else v[sl])
                      for k, v in cols.items()}, ts[sl])
    return sv.close(), sv


def same_rows(got, want, keyed=()):
    """Row for row and in order; a key column of `want` holds ids."""
    assert len(got["__ts"]) == len(want["__ts"]), \
        (len(got["__ts"]), len(want["__ts"]))
    for k, v in want.items():
        if k == "__q":
            continue
        mine = got[k]
        if k in keyed:
            v = NAMES[v]
        assert (np.asarray(mine) == np.asarray(v)).all(), k


def engine(text, mode):
    return f"@app:engine('{mode}') " + text if mode else text


def counters(app):
    snap = ledger().snapshot(app)["apps"].get(app, {})
    return {k: snap.get(k, 0) for k in JOIN_COUNTERS}


# ------------------------------------------------------------ (a) q20

Q20 = ("@app:name('{name}') @app:playback\n" + STREAM + """
@info(name='q')
from S[kind >= 4] as b
  join S[{build}]#window.time({w} sec) as a
  on b.sym == a.sym{residual}
select b.sym as auction, b.price as bid, a.price as reserve,
       a.kind as akind
insert into Out;
""")

#: the auction side's filter, as SiddhiQL and as the reference's `where`:
#: q20's, which few events pass (the block's compact rows are a few of
#: its cells), and one that every event passes (the rows outgrow
#: P x T / 64 and double)
BUILDS = {"sparse": ("kind >= 1 and kind <= 3 and price >= 40.0",
                     [["kind", ">=", 1], ["kind", "<=", 3],
                      ["price", ">=", 40.0]]),
          "full": ("kind >= 0", [["kind", ">=", 0]])}


def q20(name, w=2, residual="", build="sparse"):
    return Q20.format(name=name, w=w, residual=residual,
                      build=BUILDS[build][0])


def q20_args(window_ms=2000, residual=(), build="sparse"):
    return {"key": "sym",
            "left": {"where": [["kind", ">=", 4]], "window_ms": None},
            "right": {"where": BUILDS[build][1], "window_ms": window_ms},
            "trigger": "all", "residual": list(residual),
            "out": {"auction": ["left", "sym"], "bid": ["left", "price"],
                    "reserve": ["right", "price"], "akind": ["right", "kind"]}}


@pytest.mark.parametrize("build", sorted(BUILDS))
@pytest.mark.parametrize("chunk", [1, 7, 64, 1000])
@pytest.mark.parametrize("seed", [1, 2147483999])
def test_q20_device_host_and_reference_agree(seed, chunk, build):
    cols, ts = events(seed, n=400, kinds=12)
    tally = {}
    args = q20_args(build=build)
    want = REF.run_loop(cols, ts, args, tally)
    assert len(want["__ts"]) > 20
    same_rows(REF.run(cols, ts, args), want)
    name = f"q20_{seed}_{chunk}_{build}"
    before = counters(name)
    app = q20(name, build=build)
    dev, sv = serve_one_stream(app, cols, ts, chunk)
    assert sv.qr.backend == "device" and sv.qr.backend_reason is None
    assert type(sv.qr.device_runtime).__name__ == "DeviceKeyedJoinRuntime"
    assert sv.qr.join_runtime is None
    same_rows(dev, want, keyed=("auction",))
    host, hv = serve_one_stream(engine(app, "host").replace(name, name + "h"),
                                cols, ts, chunk)
    assert hv.qr.backend == "host"
    same_rows(host, want, keyed=("auction",))
    # the join's counters are the reference loop's own tally
    got = {k: v - before[k] for k, v in counters(name).items()}
    for k in REF.TALLY:
        assert got[f"join_{k}_total"] == tally[k], (k, got, tally)
    assert got["join_events_total"] == len(ts)
    assert got["join_device_events_total"] == len(ts)
    # q20's auctions never fill a ring; with every event on the build
    # side a key can hold more than 8 live entries, and its ring doubles
    assert 8 << got["join_ring_grown_total"] == \
        sv.qr.device_runtime.join.n_slots
    if build == "sparse":
        assert got["join_ring_grown_total"] == 0
    # every auction went up once as a compact row, into uploads of at
    # least its block's P x T / 64 rows
    assert got["join_build_rows_total"] == tally["inserted"]
    assert got["join_build_slots_total"] >= tally["inserted"]
    assert counters(name + "h")["join_events_total"] == len(ts)
    assert counters(name + "h")["join_device_events_total"] == 0


# ------------------------------- (b) two windows, both sides triggering

TWO = ("@app:name('{name}') @app:playback\n" + STREAM + """
@info(name='q')
from S[kind < 3]#window.time(1500 milliseconds) as p {uni_l}
  join S[kind >= 2 and kind < 6]#window.time(900 milliseconds) as a {uni_r}
  on p.sym == a.sym
select p.sym as sym, p.price as pp, a.price as ap, p.kind as pk, a.kind as ak
insert into Out;
""")


def two_args(trigger):
    return {"key": "sym",
            "left": {"where": [["kind", "<", 3]], "window_ms": 1500},
            "right": {"where": [["kind", ">=", 2], ["kind", "<", 6]],
                      "window_ms": 900},
            "trigger": trigger, "residual": [],
            "out": {"sym": ["left", "sym"], "pp": ["left", "price"],
                    "ap": ["right", "price"], "pk": ["left", "kind"],
                    "ak": ["right", "kind"]}}


@pytest.mark.parametrize("trigger", ["all", "left", "right"])
@pytest.mark.parametrize("chunk", [1, 5, 300])
def test_two_windowed_sides_q8_shape(trigger, chunk):
    """Both sides windowed and both triggering (Nexmark q8's shape), and
    `unidirectional` on either; `kind` 2 is on both sides, so an event
    meets itself as a right event after it entered the left window."""
    cols, ts = events(5, n=220, keys=6, kinds=8, rate=50)
    want = REF.run_loop(cols, ts, two_args(trigger))
    assert len(want["__ts"]) > 40
    same_rows(REF.run(cols, ts, two_args(trigger)), want)
    name = f"two_{trigger}_{chunk}"
    app = TWO.format(name=name,
                     uni_l="unidirectional" if trigger == "left" else "",
                     uni_r="unidirectional" if trigger == "right" else "")
    dev, sv = serve_one_stream(app, cols, ts, chunk)
    assert type(sv.qr.device_runtime).__name__ == "DeviceKeyedJoinRuntime"
    same_rows(dev, want, keyed=("sym",))
    host, _ = serve_one_stream(engine(app, "host"), cols, ts, chunk)
    same_rows(host, want, keyed=("sym",))


def test_two_streams_each_windowed():
    """The sides on two streams: a chunk holds one side, and the step of
    each stream leaves the other side's phase out."""
    cols, ts = events(9, n=200, keys=5, kinds=2, rate=50)
    args = {"key": "sym",
            "left": {"where": [["kind", "==", 0]], "window_ms": 1000},
            "right": {"where": [["kind", "==", 1]], "window_ms": 700},
            "trigger": "all", "residual": [["price", ">", "price"]],
            "out": {"sym": ["left", "sym"], "lp": ["left", "price"],
                    "rp": ["right", "price"]}}
    want = REF.run_loop(cols, ts, args)
    assert len(want["__ts"]) > 30
    app = """@app:playback
        define stream L (sym string, price float);
        define stream R (sym string, price float);
        @info(name='q')
        from L#window.time(1 sec) join R#window.time(700 milliseconds)
          on L.sym == R.sym and L.price > R.price
        select L.sym as sym, L.price as lp, R.price as rp insert into Out;"""
    got = {}
    for mode in (None, "host"):
        sv = Serving(engine(app, mode))
        for i in range(len(ts)):
            sv.send("LR"[cols["kind"][i]],
                    {"sym": NAMES[cols["sym"][i:i + 1]],
                     "price": cols["price"][i:i + 1]}, ts[i:i + 1])
        got[mode] = sv.close()
        assert (sv.qr.device_runtime is not None) == (mode is None)
        same_rows(got[mode], want, keyed=("sym",))


# ----------------------------------------- (c) every cut of one stream

@pytest.mark.parametrize("mode", [None, "host"])
def test_self_join_rows_do_not_depend_on_the_cut(mode):
    """Chunks that hold both sides interleaved, at every chunk size from
    one event to the whole stream."""
    cols, ts = events(3, n=36, keys=3, kinds=6, rate=30)
    args = q20_args(window_ms=1000)
    args["left"]["where"] = [["kind", ">=", 3]]
    args["right"]["where"] = [["kind", "<", 3]]
    want = REF.run_loop(cols, ts, args)
    assert len(want["__ts"]) > 15
    app = engine("@app:playback\n" + STREAM + """
        @info(name='q')
        from S[kind >= 3] as b join S[kind < 3]#window.time(1 sec) as a
          on b.sym == a.sym
        select b.sym as auction, b.price as bid, a.price as reserve,
               a.kind as akind
        insert into Out;""", mode)
    for chunk in range(1, len(ts) + 1):
        got, _ = serve_one_stream(app, cols, ts, chunk)
        same_rows(got, want, keyed=("auction",))


# ------------------------------ (d) the expiry boundary, equal stamps

def _planted(kinds, prices, stamps, sym=0):
    n = len(kinds)
    return ({"sym": np.full(n, sym), "kind": np.asarray(kinds),
             "price": np.asarray(prices, np.float32)},
            np.asarray(stamps, np.int64))


@pytest.mark.parametrize("chunk", [1, 3, 100])
@pytest.mark.parametrize("mode", [None, "host"])
def test_expiry_boundary_and_equal_milliseconds(mode, chunk):
    """An auction at t is live for a bid at t + w - 1 and gone at t + w
    and t + w + 1 (upstream expires at `ts + window <= now`); within one
    millisecond arrival order decides."""
    w, t = 2000, 5_000_000
    cols, ts = _planted(
        [5, 1, 5, 1, 5, 5, 5, 5],
        [10, 50, 11, 60, 12, 13, 14, 15],
        [t, t, t, t + 1, t + w - 1, t + w, t + w, t + w + 1])
    want = REF.run_loop(cols, ts, q20_args(window_ms=w))
    # bid 0 precedes its auction; bid 2 sees the first auction only; at
    # t+w-1 both are live, at t+w only the second, at t+w+1 none
    assert want["bid"].tolist() == [11, 12, 12, 13, 14]
    assert want["reserve"].tolist() == [50, 50, 60, 60, 60]
    app = engine(q20(f"edge_{mode}_{chunk}"), mode)
    got, _ = serve_one_stream(app, cols, ts, chunk)
    same_rows(got, want, keyed=("auction",))


# ---------------------------------------------------- (e) ring growth

def _spy_blocks(join):
    """Wrap ``join.process_block``: -> (every block it was given, each
    once, and its number of calls)."""
    blocks, calls, step = [], [0], join.process_block

    def spy(block, present):
        calls[0] += 1
        if not any(b is block for b in blocks):
            blocks.append(block)
        return step(block, present)
    join.process_block = spy
    return blocks, calls


@pytest.mark.parametrize("build", sorted(BUILDS))
@pytest.mark.parametrize("chunk", [1, 40])
def test_a_full_ring_doubles_and_loses_nothing(chunk, build):
    """20 live auctions of one key: the 8-slot ring doubles twice, the
    block that filled it is replayed, and the rows are the reference's.
    The build side's counters count each block's compact rows once,
    the replayed block too."""
    kinds = [1] * 20 + [5] * 3 + [1] * 2 + [5]
    cols, ts = _planted(kinds, np.arange(len(kinds)) + 50.0,
                        7_000_000 + np.arange(len(kinds)) * 10)
    tally = {}
    want = REF.run_loop(cols, ts, q20_args(build=build), tally)
    # with every event on the build side a bid also enters the ring
    assert len(want["__ts"]) == {"sparse": 3 * 20 + 22,
                                 "full": 20 + 21 + 22 + 25}[build]
    name = f"grow_{chunk}_{build}"
    before = counters(name)
    sv = Serving(q20(name, build=build))
    blocks, calls = _spy_blocks(sv.qr.device_runtime.join)
    for sl in cut(len(ts), chunk):
        sv.send("S", {k: (NAMES[v[sl]] if k == "sym" else v[sl])
                      for k, v in cols.items()}, ts[sl])
    same_rows(sv.close(), want, keyed=("auction",))
    assert sv.qr.device_runtime.join.n_slots == 32
    after = counters(name)
    assert after["join_ring_grown_total"] - before["join_ring_grown_total"] \
        == 2
    for k in REF.TALLY:     # a replayed block is counted once
        assert after[f"join_{k}_total"] - before[f"join_{k}_total"] == \
            tally[k], k
    assert calls[0] > len(blocks)                   # a block was replayed
    rows = slots = 0
    for block in blocks:
        P, T = block["ts"].shape
        (idx, _vals), = block["rows"]
        rows += int((idx < P * T).sum())
        slots += len(idx)
    assert rows == tally["inserted"]
    assert after["join_build_rows_total"] - \
        before["join_build_rows_total"] == rows
    assert after["join_build_slots_total"] - \
        before["join_build_slots_total"] == slots
    # kept per app on /metrics as on snapshot(), declared with the others
    from siddhi_tpu.core.statistics import LEDGER_TYPES
    text = "\n".join(ledger().prometheus_lines())
    for k in ("join_build_rows_total", "join_build_slots_total"):
        assert f'siddhi_{k}{{app="{name}"}} {after[k]}' in text
    assert {f"siddhi_{k}" for k in JOIN_COUNTERS} <= \
        {n for n, _kind, _text in LEDGER_TYPES}
    host, _ = serve_one_stream(
        engine(q20(name + "h", build=build), "host"),
        cols, ts, chunk)
    same_rows(host, want, keyed=("auction",))


def test_a_deep_block_is_stepped_at_its_own_depth():
    """A chunk in which one key has 40 events is one block of depth 64
    (T is a shape, as the pattern runtime's): one step, the rows and
    their order those of the event-by-event loop."""
    kinds = [1, 5] * 20 + [5] * 10
    cols, ts = _planted(kinds, np.arange(len(kinds)) + 50.0,
                        8_000_000 + np.arange(len(kinds)) * 10)
    cols["sym"][::7] = 1                 # a second key in between
    want = REF.run_loop(cols, ts, q20_args())
    sv = Serving(q20("deep"))
    join = sv.qr.device_runtime.join
    depths, step = [], join.process_block
    join.process_block = lambda block, present: (
        depths.append(block["ts"].shape[1]), step(block, present))[1]
    sv.send("S", {"sym": NAMES[cols["sym"]], "price": cols["price"],
                  "kind": cols["kind"]}, ts)
    same_rows(sv.close(), want, keyed=("auction",))
    assert set(depths) == {64}, depths


# ------------------------------------------------------- (f) residual

@pytest.mark.parametrize("chunk", [1, 50])
def test_residual_comparison(chunk):
    cols, ts = events(11, n=300, kinds=12)
    args = q20_args(residual=[["price", ">", "price"]])
    want = REF.run_loop(cols, ts, args)
    loose = REF.run_loop(cols, ts, q20_args())
    assert 10 < len(want["__ts"]) < len(loose["__ts"])
    same_rows(REF.run(cols, ts, args), want)
    app = q20(f"res_{chunk}", residual=" and b.price > a.price")
    dev, sv = serve_one_stream(app, cols, ts, chunk)
    assert type(sv.qr.device_runtime).__name__ == "DeviceKeyedJoinRuntime"
    same_rows(dev, want, keyed=("auction",))
    host, _ = serve_one_stream(engine(app, "host"), cols, ts, chunk)
    same_rows(host, want, keyed=("auction",))


# ------------------------------------------------ (g) persist, restore

@pytest.mark.parametrize("build", sorted(BUILDS))
def test_snapshot_half_way_restores_the_rings(build):
    cols, ts = events(13, n=300, kinds=12)
    want = REF.run_loop(cols, ts, q20_args(build=build))
    app = q20(f"snap_{build}", build=build)
    half = 150

    def feed(sv, sl):
        for c in cut(sl.stop - sl.start, 32):
            c = slice(sl.start + c.start, sl.start + c.stop)
            sv.send("S", {"sym": NAMES[cols["sym"][c]],
                          "price": cols["price"][c],
                          "kind": cols["kind"][c]}, ts[c])

    first = Serving(app)
    feed(first, slice(0, half))
    head = first.rows()
    snap = first.rt.snapshot()
    first.rt.shutdown()
    second = Serving(app)
    second.rt.restore(snap)
    feed(second, slice(half, len(ts)))
    tail = second.close()
    assert len(head["__ts"]) and len(tail["__ts"])
    same_rows({k: np.concatenate([head[k], tail[k]]) for k in head}, want,
              keyed=("auction",))
    # the same second half on fresh rings misses the rows whose auction
    # the snapshot carried
    fresh = Serving(app)
    feed(fresh, slice(half, len(ts)))
    assert len(fresh.close()["__ts"]) < len(tail["__ts"])


# -------------------------------------------------- (h) integer keys

@pytest.mark.parametrize("ltype,rtype", [("int", "int"), ("long", "long"),
                                         ("int", "long")])
def test_integer_keys(ltype, rtype):
    """int and long keys, the ring side's key given back in its own
    type, and a long and a double carried through the ring whole."""
    rng = np.random.default_rng(17)
    n = 160
    ids = rng.integers(0, 7, n) + (0 if ltype == "int" else 3_000_000_000)
    if "int" in (ltype, rtype):
        ids = rng.integers(0, 7, n)
    who = rng.integers(0, 2, n)
    big = rng.integers(-2**62, 2**62, n)
    dbl = rng.uniform(-1e300, 1e300, n)
    ts = 2_000_000 + np.arange(n, dtype=np.int64) * 25
    app = f"""@app:playback
        define stream L (id {ltype}, v float);
        define stream R (id {rtype}, big long, dbl double, flag bool);
        @info(name='q')
        from L join R#window.time(1 sec) on L.id == R.id
        select L.id as lid, R.id as rid, L.v as v, R.big as big,
               R.dbl as dbl, R.flag as flag
        insert into Out;"""
    np_of = {"int": np.int32, "long": np.int64}
    got = {}
    for mode in (None, "host"):
        sv = Serving(engine(app, mode))
        for i in range(n):
            sl = slice(i, i + 1)
            if who[i]:
                sv.send("R", {"id": ids[sl].astype(np_of[rtype]),
                              "big": big[sl], "dbl": dbl[sl],
                              "flag": big[sl] > 0}, ts[sl])
            else:
                sv.send("L", {"id": ids[sl].astype(np_of[ltype]),
                              "v": np.asarray([i], np.float32)}, ts[sl])
        got[mode] = sv.close()
        assert (sv.qr.device_runtime is not None) == (mode is None), \
            sv.qr.backend_reason
    dev, host = got[None], got["host"]
    assert len(host["__ts"]) > 50
    for k in host:
        assert dev[k].dtype == host[k].dtype, k
        assert (dev[k] == host[k]).all(), k
    assert dev["rid"].dtype == (np.int32 if rtype == "int" else np.int64)


# ------------------------------------- (j) strings through the ring

STRINGS = """@app:name('{name}') @app:playback
define stream S (sym string, price float, kind int, note string, tag string);
@info(name='q')
from S[kind >= 4]{lw} as b
  join S[kind >= 1 and kind <= 3]#window.time(2 sec) as a
  on b.sym == a.sym
select b.sym as auction, b.note as bnote, a.note as anote, a.tag as atag,
       a.price as reserve
insert into Out;
"""


def _noted(seed, n=240):
    """`events` with two string columns; every seventh note is null."""
    cols, ts = events(seed, n=n, kinds=12)
    note = np.asarray([f"note-{i % 23}" for i in range(n)], object)
    note[::7] = None
    tag = np.asarray([f"tag-{i}" for i in range(n)], object)
    return cols, ts, note, tag


def _send_noted(sv, cols, note, tag, ts, sl):
    sv.send("S", {"sym": NAMES[cols["sym"][sl]], "price": cols["price"][sl],
                  "kind": cols["kind"][sl], "note": note[sl],
                  "tag": tag[sl]}, ts[sl])


@pytest.mark.parametrize("lw", ["", "#window.time(2 sec)"])
@pytest.mark.parametrize("chunk", [1, 9, 240])
def test_strings_of_a_windowed_side_ride_the_ring(chunk, lw):
    """A string the select reads of a windowed side is carried as its
    code in the engine's dictionary (nulls too); a probing side's comes
    from the chunk.  Device == host, row for row, one side windowed
    (q20 as published: the auction's strings) and both."""
    cols, ts, note, tag = _noted(29)
    got = {}
    for mode in (None, "host"):
        sv = Serving(engine(STRINGS.format(name=f"str{chunk}{len(lw)}"
                                           f"{mode}", lw=lw), mode))
        assert (sv.qr.device_runtime is not None) == (mode is None), \
            sv.qr.backend_reason
        if mode is None:
            join = sv.qr.device_runtime.join
        for sl in cut(len(ts), chunk):
            _send_noted(sv, cols, note, tag, ts, sl)
        got[mode] = sv.close()
    dev, host = got[None], got["host"]
    assert len(host["__ts"]) > 40
    assert any(v is None for v in host["anote"]) and \
        any(v is None for v in host["bnote"])
    for k in host:
        assert dev[k].dtype == host[k].dtype, k
        assert (dev[k] == host[k]).all(), k
    if not lw:
        # only the events that enter a ring take a code
        auctions = (cols["kind"] >= 1) & (cols["kind"] <= 3)
        held = set(join.str_decoder[1:])
        assert held == {v for v in note[auctions] if v is not None} | \
            set(tag[auctions])


def test_snapshot_carries_the_rings_strings():
    cols, ts, note, tag = _noted(31)
    app = STRINGS.format(name="strsnap", lw="")
    whole = Serving(app)
    _send_noted(whole, cols, note, tag, ts, slice(0, len(ts)))
    want = whole.close()
    half = 120
    first = Serving(app)
    _send_noted(first, cols, note, tag, ts, slice(0, half))
    head = first.rows()
    snap = first.rt.snapshot()
    first.rt.shutdown()
    second = Serving(app)
    second.rt.restore(snap)
    _send_noted(second, cols, note, tag, ts, slice(half, len(ts)))
    tail = second.close()
    assert len(head["__ts"]) and len(tail["__ts"])
    for k in want:
        assert (np.concatenate([head[k], tail[k]]) == want[k]).all(), k
    # rows of the second half name strings only the snapshot held
    assert set(tail["atag"]) & set(tag[:half])


# ----------------------------------- (k) the build side's compact rows

def _step_layouts():
    """-> {layout: (spec, groups, the side bits whose events carry each
    group)}: q20's one group (the ring side's 64-bit halves and a string
    code, beside a float), and two windowed sides whose events carry a
    group each, compared by a residual over both."""
    from siddhi_tpu.ops.keyed_join import LEFT, RIGHT, JoinSpec, Ring
    return {
        "one group": (
            JoinSpec(rings=(None, Ring(300, (("a#0", "i"), ("a#1", "i"),
                                             ("s", "i"), ("x", "f")))),
                     triggers=(True, True), residual=None),
            (("a#0", "a#1", "s"),), (RIGHT,)),
        "two groups": (
            JoinSpec(rings=(Ring(500, (("a", "i"), ("x", "f"))),
                            Ring(300, (("b", "i"), ("c", "i")))),
                     triggers=(True, True),
                     residual=lambda lv, rv: (lv["a"] & 3) != (rv["b"] & 3)),
            (("a",), ("b", "c")), (LEFT, RIGHT))}


@pytest.mark.parametrize("T", [4, 8, 16])
@pytest.mark.parametrize("layout", ["one group", "two groups"])
def test_compact_rows_step_equals_dense_planes(layout, T):
    """The step given a block's int planes as compact rows (in any order,
    padding rows behind them) returns the carry, rows and tail that it
    returns given the same planes dense, bit for bit, block after
    block."""
    import jax

    from siddhi_tpu.ops.keyed_join import build_step, make_carry
    from siddhi_tpu.plan.join_compiler import compact_rows
    spec, groups, carriers = _step_layouts()[layout]
    P, cap = 32, 4096
    dense_step = jax.jit(build_step(spec, (True, True)), static_argnums=2)
    rows_step = jax.jit(build_step(spec, (True, True), groups),
                        static_argnums=2)
    rng = np.random.default_rng(4200 + T)
    carry = {"dense": make_carry(spec, P, 8), "rows": make_carry(spec, P, 8)}
    for b in range(3):
        side = rng.choice(4, (P, T), p=[0.4, 0.25, 0.25, 0.1]) \
            .astype(np.int32)
        ts = np.broadcast_to(b * T * 40 + np.arange(T, dtype=np.int32) * 40,
                             (P, T)).copy()
        x = rng.uniform(0, 100, (P, T)).astype(np.float32)
        dense = {"ts": ts, "side": side, "f:x": x, "rows": ()}
        compact = {"ts": ts, "side": side, "f:x": x, "rows": ()}
        for names, bit in zip(groups, carriers):
            lane, tick = np.nonzero(side & bit)
            vals = rng.integers(-2**31, 2**31, (len(names), len(lane)),
                                dtype=np.int64).astype(np.int32)
            for name, v in zip(names, vals):
                dense[f"i:{name}"] = np.zeros((P, T), np.int32)
                dense[f"i:{name}"][lane, tick] = v
            order = rng.permutation(len(lane))
            idx, up = compact_rows(lane[order], tick[order], vals[:, order],
                                   P, T)
            assert (idx == P * T).any() and len(idx) >= P * T // 64
            compact["rows"] += ((idx, up),)
        carry["dense"], *want = dense_step(carry["dense"], dense, cap)
        carry["rows"], *got = rows_step(carry["rows"], compact, cap)
        for a, b_ in zip(jax.tree_util.tree_leaves(want + [carry["dense"]]),
                         jax.tree_util.tree_leaves(got + [carry["rows"]])):
            assert np.array_equal(np.asarray(a), np.asarray(b_))
    assert int(want[1][0]) > 0          # the blocks matched something


# ------------------------------------- (i) what stays on core/join.py

FALLBACKS = {
    "outer": ("from L#window.time(1 sec) left outer join "
              "R#window.time(1 sec) on L.id == R.id",
              "left outer join"),
    "non-equi": ("from L#window.time(1 sec) join R#window.time(1 sec) "
                 "on L.v > R.v", "no key equality"),
    "table": ("from L join T on L.id == T.id", "'T' is a table"),
    "named window": ("from L join W on L.id == W.id",
                     "'W' is a named window"),
    "length window": ("from L#window.length(5) join R#window.length(5) "
                      "on L.id == R.id", "#window.length has no keyed ring"),
    "no window": ("from L join R on L.id == R.id",
                  "neither side has a window"),
    "string residual": ("from L#window.time(1 sec) join "
                        "R#window.time(1 sec) on L.id == R.id and "
                        "L.s != R.s", "the residual compares 's'"),
    "object carried": ("from L join R#window.time(1 sec) on L.id == R.id",
                       "'R.o' (OBJECT) of a windowed side"),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_joins_that_stay_on_the_host_runtime(case):
    """Each with its reason, and with the rows the host engine gives."""
    frm, why = FALLBACKS[case]
    select = "select L.id as lid, L.v as lv, R.v as rv, R.o as ro" \
        if case == "object carried" else "select L.id as lid, L.v as lv"
    app = f"""@app:playback
        define stream L (id int, v float, s string, o object);
        define stream R (id int, v float, s string, o object);
        define table T (id int, v float);
        define window W (id int, v float) time(1 sec);
        define stream Fill (id int, v float);
        from Fill insert into T;
        from Fill insert into W;
        @info(name='q') {frm} {select} insert into Out;"""
    got = {}
    for mode in (None, "host"):
        rng = np.random.default_rng(23)
        sv = Serving(engine(app, mode))
        assert sv.qr.device_runtime is None and sv.qr.join_runtime is not None
        if mode is None:
            assert why in sv.qr.backend_reason, sv.qr.backend_reason
        for i in range(60):
            stream = ("L", "R", "Fill")[i % 3]
            c = {"id": np.asarray([int(rng.integers(0, 4))], np.int32),
                 "v": np.asarray([rng.uniform(0, 9)], np.float32)}
            if stream != "Fill":
                c["s"] = np.asarray([f"s{i % 2}"], object)
                c["o"] = np.empty(1, object)
                c["o"][0] = ("o", i % 2)
            sv.send(stream, c, np.asarray([3_000_000 + 40 * i]))
        got[mode] = sv.close()
    for k in got["host"]:
        assert (got[None][k] == got["host"][k]).all(), k
    if case not in ("no window",):
        assert len(got["host"]["__ts"]) > 0

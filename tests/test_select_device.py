"""Device selection-tail conformance (round 19): the query's having /
order-by / limit / offset tail compiled into the egress kernel
(plan/select_compiler.py + ops/select.py) must be VALUE-IDENTICAL to
the host QuerySelector over the same chunks — a randomized sweep over
group-by arity x having x order direction x limit/offset, plus the
blocked-shape routing contract, the SIDDHI_TPU_SELECT kill switch, and
persist/restore of the selector-bearing device state.

Reference: query/selector/QuerySelector.java:226-320 (order-by /
limit / offset post-processing), OrderByEventComparator."""
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.snapshot import InMemoryPersistenceStore

STREAM = "define stream S (sym string, user string, price float, " \
         "volume long);\n"


def run_batches(app, batches, engine=None):
    """Feed column batches through the public API; returns (device_hit,
    rows, selection routes by query name)."""
    prefix = "@app:playback "
    if engine:
        prefix += f"@app:engine('{engine}') "
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(prefix + app)
    out = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: out.extend(tuple(e.data) for e in evs)))
    rt.start()
    for cols, ts in batches:
        rt.get_input_handler("S").send_batch(cols, timestamps=ts)
    routes = {n: q.selection_route for n, q in rt.query_runtimes.items()}
    backends = {n: q.backend for n, q in rt.query_runtimes.items()}
    device = any(b == "device" for b in backends.values()) or \
        any(pr.device_mode for pr in rt.partition_runtimes)
    rt.shutdown()
    return device, out, routes


def _batches(n_chunks=2, n=48, seed=0, n_sym=3, n_user=4):
    """Integer-valued float prices: exact in f32, f64 and the device's
    two-float pairs alike, so sort keys tie identically on every path."""
    rng = np.random.default_rng(seed)
    out, t0 = [], 1_000_000
    for _ in range(n_chunks):
        cols = {
            "sym": np.asarray(
                [f"s{i}" for i in rng.integers(0, n_sym, n)], object),
            "user": np.asarray(
                [f"u{i}" for i in rng.integers(0, n_user, n)], object),
            "price": rng.integers(1, 100, n).astype(np.float32),
            "volume": rng.integers(-50, 50, n).astype(np.int64),
        }
        out.append((cols, t0 + np.arange(n, dtype=np.int64) * 100))
        t0 += n * 100
    return out


def _norm(rows):
    """Float payloads compare through float32 (conformance-corpus
    convention): host sums float64, device exact two-float f32."""
    return [tuple(float(np.float32(v)) if isinstance(v, float) else v
                  for v in r) for r in rows]


def assert_parity(app, batches, expect_device=True):
    _, host, _ = run_batches(app, batches, engine="host")
    dev, rows, routes = run_batches(app, batches)
    assert dev == expect_device, f"device={dev}"
    assert _norm(host) == _norm(rows), \
        f"host={host[:6]}... dev={rows[:6]}..."
    assert len(host) > 0
    return routes


# ------------------------------------------------- randomized sweep

AGGS = ("sum(price) as t, count() as n, max(price) as hi, "
        "min(volume) as lo")
HAVINGS = [None, "t > 50.0", "n >= 2", "not (t < 30.0)",
           "lo > -45 and n > 1", "hi >= 10.0 or lo < 0"]
ORDERS = [[], ["t desc"], ["n asc", "t desc"], ["hi asc"],
          ["lo desc", "n desc"]]


@pytest.mark.parametrize("seed", range(10))
def test_randomized_parity_sweep(seed):
    """Group-by arity x having x order-by direction x limit/offset,
    asserted EXACTLY against the host QuerySelector on the same chunks
    (running aggregation — no window — so limit/offset is
    device-legal)."""
    rng = np.random.default_rng(100 + seed)
    keys = ["sym"] if rng.integers(0, 2) == 0 else ["sym", "user"]
    having = HAVINGS[rng.integers(0, len(HAVINGS))]
    order = ORDERS[rng.integers(0, len(ORDERS))]
    limit = [None, 2, 3][rng.integers(0, 3)]
    offset = 1 if (limit is not None and rng.integers(0, 2)) else None
    q = (f"@info(name='q') from S select {', '.join(keys)}, {AGGS} "
         f"group by {', '.join(keys)}")
    if having:
        q += f" having {having}"
    if order:
        q += " order by " + ", ".join(order)
    if limit is not None:
        q += f" limit {limit}"
    if offset is not None:
        q += f" offset {offset}"
    q += " insert into Out;"
    routes = assert_parity(STREAM + q, _batches(n_chunks=3, seed=seed))
    active = bool(having or order or limit is not None or
                  offset is not None)
    if active:
        # the tail must actually ride the egress kernel, not merely
        # agree with the host by accident of a silent fallback
        assert routes["q"]["backend"] == "device", routes["q"]


def test_windowed_having_order_parity():
    """Sliding length window + having + multi-key order-by: one of the
    burned-down host-fallback shapes (docs/device_coverage.md)."""
    app = STREAM + (
        "@info(name='q') from S#window.length(4) "
        "select sym, sum(price) as t, max(price) as hi, count() as n "
        "group by sym having not (t < 10.0) "
        "order by hi desc, t asc insert into Out;")
    routes = assert_parity(app, _batches(n_chunks=2, seed=5))
    assert routes["q"]["backend"] == "device"


def test_time_window_having_order_parity():
    app = STREAM + (
        "@info(name='q') from S#window.time(10 sec) "
        "select sym, sum(price) as t group by sym "
        "having t > 20.0 order by t desc insert into Out;")
    routes = assert_parity(app, _batches(n_chunks=2, seed=6))
    assert routes["q"]["backend"] == "device"


def test_minmax_forever_having_order_parity():
    app = STREAM + (
        "@info(name='q') from S select sym, maxForever(price) as mx, "
        "minForever(volume) as mn, count() as n group by sym "
        "having mx > 5.0 order by mn asc insert into Out;")
    routes = assert_parity(app, _batches(n_chunks=2, seed=7))
    assert routes["q"]["backend"] == "device"


def test_keyed_having_per_key_parity():
    """Partitioned (keyed) having rides the device kernel; global
    emission order across keys differs from the host's per-key-sub-chunk
    oracle even WITHOUT selection (pre-existing chunking artifact, see
    test_device_grouped_agg.assert_parity unordered=...), so keyed
    parity is per-key subsequence equality."""
    app = STREAM + (
        "partition with (sym of S) begin\n"
        "@info(name='q') from S#window.length(4) "
        "select sym, sum(price) as t, count() as n group by sym "
        "having t > 20.0 insert into Out;\nend;")
    batches = _batches(n_chunks=2, seed=3)
    _, host, _ = run_batches(app, batches, engine="host")
    dev, rows, _ = run_batches(app, batches)
    assert dev
    assert len(host) > 0
    for s in sorted({r[0] for r in host} | {r[0] for r in rows}):
        assert _norm([r for r in host if r[0] == s]) == \
            _norm([r for r in rows if r[0] == s]), f"key {s}"


# --------------------------------------------- blocked-shape routing

@pytest.mark.parametrize("frag,reason_sub", [
    # float64 division: avg/stddev atoms never compile
    ("select sym, avg(price) as m group by sym having m > 1.0",
     "float64 division"),
    # exact int64 sum exceeds the two-float compare range
    ("select sym, sum(volume) as t group by sym having t > 10",
     "two-float compare"),
    # group-key columns live host-side
    ("select sym, count() as n group by sym having sym == 's1'",
     "key columns"),
])
def test_blocked_atoms_stay_host(frag, reason_sub):
    app = STREAM + f"@info(name='q') from S {frag} insert into Out;"
    routes = assert_parity(app, _batches(n_chunks=2, seed=9),
                           expect_device=False)
    route = routes["q"]
    assert route["backend"] == "host"
    assert reason_sub in route["reason"], route["reason"]


def test_windowed_limit_stays_host():
    """limit over a sliding window shares slots with expired rows on
    the host path — gated host-only, value-identical fallback."""
    app = STREAM + (
        "@info(name='q') from S#window.length(4) "
        "select sym, sum(price) as t group by sym "
        "having t > 0.0 order by t desc limit 2 insert into Out;")
    _, host, _ = run_batches(app, _batches(n_chunks=2, seed=4),
                             engine="host")
    dev, rows, routes = run_batches(app, _batches(n_chunks=2, seed=4))
    assert _norm(host) == _norm(rows)
    route = routes["q"]
    assert route["backend"] == "host"
    assert "expired" in route["reason"], route["reason"]


def test_keyed_order_limit_stays_host():
    """Partition clones don't surface per-clone selection_route; the
    static gate (analyzer SP012) carries the keyed routing verdict."""
    from siddhi_tpu.analysis import analyze
    app = STREAM + (
        "partition with (sym of S) begin\n"
        "@info(name='q') from S select sym, sum(price) as t "
        "group by sym order by t desc limit 1 insert into Out;\nend;")
    _, host, _ = run_batches(app, _batches(n_chunks=2, seed=8),
                             engine="host")
    _, rows, _ = run_batches(app, _batches(n_chunks=2, seed=8))
    assert len(host) > 0
    for s in sorted({r[0] for r in host} | {r[0] for r in rows}):
        assert _norm([r for r in host if r[0] == s]) == \
            _norm([r for r in rows if r[0] == s]), f"key {s}"
    sp012 = [d for d in analyze("@app:playback " + app).diagnostics
             if d.code == "SP012"]
    assert sp012 and "partition" in sp012[0].message, sp012


def test_select_kill_switch(monkeypatch):
    """SIDDHI_TPU_SELECT=0 pins a device-expressible tail back to the
    host selector — parity still holds, route says why."""
    monkeypatch.setenv("SIDDHI_TPU_SELECT", "0")
    app = STREAM + (
        "@info(name='q') from S select sym, sum(price) as t "
        "group by sym having t > 10.0 order by t desc limit 2 "
        "insert into Out;")
    routes = assert_parity(app, _batches(n_chunks=2, seed=10),
                           expect_device=False)
    route = routes["q"]
    assert route["backend"] == "host"
    assert "SIDDHI_TPU_SELECT" in route["reason"], route["reason"]


# ------------------------------------------------- persist / restore

def test_persist_restore_device_selector_state():
    """Snapshot a device run mid-stream, restore into a fresh runtime,
    continue — the continuation must equal the chunk-2 emissions of a
    continuously-fed host oracle (the selector itself is stateless; the
    state that must survive is the grouped-agg planes it selects
    over)."""
    body = STREAM + (
        "@info(name='q') from S select sym, sum(price) as t, "
        "count() as n group by sym having t > 20.0 "
        "order by t desc limit 3 insert into Out;")
    b1, b2 = _batches(n_chunks=2, seed=11)

    store = InMemoryPersistenceStore()
    m = SiddhiManager()
    m.set_persistence_store(store)
    rt = m.create_siddhi_app_runtime("@app:playback " + body)
    out1 = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: out1.extend(tuple(e.data) for e in evs)))
    rt.start()
    rt.get_input_handler("S").send_batch(b1[0], timestamps=b1[1])
    route = rt.query_runtimes["q"].selection_route
    assert route["backend"] == "device"
    # having + order-by + limit 3, all in the egress kernel's signature
    assert route["sig"].startswith("h1o1l3"), route
    rt.persist()
    rt.shutdown()

    m2 = SiddhiManager()
    m2.set_persistence_store(store)
    rt2 = m2.create_siddhi_app_runtime("@app:playback " + body)
    out2 = []
    rt2.add_callback("Out", StreamCallback(
        lambda evs: out2.extend(tuple(e.data) for e in evs)))
    rt2.start()
    rt2.restore_last_revision()
    assert rt2.query_runtimes["q"].selection_route["backend"] == "device"
    rt2.get_input_handler("S").send_batch(b2[0], timestamps=b2[1])
    rt2.shutdown()

    _, host, _ = run_batches(body, [b1], engine="host")
    mark = len(host)
    _, host_full, _ = run_batches(body, [b1, b2], engine="host")
    assert host_full[:mark] == host
    assert _norm(host_full[mark:]) == _norm(out2)
    assert len(out2) > 0

"""Flight recorder + incident bundles (core/flight.py, observability PR).

The always-on bounded ring of per-block records, the incident bus
(watchdog trips, circuit-breaker OPEN, quarantine bursts, buffer
overflow, junction exceptions, on-demand), bundle dump/retention, the
SIDDHI_TPU_FLIGHT kill switch, and the REST surface
(GET /incidents, GET /incidents/{id}/bundle,
POST /siddhi/apps/{app}/debug/bundle, GET /siddhi/apps/{app}/trace).
"""
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import QueryCallback, SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.flight import (FlightRecorder, flight,  # noqa: E402
                                    flight_enabled)
from siddhi_tpu.core.resilience import InMemoryErrorStore  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_flight(tmp_path, monkeypatch):
    """The recorder is process-global; isolate each test and point the
    bundle directory at tmp so tests never litter the real one."""
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT_DIR", str(tmp_path / "bundles"))
    flight().reset()
    yield
    flight().reset()
    from siddhi_tpu.core.tracing import tracer
    tracer().disable()
    tracer().clear()


# -------------------------------------------------------------- the ring

def test_ring_records_ingest_blocks():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (v float);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    rt.add_callback("Out", StreamCallback(lambda evs: None))
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(5):
        h.send([float(i)])
    rt.flush()
    ring = flight().ring()
    rt.shutdown()
    # compile rows (round 16) interleave with ingest rows on the
    # same ring — filter to the ingest records for this stream
    recs = [r for r in ring if r.get("stream") == "S"]
    assert len(recs) == 5
    r = recs[-1]
    assert r["app"] == rt.name and r["batch"] == 1
    assert {"block", "t", "dispatches", "scan_ticks",
            "queue_depth", "saturation"} <= set(r)
    blocks = [r["block"] for r in recs]
    assert blocks == sorted(blocks)


def test_kill_switch_disables_ring_and_bus(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT", "0")
    assert not flight_enabled()
    fl = flight()
    fl.record_block("a", stream="S", batch=1)
    assert fl.ring() == []
    assert fl.emit("on_demand", app="a") is None
    assert fl.incidents() == []


def test_ring_capacity_and_bundle_retention(tmp_path):
    fr = FlightRecorder(capacity=4, keep=2)
    for i in range(10):
        fr.record_block("a", stream="S", batch=i)
    assert len(fr.ring()) == 4
    assert [r["batch"] for r in fr.ring()] == [6, 7, 8, 9]
    ids = [fr.emit(f"k{i}", app="a")["id"] for i in range(3)]
    # all three incidents stay listed, only the newest 2 bundles retained
    assert [i["id"] for i in fr.incidents()] == ids
    assert fr.bundle(ids[0]) is None
    assert fr.bundle(ids[1]) is not None and fr.bundle(ids[2]) is not None
    d = os.environ["SIDDHI_TPU_FLIGHT_DIR"]
    kept = sorted(p for p in os.listdir(d) if p.endswith(".json"))
    assert len(kept) == 2


def test_errors_ride_the_ring():
    fl = flight()
    fl.note_error("a", "S", ValueError("boom"))
    fl.record_block("a", stream="S", batch=1)
    rec = fl.ring()[-1]
    assert rec["last_error"]["error"] == "ValueError: boom"
    assert rec["last_error"]["where"] == "S"


# ---------------------------------------------------------- incident bus

def test_watchdog_trip_emits_readable_bundle():
    """Forced SESSION_REARM_PATHOLOGY dispatch storm: the watchdog trip
    must land a 'watchdog_trip' bundle whose detail is the WD001
    incident and whose ring shows the blocks leading up to it."""
    import siddhi_tpu.plan.dwin_compiler as dwc
    cse = "define stream cse (symbol string, price float, volume long);\n"
    app = ("@app:playback " + cse +
           "@info(name='q') from cse#window.session(700, symbol) "
           "select symbol, price, volume insert all events into out;")
    dwc.SESSION_REARM_PATHOLOGY = True
    try:
        m = SiddhiManager()
        m.siddhi_context.error_store = InMemoryErrorStore()
        rt = m.create_siddhi_app_runtime(app)
        rt.add_callback("q", QueryCallback(lambda *a: None))
        rt.start()
        h = rt.get_input_handler("cse")

        def send(sym, ts):
            h.send_batch(
                {"symbol": np.asarray([sym], object),
                 "price": np.asarray([1.0], np.float32),
                 "volume": np.asarray([ts], np.int64)},
                np.asarray([ts], np.int64))

        send("A", 1000)
        send("C", 50_000)          # un-guarded: a ~49k-fire 1 ms crawl
        assert rt.watchdog.incidents, "storm did not trip the watchdog"
        incs = flight().incidents()
        assert any(i["kind"] == "watchdog_trip" for i in incs)
        bid = next(i["id"] for i in incs if i["kind"] == "watchdog_trip")
        bundle = flight().bundle(bid)
        assert bundle["detail"]["code"] == "WD001"
        assert bundle["app"] == rt.name
        assert any(r.get("stream") == "cse" for r in bundle["ring"])
        assert "env" in bundle and "config" in bundle
        json.dumps(bundle)         # fully JSON-serializable = readable
        d = os.environ["SIDDHI_TPU_FLIGHT_DIR"]
        assert json.load(open(os.path.join(d, f"{bid}.json")))["id"] == bid
        rt.shutdown()
        m.shutdown()
    finally:
        dwc.SESSION_REARM_PATHOLOGY = False


def test_circuit_open_emits_bundle():
    """A sink breaker's CLOSED -> OPEN transition is an incident."""
    import chaos
    chaos.reset()
    chaos.SCRIPTS["flightcb"] = chaos.FailureScript.fail_always()
    m = SiddhiManager()
    chaos.register(m)
    rt = m.create_siddhi_app_runtime("""
        @app:name('cbapp')
        define stream S (v int);
        @sink(type='chaos', chaos.id='flightcb', retry.max.attempts='1',
              retry.base.delay.ms='1', retry.jitter='0',
              circuit.failure.threshold='2', circuit.reset.ms='60000')
        define stream O (v int);
        @info(name='q') from S select v insert into O;
    """)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(6):
        h.send([i])
    assert chaos.INSTANCES["flightcb"].retry_join(30.0)
    incs = flight().incidents()
    assert any(i["kind"] == "circuit_open" and i["app"] == "cbapp"
               for i in incs), incs
    bid = next(i["id"] for i in incs if i["kind"] == "circuit_open")
    bundle = flight().bundle(bid)
    assert bundle["detail"]["sink"] == "O"
    assert bundle["detail"]["from"] == "closed"
    rt.shutdown()
    m.shutdown()


def test_quarantine_burst_emits_bundle(monkeypatch):
    """A single routing call rejecting >= the burst threshold is an
    incident (mass-poison feeds are a fault, not background noise)."""
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT_QUARANTINE_BURST", "5")
    m = SiddhiManager()
    m.set_error_store(InMemoryErrorStore())
    rt = m.create_siddhi_app_runtime("""
        @quarantine(ts.slack.ms='1000')
        define stream In (symbol string, price float, volume long);
        @info(name='q') from In select symbol, price, volume
        insert into Out;
    """)
    rt.add_callback("Out", StreamCallback(lambda evs: None))
    rt.start()
    h = rt.get_input_handler("In")
    nan = float("nan")
    h.send_batch({"symbol": np.asarray(["A"] * 8, object),
                  "price": np.asarray([nan] * 8, np.float32),
                  "volume": np.arange(8, dtype=np.int64)},
                 timestamps=1_000_000 + np.arange(8, dtype=np.int64))
    rt.flush()
    incs = flight().incidents()
    assert any(i["kind"] == "quarantine_burst" for i in incs), incs
    bid = next(i["id"] for i in incs if i["kind"] == "quarantine_burst")
    bundle = flight().bundle(bid)
    assert bundle["detail"]["rejected"] >= 5
    assert bundle["detail"]["stream"] == "In"
    rt.shutdown()
    m.shutdown()


def test_junction_exception_emits_bundle():
    """An uncaught subscriber exception (OnError LOG path) lands a
    'junction_exception' bundle and notes the error for the ring."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
    """)

    def boom(evs):
        raise RuntimeError("subscriber exploded")

    rt.add_callback("Out", StreamCallback(boom))
    rt.start()
    rt.get_input_handler("S").send([1])
    rt.flush()
    incs = flight().incidents()
    assert any(i["kind"] == "junction_exception" for i in incs), incs
    rt.shutdown()


# ------------------------------------------------------------------ REST

APP = """
@app:name('flightapp')
@app:statistics(reporter='console', interval='300', tracing='true',
                telemetry='true')
define stream S (sym string, price float);
@info(name='q')
from every e1=S[price > 10.0] -> e2=S[price > e1.price]
select e1.price as p1, e2.price as p2 insert into Out;
"""


def _req(method, url, payload=None):
    data = None
    if payload is not None:
        data = (payload if isinstance(payload, str)
                else json.dumps(payload)).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read().decode())


def test_rest_incident_surface():
    from siddhi_tpu.service.rest import SiddhiService
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", APP)
        rng = np.random.default_rng(0)
        _req("POST", f"{base}/siddhi/apps/flightapp/streams/S",
             [{"data": ["A", float(rng.uniform(5, 30))]}
              for _ in range(20)])
        svc.manager.get_siddhi_app_runtime("flightapp").flush()

        assert _req("GET", f"{base}/incidents") == {"incidents": []}

        out = _req("POST", f"{base}/siddhi/apps/flightapp/debug/bundle",
                   {"note": "operator snapshot"})
        assert out["kind"] == "on_demand"
        incs = _req("GET", f"{base}/incidents")["incidents"]
        assert [i["id"] for i in incs] == [out["id"]]

        bundle = _req("GET", f"{base}/incidents/{out['id']}/bundle")
        assert bundle["detail"]["note"] == "operator snapshot"
        # 20 ingest rows; compile rows (round 16) ride the same ring
        ingest = [r for r in bundle["ring"] if r.get("stream")]
        assert len(ingest) == 20
        assert any("compile" in r for r in bundle["ring"])
        assert any(ln.startswith("siddhi_kernel_")
                   for ln in bundle["metrics"])
        assert bundle["trace"]["traceEvents"]
        assert bundle["statistics"]["telemetry"]["nfa"]["q"]

        # unknown bundle id → 404
        try:
            _req("GET", f"{base}/incidents/inc-9999/bundle")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

        # Chrome-trace parity route
        doc = _req("GET", f"{base}/siddhi/apps/flightapp/trace")
        assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"
        names = {e["name"] for e in doc["traceEvents"]}
        assert "ingest.chunk" in names
    finally:
        svc.stop()


def test_rest_bundle_409_when_disabled(monkeypatch):
    from siddhi_tpu.service.rest import SiddhiService
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT", "0")
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", APP)
        try:
            _req("POST", f"{base}/siddhi/apps/flightapp/debug/bundle", {})
            assert False, "expected 409"
        except urllib.error.HTTPError as e:
            assert e.code == 409
    finally:
        svc.stop()

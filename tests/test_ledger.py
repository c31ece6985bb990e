"""Latency ledger, lag watermarks and the SLO engine (core/ledger.py).

Covers: nest-aware exclusive-time spans, the SIDDHI_TPU_LEDGER kill
switch, per-block folds into per-app histograms, event-time lag
watermarks, @app:slo parsing + burn-rate evaluation, the SLO001
incident bundle with waterfall evidence, the REST/statistics surfaces,
and the SA07x analyzer diagnostics.
"""
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.flight import flight  # noqa: E402
from siddhi_tpu.core.ledger import (LEDGER_ENV, STAGES,  # noqa: E402
                                    LatencyLedger, SloConfig, ledger,
                                    ledger_enabled)


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    """Ledger and flight recorder are process-global; isolate each test
    and point the bundle dir at tmp."""
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT_DIR", str(tmp_path / "bundles"))
    ledger().reset()
    flight().reset()
    yield
    ledger().reset()
    flight().reset()


# ------------------------------------------------------------------ spans

class _VirtualClock:
    """Deterministic stand-in for perf_counter_ns — spans read whatever
    the test dialed in, so exclusive-time math asserts exact nanoseconds
    instead of racing the scheduler."""

    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.ns

    def tick(self, ms):
        self.ns += int(ms * 1_000_000)


def test_span_records_exclusive_time(monkeypatch):
    import siddhi_tpu.core.ledger as ledger_mod
    clock = _VirtualClock()
    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    led = LatencyLedger()
    with led.span("dispatch"):
        clock.tick(2)
        with led.span("device"):
            clock.tick(5)
        clock.tick(2)
    ns = led.stage_ns()
    # device gets its own elapsed; dispatch gets only the surrounding
    # host time — NOT dispatch+device double counted
    assert ns["device"] == 5_000_000
    assert ns["dispatch"] == 4_000_000
    assert ns["dispatch"] + ns["device"] == 9_000_000


def test_span_nesting_three_deep(monkeypatch):
    import siddhi_tpu.core.ledger as ledger_mod
    clock = _VirtualClock()
    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    led = LatencyLedger()
    with led.span("dispatch"):
        clock.tick(1)
        with led.span("decode"):
            with led.span("publish"):
                clock.tick(3)
            clock.tick(1)
    ns = led.stage_ns()
    # outer spans only carry their own exclusive time, not the child's
    assert ns["publish"] == 3_000_000
    assert ns["decode"] == 1_000_000
    assert ns["dispatch"] == 1_000_000


def test_kill_switch_disables_spans_and_blocks(monkeypatch):
    monkeypatch.setenv(LEDGER_ENV, "0")
    assert not ledger_enabled()
    led = LatencyLedger()
    with led.span("device"):
        time.sleep(0.001)
    assert led.stage_ns()["device"] == 0

    class Owner:
        pass

    assert led.note_block("a", Owner()) is None
    monkeypatch.setenv(LEDGER_ENV, "1")
    assert ledger_enabled()


def test_record_clamps_negative():
    led = LatencyLedger()
    led.record("queue", -50)
    assert led.stage_ns()["queue"] == 0


# ------------------------------------------------------------- note_block

class _Owner:
    pass


def test_note_block_folds_deltas_into_histograms():
    led = LatencyLedger()
    o = _Owner()
    assert led.note_block("app1", o) is None     # first call: baseline
    led.record("device", 3_000_000)
    led.record("ingress", 1_000_000)
    row = led.note_block("app1", o)
    assert row == {"device": 3.0, "ingress": 1.0}
    snap = led.snapshot(app="app1")
    stages = snap["apps"]["app1"]["stages_ms"]
    assert stages["device"]["count"] == 1
    assert stages["total"]["count"] == 1
    assert abs(stages["device"]["mean"] - 3.0) < 0.5
    assert snap["apps"]["app1"]["last_block_ms"]["device"] == 3.0


def test_note_block_row_skipped_when_not_wanted():
    led = LatencyLedger()
    o = _Owner()
    led.note_block("app1", o)
    led.record("device", 2_000_000)
    assert led.note_block("app1", o, want_row=False) is None
    # ... but the histogram fold still happened
    stages = led.snapshot(app="app1")["apps"]["app1"]["stages_ms"]
    assert stages["device"]["count"] == 1


def test_deferred_fold_drains_on_every_read_surface():
    led = LatencyLedger()
    o = _Owner()
    led.note_block("a", o)
    for _ in range(5):
        led.record("device", 1_000_000)
        led.note_block("a", o)
    # buffered, then folded lazily by prometheus_lines
    lines = led.prometheus_lines()
    assert any(l.startswith("siddhi_ledger_stage_latency_ms") and
               'app="a"' in l for l in lines)
    assert led.snapshot(app="a")["apps"]["a"]["stages_ms"][
        "device"]["count"] == 5


# ------------------------------------------------------- lag watermarks

def test_note_ingress_lag_watermark():
    led = LatencyLedger()
    led.note_ingress("app1", "S", event_ts_ms=1_000,
                     now_ms=1_750.0, dur_ns=10_000)
    snap = led.snapshot(app="app1")
    lag = snap["apps"]["app1"]["lag"]["S"]
    assert lag["lag_ms"] == 750.0
    assert lag["processing_lag_ms"] >= 0
    assert led.stage_ns()["ingress"] == 10_000
    lines = led.prometheus_lines()
    assert any(l.startswith("siddhi_event_time_lag_ms") and "750" in l
               for l in lines)
    assert any(l.startswith("siddhi_processing_lag_ms") for l in lines)


# ------------------------------------------------------------ SLO config

def test_slo_config_from_annotation():
    from siddhi_tpu.query_api.annotation import Annotation
    ann = (Annotation("app:slo")
           .element("latency.p99.ms", "250")
           .element("lag.ms", "1500")
           .element("window.blocks", "32")
           .element("breach.blocks", "5"))
    cfg = SloConfig.from_annotation(ann)
    assert cfg.latency_p99_ms == 250.0
    assert cfg.lag_ms == 1500.0
    assert cfg.window_blocks == 32
    assert cfg.breach_blocks == 5


def test_slo_config_tolerates_malformed_values():
    from siddhi_tpu.query_api.annotation import Annotation
    ann = (Annotation("app:slo")
           .element("latency.p99.ms", "fast")
           .element("window.blocks", "-3"))
    cfg = SloConfig.from_annotation(ann)
    assert cfg.latency_p99_ms is None          # malformed -> default
    assert cfg.window_blocks == 128
    assert cfg.breach_blocks == 3


def test_slo_breach_needs_consecutive_blocks():
    led = LatencyLedger()
    led.register_slo("a", SloConfig(latency_p99_ms=0.001,
                                    window_blocks=8, breach_blocks=3))
    o = _Owner()
    led.note_block("a", o)
    transitions = []
    for _ in range(8):
        led.record("device", 5_000_000)        # 5 ms >> 0.001 ms target
        st = led._slo["a"]
        before = st.breached
        led.note_block("a", o)
        if st.breached and not before:
            transitions.append(st.consecutive)
    assert led.slo_breached("a")
    assert len(transitions) == 1               # one transition, once
    st = led._slo["a"]
    assert st.breach_total == 1
    assert st.burn_latency > 1.0


def test_slo_recovery_clears_breach():
    led = LatencyLedger()
    led.register_slo("a", SloConfig(latency_p99_ms=1e9,
                                    window_blocks=8, breach_blocks=1))
    st = led._slo["a"]
    st.breached = True
    st.consecutive = 3
    assert st.observe(0.5, None) is False      # under target
    assert not st.breached
    assert st.consecutive == 0


def test_slo_breach_emits_slo001_bundle_with_waterfall():
    led = ledger()
    led.register_slo("appX", SloConfig(latency_p99_ms=0.000001,
                                       window_blocks=8, breach_blocks=2))
    o = _Owner()
    led.note_block("appX", o)
    for _ in range(8):
        led.record("device", 2_000_000)
        led.record("decode", 500_000)
        led.note_block("appX", o)
    assert led.slo_breached("appX")
    incs = [i for i in flight().incidents() if i["kind"] == "slo_breach"]
    assert len(incs) == 1
    bundle = flight().bundle(incs[0]["id"])
    det = bundle["detail"]
    assert det["code"] == "SLO001"
    assert det["slo"]["latency.p99.ms"] == 0.000001
    assert det["observed"]["breached"] is True
    # the breach ships its own waterfall evidence
    assert det["waterfall"]["device"] == 2.0
    assert det["waterfall"]["decode"] == 0.5
    assert det["stage_summary_ms"]["device"]["count"] >= 1


# -------------------------------------------------- runtime integration

def test_app_slo_annotation_registers_and_drops():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('sloapp') "
        "@app:slo(latency.p99.ms='250', lag.ms='1500') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    assert rt.slo_config is not None
    assert rt.slo_config.latency_p99_ms == 250.0
    assert "sloapp" in ledger()._slo
    rt.start()
    rt.shutdown()
    assert "sloapp" not in ledger()._slo        # drop_app on shutdown


def test_engine_block_produces_full_waterfall():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('wfapp') "
        "define stream S (sym string, price float); "
        "@info(name='q') from S[price > 0.0] "
        "select sym, price insert into Out;")
    got = []
    rt.add_callback("Out", StreamCallback(lambda evs: got.append(len(evs))))
    rt.start()
    h = rt.get_input_handler("S")
    cols = {"sym": np.asarray(["A"] * 16, object),
            "price": np.arange(1.0, 17.0)}
    for i in range(4):
        h.send_batch(cols, 1_000 + i * 16 + np.arange(16, dtype=np.int64))
    rt.flush()
    snap = rt.statistics
    lg = snap["ledger"]
    assert lg["enabled"]
    stages = lg["apps"]["wfapp"]["stages_ms"]
    # ingress + dispatch + device all saw blocks (first block is the
    # delta baseline, so count >= 2)
    for stage in ("ingress", "dispatch", "device", "total"):
        assert stages[stage]["count"] >= 2, (stage, stages)
    assert lg["apps"]["wfapp"]["lag"]["S"]["lag_ms"] is not None
    last = lg["apps"]["wfapp"]["last_block_ms"]
    assert last.get("device", 0) > 0
    # the flight ring rows carry the per-block waterfall
    rows = [r for r in flight().ring() if r.get("app") == "wfapp"
            and "ledger" in r]
    assert rows and rows[-1]["ledger"].get("device", 0) > 0
    rt.shutdown()


def test_ledger_kill_switch_end_to_end(monkeypatch):
    monkeypatch.setenv(LEDGER_ENV, "0")
    ledger().reset()
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('offapp') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(3):
        h.send([float(i + 1)])
    rt.flush()
    snap = rt.statistics["ledger"]
    assert snap["enabled"] is False
    assert all(v == 0 for v in snap["stage_seconds"].values())
    assert "offapp" not in snap["apps"] or not snap["apps"]["offapp"].get(
        "stages_ms")
    rt.shutdown()


# ----------------------------------------------------------- REST + /slo

def _rest(method, url, payload=None):
    data = None
    if payload is not None:
        data = (payload if isinstance(payload, str)
                else json.dumps(payload)).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def test_rest_slo_surface_and_health_degradation():
    from siddhi_tpu.service.rest import SiddhiService
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _rest("POST", f"{base}/siddhi/artifact/deploy",
              "@app:name('slorest') "
              "@app:slo(latency.p99.ms='0.000001', window.blocks='8', "
              "breach.blocks='2') "
              "define stream S (v float); "
              "@info(name='q') from S[v > 0.0] select v insert into Out;")
        for i in range(12):
            _rest("POST", f"{base}/siddhi/apps/slorest/streams/S",
                  [{"data": [float(j + 1)]} for j in range(4)])
        svc.manager.get_siddhi_app_runtime("slorest").flush()
        slo = _rest("GET", f"{base}/slo")
        assert slo["enabled"]
        app_slo = slo["apps"]["slorest"]["slo"]
        assert app_slo["config"]["latency.p99.ms"] == 0.000001
        assert app_slo["breached"] is True
        assert app_slo["burn_rate"]["latency_p99"] > 1.0
        health = _rest("GET", f"{base}/health")
        assert health["apps"]["slorest"]["slo_breached"] is True
        assert health["status"] == "degraded"
        # burn-rate gauges ride /metrics
        req = urllib.request.Request(f"{base}/metrics")
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        assert "siddhi_slo_burn_rate" in text
        assert 'siddhi_slo_breach_active{app="slorest"} 1' in text
        assert "siddhi_ledger_stage_seconds_total" in text
    finally:
        svc.stop()


# ------------------------------------------------------- SA07x analyzer

def test_analyzer_sa070_invalid_slo():
    from siddhi_tpu.analysis import analyze
    res = analyze(
        "@app:name('a') @app:slo(latency.p99.ms='fast') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    assert any(d.code == "SA070" for d in res.diagnostics)


def test_analyzer_sa071_unknown_option():
    from siddhi_tpu.analysis import analyze
    res = analyze(
        "@app:name('a') @app:slo(latency.p99.ms='250', latencyy='1') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    codes = [d.code for d in res.diagnostics]
    assert "SA071" in codes and "SA070" not in codes


def test_analyzer_sa072_no_targets():
    from siddhi_tpu.analysis import analyze
    res = analyze(
        "@app:name('a') @app:slo(window.blocks='16') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    assert any(d.code == "SA072" for d in res.diagnostics)


def test_analyzer_clean_slo_no_diagnostics():
    from siddhi_tpu.analysis import analyze
    res = analyze(
        "@app:name('a') @app:slo(latency.p99.ms='250', lag.ms='1000') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    assert not [d for d in res.diagnostics if d.code.startswith("SA07")]


# ------------------------------------------- named spans, waits, one source
# (PR 26: the ledger's span is the program's one span source — named
# sub-spans, the profiler's clock, the waits of a block in flight)

from siddhi_tpu.core.ledger import (ANNOTATIONS, SPAN_NAMES,  # noqa: E402
                                    WAITS)
from siddhi_tpu.core.tracing import tracer  # noqa: E402


def _nested_run(led, clock, name):
    with led.span("device"):
        clock.tick(5)
        with led.span("device", name):
            clock.tick(3)
        clock.tick(2)


def test_named_span_fills_its_key_and_leaves_the_stage_total(monkeypatch):
    import siddhi_tpu.core.ledger as ledger_mod
    clock = _VirtualClock()
    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    named, plain = LatencyLedger(), LatencyLedger()
    _nested_run(named, clock, "pack")
    _nested_run(plain, clock, None)
    assert named.stage_ns()["device"] == plain.stage_ns()["device"] \
        == 10_000_000
    assert named.stage_ns()["device.pack"] == 3_000_000
    assert plain.stage_ns()["device.pack"] == 0
    assert named.snapshot()["stage_spans"] == plain.snapshot()["stage_spans"]
    # the seven stages first, the declared sub-spans beside them
    assert list(named.stage_ns()) == list(STAGES + SPAN_NAMES)


def test_undeclared_span_name_is_refused():
    with pytest.raises(KeyError):
        LatencyLedger().span("device", "no_such_span")


def test_span_without_stage_credits_nothing_and_hands_its_block_on(
        monkeypatch):
    import siddhi_tpu.core.ledger as ledger_mod
    clock = _VirtualClock()
    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    led = LatencyLedger()
    seen = []
    # nobody records (no profiler session, exporter off): a span given a
    # block hands it on all the same, so spans nest alike traced or not
    assert not tracer().enabled
    with led.span("dispatch", None, 4, "a"):
        clock.tick(1)
        with led.span(None, "match.scatter", 9, "a"):
            clock.tick(1)
            with led.span("publish"):
                seen.append(led.current_block())
                clock.tick(2)
        with led.span(None, "parse"):       # only an annotation
            seen.append(led.current_block())
    ns = led.stage_ns()
    assert seen == [9, 4]
    assert ns["publish"] == 2_000_000
    assert ns["dispatch"] == 2_000_000      # the annotation's own time stays
    assert sum(ns.values()) == 4_000_000
    assert led.snapshot()["stage_spans"]["dispatch"] == 1
    # a name the program does not list is refused, with a stage or without
    with pytest.raises(KeyError):
        led.span(None, "no_such_annotation")


def _launch_run(led, clock, launch):
    import contextlib
    with led.span("dispatch", None, 7, "a"):
        clock.tick(1)
        with led.span("device", "sync"):
            clock.tick(2)
            with (led.span(None, "device.issue/some.kind") if launch
                  else contextlib.nullcontext()):
                clock.tick(3)
                with led.span("egress_d2h"):
                    clock.tick(1)
            clock.tick(1)


def test_the_launch_span_has_no_stage_and_moves_none(monkeypatch):
    """`device.issue` is opened without a stage, wherever a registry-jitted
    call is made from: its time stays with the stage that called it, no
    stage counts a span more, and the sub-spans stay exclusive of one
    another (`device.sync` loses what `device.issue` took)."""
    import siddhi_tpu.core.ledger as ledger_mod
    clock = _VirtualClock()
    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    with_launch, without = LatencyLedger(), LatencyLedger()
    _launch_run(with_launch, clock, True)
    _launch_run(without, clock, False)
    a, b = with_launch.stage_ns(), without.stage_ns()
    assert {s: a[s] for s in STAGES} == {s: b[s] for s in STAGES}
    assert a["device"] == 6_000_000 and a["egress_d2h"] == 1_000_000
    assert with_launch.snapshot()["stage_spans"] == \
        without.snapshot()["stage_spans"]
    assert a["device.issue"] == 3_000_000 and b["device.issue"] == 0
    assert a["device.sync"] == 3_000_000 and b["device.sync"] == 6_000_000
    # the launch itself on no stack at all (a prewarm): its key only
    with with_launch.span(None, "device.issue/some.kind"):
        clock.tick(5)
    after = with_launch.stage_ns()
    assert after["device.issue"] == 8_000_000
    assert {s: after[s] for s in STAGES} == {s: a[s] for s in STAGES}


_JOIN_APP = """@app:playback
define stream L (id int, price float);
define stream R (id int, threshold float);
@info(name='q')
from L#window.length(5) join R#window.length(5)
    on L.price > R.threshold and L.id == R.id
select L.id as lid, L.price as p, R.threshold as t insert into Out;"""

_DWIN_APP = """@app:playback
define stream L (id int, price float);
@info(name='q') from L#window.length(5) select id, price insert into Out;"""


@pytest.mark.parametrize("app,streams", [(_JOIN_APP, ("L", "R")),
                                         (_DWIN_APP, ("L",))],
                         ids=["join", "dwin"])
def test_launches_outside_a_device_span_stay_with_their_stage(
        app, streams, monkeypatch):
    """The join probe and the device-window steps are registry-jitted
    calls made under `dispatch`, with no `device` span around them: with
    the launch span there and with it taken out, every stage counts the
    same spans, `device` gets nothing, and what the span's own two clock
    reads cost stays in `dispatch` (a clock that counts its reads makes
    the two runs comparable to the nanosecond)."""
    import contextlib

    import siddhi_tpu.core.ledger as ledger_mod
    import siddhi_tpu.plan.shapes as shapes_mod
    from siddhi_tpu import StreamCallback
    tick = 1_000
    reads = [0]

    def clock():
        reads[0] += tick
        return reads[0]

    monkeypatch.setattr(ledger_mod, "_pcns", clock)
    # the junction's gap stamps are on the wall clock: leave them out
    monkeypatch.setattr(LatencyLedger, "record", lambda self, st, ns: None)
    led = ledger()
    rng = np.random.default_rng(4)
    sends = [(streams[int(rng.integers(0, len(streams)))],
              [int(rng.integers(0, 5)), float(np.float32(rng.uniform(0, 100)))],
              1_000_000 + 100 * i) for i in range(30)]

    def run():
        led.reset()
        rt = SiddhiManager().create_siddhi_app_runtime(app)
        rows = []
        rt.add_callback("Out", StreamCallback(rows.extend))
        rt.start()
        for sid, row, ts in sends:
            rt.get_input_handler(sid).send(row, timestamp=ts)
        assert rt.query_runtimes["q"].backend == "device"
        rt.shutdown()
        return led.stage_ns(), dict(led.snapshot()["stage_spans"]), len(rows)

    named, spans_named, rows_named = run()
    launches = [0]

    def no_span(stage, name):
        # count the launches of a delivery (one at build time is on no
        # stack, and credits no stage either way)
        launches[0] += led.current_block() is not None
        return contextlib.nullcontext()

    monkeypatch.setattr(shapes_mod, "_span", no_span)
    plain, spans_plain, rows_plain = run()
    assert rows_named == rows_plain > 0 and launches[0] > 0
    assert spans_named == spans_plain and spans_named["device"] == 0
    assert named["device"] == plain["device"] == 0
    assert plain["device.issue"] == 0 < named["device.issue"]
    for stage in ("egress_d2h", "decode", "publish"):
        assert named[stage] == plain[stage], stage
    assert named["dispatch"] - plain["dispatch"] == 2 * tick * launches[0]


def test_note_retire_banks_waits_and_counts(monkeypatch):
    from siddhi_tpu.core.ledger import ON_DEPTH, ON_FLUSH, ON_READY
    led = LatencyLedger()
    led.note_retire("a", 1_000_000, 3_000_000, 9_000_000, True, ON_READY)
    led.note_retire("a", 2_000_000, 2_000_000, 4_000_000, False, ON_DEPTH)
    # dispatched ledger-off
    led.note_retire("a", None, None, 5_000_000, True, ON_FLUSH)
    app = led.snapshot("a")["apps"]["a"]
    assert app["retire_ready_total"] == 2
    assert app["retire_blocked_total"] == 1
    # what caused each retire, beside whether its result was there
    assert (app["retire_on_ready_total"], app["retire_on_depth_total"],
            app["retire_on_flush_total"]) == (1, 1, 1)
    assert app["stages_ms"]["wait.inflight"]["count"] == 2
    assert app["stages_ms"]["wait.defer"]["count"] == 2
    assert app["stages_ms"]["wait.defer"]["min"] == 0.0
    assert app["stages_ms"]["wait.inflight"]["max"] == pytest.approx(8.0)
    text = "\n".join(led.prometheus_lines())
    assert 'siddhi_retire_ready_total{app="a"} 2' in text
    assert 'siddhi_retire_blocked_total{app="a"} 1' in text
    assert 'siddhi_retire_on_depth_total{app="a"} 1' in text
    assert 'siddhi_ledger_span_seconds_total{span="device.pack"}' in text
    led.drop_app("a")
    assert "a" not in led.snapshot()["apps"]
    assert led.snapshot("a")["apps"]["a"] == {"stages_ms": {}}


_TWO_QUERIES = """@app:name('twoq') @app:playback {head}
define stream S (sym string, price float, kind int);
partition with (sym of S) begin
@info(name='q0')
from every e1=S[kind == 0 and price > 50.0]
    -> e2=S[kind == 1 and price > e1.price] within 1 sec
select e1.sym as sym, e1.price as p1, e2.price as p2 insert into Out0;
@info(name='q1')
from every e1=S[kind == 0 and price > 60.0]
    -> e2=S[kind == 1 and price > e1.price] within 1 sec
select e1.sym as sym, e1.price as p1, e2.price as p2 insert into Out1;
end;
"""


@pytest.fixture
def single_device(monkeypatch):
    """The suite's eight virtual devices would mesh-shard the patterns;
    one device is what a served chip has, and there they gang."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    yield
    # a partition's device queries are not shut down with their app, so
    # their automata would stay in the process-wide gang: take them out,
    # for the files that count its buckets (tests/test_multitenant.py)
    from siddhi_tpu.plan.xtenant import tenant_packer
    packer = tenant_packer()
    for row in list(packer.buckets.values()):
        for bucket in list(row):
            for nfa in list(bucket.tenants):
                if getattr(nfa, "_xt_label", "").startswith("twoq/"):
                    packer.evict(nfa)


def _drive_two_queries(head, blocks=7, before=None):
    """Two pattern queries of one partition over one stream, `blocks`
    synchronous sends (each a block of its own); -> the app's ledger
    entry at the end, rows delivered."""
    from siddhi_tpu import ColumnarStreamCallback
    rt = SiddhiManager().create_siddhi_app_runtime(
        _TWO_QUERIES.format(head=head))
    rows = [0]
    for out in ("Out0", "Out1"):
        rt.add_callback(out, ColumnarStreamCallback(
            lambda c: rows.__setitem__(0, rows[0] + len(c))))
    rt.start()
    h = rt.get_input_handler("S")
    rng = np.random.default_rng(5)
    n = 48
    for i in range(blocks):
        if before is not None:
            before(i)
        h.send_batch(
            {"sym": np.asarray([f"k{j % 5}" for j in range(n)], object),
             "price": rng.uniform(0, 100, n).astype(np.float32),
             "kind": rng.integers(0, 2, n).astype(np.int64)},
            timestamps=1_000 + 10 * n * i + 10 * np.arange(n, dtype=np.int64))
    rt.flush()
    entry = rt.statistics["ledger"]["apps"]["twoq"]
    totals = ledger().stage_ns()
    rt.shutdown()
    return entry, totals, rows[0]


def test_named_histograms_hold_one_entry_per_execution_with_two_owners(
        single_device):
    """The case note_block gets wrong: with two queries on one stream its
    entries each span both queries' stage time.  A named span's histogram
    holds that span's own time, once per execution, so it adds up to the
    accumulator."""
    blocks = 7
    entry, totals, rows = _drive_two_queries("@app:pipeline('4')", blocks)
    assert rows > 0
    st = entry["stages_ms"]
    for key in ("dispatch.keys", "dispatch.lanes", "dispatch.cols",
                "device.encode", "device.pack", "device.retire"):
        assert st[key]["count"] == 2 * blocks, (key, st[key])
        assert st[key]["mean"] * st[key]["count"] == pytest.approx(
            totals[key] / 1e6, rel=1e-6), key
    # one gang flush per block steps both tenants: one sync around one
    # launch (which has no stage, so an accumulator and no histogram)
    assert st["device.sync"]["count"] == blocks
    assert "device.issue" not in st and totals["device.issue"] > 0


@pytest.mark.parametrize("head,depth", [("@app:pipeline('4')", 4), ("", 0)])
def test_waits_of_a_block_in_flight(single_device, head, depth):
    blocks = 7
    entry, _totals, rows = _drive_two_queries(head, blocks)
    assert rows > 0
    st = entry["stages_ms"]
    assert st["wait.defer"]["count"] == st["wait.inflight"]["count"] \
        == 2 * blocks
    assert entry["retire_ready_total"] + entry["retire_blocked_total"] \
        == 2 * blocks
    assert st["wait.defer"]["max"] <= st["wait.inflight"]["max"]
    if depth == 0:
        # every ingest retires inside itself, before any flush launched
        # the gang: the retire's own resolve does, so none was ready and
        # the (short) time in flight is all deferral
        assert entry["retire_blocked_total"] == 2 * blocks
        assert st["wait.defer"]["mean"] == pytest.approx(
            st["wait.inflight"]["mean"])
        assert st["device.retire"]["count"] == 2 * blocks
    else:
        # a block is launched by the next block's sync (these sends are
        # synchronous: no junction worker settles them) and retired by
        # the first later submit that finds its result ready, at the
        # latest by the fifth, which blocks on it (the cap): deferred
        # for less than it is in flight
        assert st["wait.defer"]["mean"] < st["wait.inflight"]["mean"]
        assert entry["retire_ready_total"] > 0
        # the last block of either query is still pending at the closing
        # flush: the first query's launches the gang and waits for it
        assert entry["retire_on_flush_total"] >= 2
        assert entry["retire_blocked_total"] >= 1
    assert entry["retire_on_ready_total"] + entry["retire_on_depth_total"] \
        + entry["retire_on_flush_total"] == 2 * blocks


def test_naming_sub_spans_moves_no_stage_total(single_device, monkeypatch):
    """The same fixed tiny app with the spans named and un-named: each
    stage counts as many spans, and a sub-span's time is part of its own
    stage's."""
    blocks = 5
    led = ledger()
    _e, named, _r = _drive_two_queries("@app:pipeline('4')", blocks)
    spans_named = dict(led.snapshot()["stage_spans"])
    led.reset()
    orig = LatencyLedger.span
    monkeypatch.setattr(
        LatencyLedger, "span",
        lambda self, stage, name=None, block=None, app=None: orig(
            self, stage, None if stage is not None else name, block, app))
    _e, plain, _r = _drive_two_queries("@app:pipeline('4')", blocks)
    assert led.snapshot()["stage_spans"] == spans_named
    assert all(plain[k] == 0 for k in SPAN_NAMES if k != "device.issue")
    for stage in STAGES:
        subs = sum(v for k, v in named.items()
                   if k.startswith(stage + ".") and k != "device.issue")
        assert subs <= named[stage], stage
        assert (named[stage] > 0) == (plain[stage] > 0), stage


def test_spans_lie_on_the_profilers_clock_and_not_in_the_chrome_buffer(
        single_device, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tracer().clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def start(i):
        if i == 2:              # the first two blocks compile
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    try:
        _drive_two_queries("@Async(buffer.size='8')", 6, before=start)
    finally:
        jax.profiler.stop_trace()
    names, with_block = set(), set()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("siddhi/"):
                        names.add(ev.name)
                        if any(k == "block" for k, _v in ev.stats):
                            with_block.add(ev.name)
    assert {"siddhi/dispatch", "siddhi/dispatch.keys", "siddhi/device.pack",
            "siddhi/device.sync", "siddhi/device.issue/nfa.xstep",
            "siddhi/device.retire", "siddhi/publish", "siddhi/queue.idle",
            "siddhi/ingest.chunk"} <= names, names
    assert {"siddhi/dispatch", "siddhi/device.pack", "siddhi/device.retire",
            "siddhi/device.issue/nfa.xstep", "siddhi/publish"} <= with_block
    # the operator's exporter is fed only when tracing='true'
    assert tracer().to_dict()["traceEvents"] == []


def test_tracing_true_feeds_the_exporter_from_the_same_spans(single_device):
    tracer().clear()
    try:
        _drive_two_queries(
            "@app:statistics(reporter='console', interval='300', "
            "tracing='true')", 3)
        evs = tracer().to_dict()["traceEvents"]
    finally:
        tracer().disable()
        tracer().clear()
    names = {e["name"] for e in evs}
    assert {"ingest.chunk", "dispatch", "dispatch.keys", "device.pack",
            "device.issue/nfa.xstep", "publish"} <= names, names
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans and all(e["dur"] >= 0 and e["ts"] > 0 for e in spans)
    assert any(e.get("args", {}).get("block") for e in spans)


def test_enabling_the_exporter_with_the_ledger_off_says_so(
        monkeypatch, caplog):
    """The ledger's spans are the exporter's only source: with the
    ledger's switch off the trace stays empty, and enabling it warns."""
    monkeypatch.setenv(LEDGER_ENV, "0")
    tracer().clear()
    try:
        with caplog.at_level("WARNING", logger="siddhi_tpu.core.tracing"):
            tracer().enable()
        assert LEDGER_ENV in caplog.text
        with ledger().span("device"):
            pass
        assert tracer().to_dict()["traceEvents"] == []
        monkeypatch.setenv(LEDGER_ENV, "1")
        with ledger().span("device"):
            pass
        assert [e["name"] for e in tracer().to_dict()["traceEvents"]] == \
            ["device"]
    finally:
        tracer().disable()
        tracer().clear()


def test_declared_names_are_what_the_docs_list():
    assert set(WAITS) == {"wait.defer", "wait.inflight"}
    assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "observability.md")).read()
    for key in SPAN_NAMES + WAITS + ANNOTATIONS:
        assert f"`{key}`" in doc, key

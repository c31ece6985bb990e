"""The ledger's second clock (core/ledger.py): a recorded span of
``ONCPU_KEYS`` reads the thread's CPU clock just outside its wall stamps,
keeps both clocks exclusive by the same rules, and charges no stage for
the reads.  The operator's exporter is the recording switch here, or a
profiler session stood in for (no session on the CPU backend's suite)."""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import siddhi_tpu.core.ledger as ledger_mod  # noqa: E402
from siddhi_tpu import ColumnarStreamCallback, SiddhiManager  # noqa: E402
from siddhi_tpu.core.ledger import (ONCPU_KEYS, SPAN_NAMES,  # noqa: E402
                                    LatencyLedger, ledger)
from siddhi_tpu.core.tracing import tracer  # noqa: E402

MS = 1_000_000


@pytest.fixture(autouse=True)
def _clean():
    ledger().reset()
    tracer().disable()
    tracer().clear()
    yield
    tracer().disable()
    tracer().clear()
    ledger().reset()


@pytest.fixture
def recording():
    tracer().enable()
    yield
    tracer().disable()


class _Clocks:
    """A wall clock and a CPU clock the test advances by hand."""

    def __init__(self):
        self.wall = 0
        self.cpu = 0

    def tick(self, wall_ms, cpu_ms):
        self.wall += wall_ms * MS
        self.cpu += cpu_ms * MS


@pytest.fixture
def clocks(monkeypatch):
    c = _Clocks()
    monkeypatch.setattr(ledger_mod, "_pcns", lambda: c.wall)
    monkeypatch.setattr(ledger_mod, "_tns", lambda: c.cpu)
    return c


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation in a session."""

    def __init__(self, name, **stats):
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def session(monkeypatch):
    """A profiler session and no exporter: spans are recorded, and only
    those that need it read the CPU clock."""
    monkeypatch.setattr(ledger_mod, "_ta_session", lambda: True)
    monkeypatch.setattr(ledger_mod, "_TA", _Annotation)
    assert not tracer().enabled


def _outer(run):
    """(wall s, CPU s) of this thread around ``run()``, read apart from
    the ledger: what the span inside may at most have seen."""
    w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
    run()
    return ((time.perf_counter_ns() - w0) / 1e9,
            (time.thread_time_ns() - c0) / 1e9)


def test_the_keys_with_a_pair_are_declared():
    assert set(ONCPU_KEYS) <= set(SPAN_NAMES)
    assert set(LatencyLedger().oncpu_seconds()) == set(ONCPU_KEYS)


def test_a_sleep_reads_wall_time_and_almost_no_cpu(recording):
    led = LatencyLedger()

    def run():
        with led.span("device", "pack"):
            time.sleep(0.05)

    wall, cpu = _outer(run)
    pair = led.oncpu_seconds()["device.pack"]
    assert 0.045 <= pair["wall"] <= wall
    assert 0 <= pair["cpu"] <= cpu
    assert pair["cpu"] < 0.02
    # the always-on wall accumulator saw the same span
    assert led.stage_ns()["device.pack"] == pytest.approx(pair["wall"] * 1e9)


def test_a_busy_loop_reads_cpu_close_to_wall(recording):
    """30 ms of the thread's own CPU in a span: the span reads them, and
    reads the thread off its CPU no longer than it really was (a loaded
    machine may deschedule it: the clocks around the span say how long).
    The CPU reads lie outside the wall stamps: the CPU time may pass the
    wall time by their own cost."""
    led = LatencyLedger()

    def run():
        with led.span("dispatch", "keys"):
            c0 = time.thread_time_ns()
            while time.thread_time_ns() - c0 < 30 * MS:
                pass

    wall, cpu = _outer(run)
    pair = led.oncpu_seconds()["dispatch.keys"]
    assert 0.03 <= pair["cpu"] <= cpu
    assert pair["cpu"] <= pair["wall"] + 1e-4
    assert pair["wall"] <= wall
    assert pair["wall"] - pair["cpu"] <= wall - cpu + 1e-4
    assert pair["cpu"] / pair["wall"] >= cpu / wall - 0.01


def test_nested_stage_and_named_spans_stay_exclusive_on_both_clocks(
        recording, clocks):
    led = LatencyLedger()
    with led.span("dispatch", None, 7, "a"):
        clocks.tick(1, 1)
        with led.span("device", "sync"):
            clocks.tick(2, 1)
            # the launch: a declared name without a stage
            with led.span(None, "device.issue/some.kind"):
                clocks.tick(3, 1)
                with led.span("dispatch", "cols"):
                    clocks.tick(1, 1)
            # an annotation only: its time stays with `device.sync`
            with led.span(None, "match.scatter"):
                clocks.tick(2, 2)
            clocks.tick(1, 0)
    on = led.oncpu_seconds()
    got = {k: (round(on[k]["wall"] * 1e3, 6), round(on[k]["cpu"] * 1e3, 6))
           for k in ("dispatch.cols", "device.sync", "device.issue")}
    assert got == {"dispatch.cols": (1, 1), "device.sync": (5, 3),
                   "device.issue": (3, 1)}
    # every span was recorded: the wall pair is the always-on accumulator
    ns = led.stage_ns()
    assert all(on[k]["wall"] * 1e9 == pytest.approx(ns[k]) for k in on)
    assert on["device.pack"] == {"wall": 0.0, "cpu": 0.0}
    # no new /metrics family
    assert not any("cpu" in line for line in led.prometheus_lines())


def test_nobody_recording_never_reads_the_cpu_clock(monkeypatch):
    reads = [0]

    def counted():
        reads[0] += 1
        return time.thread_time_ns()

    monkeypatch.setattr(ledger_mod, "_tns", counted)
    assert not tracer().enabled
    led = LatencyLedger()
    with led.span("dispatch", None, 3, "a"):
        with led.span("device", "pack"):
            with led.span(None, "device.issue/k"):
                pass
        with led.span(None, "deliver", 4, "a"):
            pass
    _run_app(3)
    assert reads[0] == 0
    assert all(v == {"wall": 0.0, "cpu": 0.0}
               for v in led.oncpu_seconds().values())
    assert all(v == {"wall": 0.0, "cpu": 0.0}
               for v in ledger().oncpu_seconds().values())
    # the same spans recorded: two reads a span
    tracer().enable()
    with led.span("dispatch", None, 3, "a"):
        with led.span("device", "pack"):
            pass
    assert reads[0] == 4


def test_a_profiler_session_reads_the_cpu_clock_where_it_is_needed(
        session, clocks, monkeypatch):
    """Under a profiler session alone the keys with a pair read the CPU
    clock, and so do the stage and keyed spans inside them, through an
    annotation too; other stages and the annotations do without it."""
    reads = [0]

    def cpu():
        reads[0] += 1
        return clocks.cpu

    monkeypatch.setattr(ledger_mod, "_tns", cpu)
    led = LatencyLedger()
    with led.span("dispatch", None, 3, "a"):
        clocks.tick(1, 1)
        with led.span("device", "sync"):
            clocks.tick(1, 1)
            with led.span(None, "match.scatter"):
                clocks.tick(1, 1)
                with led.span("publish"):
                    clocks.tick(2, 1)
        with led.span(None, "ingest.chunk"):
            clocks.tick(1, 0)
    assert reads[0] == 4
    on = led.oncpu_seconds()
    assert on["device.sync"] == {"wall": 2e-3, "cpu": 2e-3}
    assert led.stage_ns()["publish"] == 2 * MS


def test_the_clock_reads_are_charged_to_no_stage(session, clocks,
                                                 monkeypatch):
    """Each CPU read here takes 5 ms of wall time: the stages and keys
    read as they would without the second clock, and the time of the
    reads is in no accumulator."""
    def slow_cpu():
        clocks.wall += 5 * MS
        return clocks.cpu

    monkeypatch.setattr(ledger_mod, "_tns", slow_cpu)
    led = LatencyLedger()
    with led.span("dispatch", None, 1, "a"):
        clocks.tick(1, 1)
        with led.span("dispatch", "keys"):
            clocks.tick(2, 2)
        with led.span("device"):
            clocks.tick(1, 1)
            with led.span("device", "sync"):
                clocks.tick(1, 1)
                with led.span(None, "device.issue/k"):
                    clocks.tick(3, 0)
    ns = {k: v / MS for k, v in led.stage_ns().items() if v}
    assert ns == {"dispatch": 3, "device": 5, "dispatch.keys": 2,
                  "device.sync": 1, "device.issue": 3}
    on = led.oncpu_seconds()
    assert {k: (on[k]["wall"] * 1e3, on[k]["cpu"] * 1e3)
            for k in ("dispatch.keys", "device.sync", "device.issue")} == \
        {"dispatch.keys": (2, 2), "device.sync": (1, 1),
         "device.issue": (3, 0)}


def test_an_exporter_event_carries_its_cpu_time(recording, clocks):
    led = LatencyLedger()
    with led.span("dispatch", None, 5, "a"):
        clocks.tick(4, 1)
        with led.span("device", "pack"):
            clocks.tick(2, 2)
    evs = {e["name"]: e for e in tracer().to_dict()["traceEvents"]}
    # inclusive, like the event's `dur`
    assert evs["device.pack"]["args"] == {"cpu_us": 2000.0, "block": 5}
    assert evs["dispatch"]["args"] == {"cpu_us": 3000.0, "block": 5}
    assert evs["dispatch"]["dur"] == 6000.0
    with led.span(None, "parse"):
        pass
    parse = [e for e in tracer().to_dict()["traceEvents"]
             if e["name"] == "parse"]
    assert parse[0]["args"] == {"cpu_us": 0.0}


_APP = """@app:name('oncpuq') @app:playback
define stream S (sym string, price float, kind int);
partition with (sym of S) begin
@info(name='q0')
from every e1=S[kind == 0 and price > 50.0]
    -> e2=S[kind == 1 and price > e1.price] within 1 sec
select e1.sym as sym, e1.price as p1, e2.price as p2 insert into Out;
end;
"""


def _run_app(blocks):
    """A keyed pattern on the device path, `blocks` sends; -> the app's
    ledger doc and rows delivered."""
    rt = SiddhiManager().create_siddhi_app_runtime(_APP)
    rows = [0]
    rt.add_callback("Out", ColumnarStreamCallback(
        lambda c: rows.__setitem__(0, rows[0] + len(c))))
    rt.start()
    h = rt.get_input_handler("S")
    rng = np.random.default_rng(2)
    n = 48
    for i in range(blocks):
        h.send_batch(
            {"sym": np.asarray([f"k{j % 5}" for j in range(n)], object),
             "price": rng.uniform(0, 100, n).astype(np.float32),
             "kind": rng.integers(0, 2, n).astype(np.int64)},
            timestamps=1_000 + 10 * n * i + 10 * np.arange(n, dtype=np.int64))
    rt.flush()
    doc = rt.statistics["ledger"]
    rt.shutdown()
    return doc, rows[0]


@pytest.fixture
def single_device(monkeypatch):
    """One device, as a served chip has: the pattern gangs and launches
    through a bucket's sync."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    yield
    from siddhi_tpu.plan.xtenant import tenant_packer
    packer = tenant_packer()
    for row in list(packer.buckets.values()):
        for bucket in list(row):
            for nfa in list(bucket.tenants):
                if getattr(nfa, "_xt_label", "").startswith("oncpuq/"):
                    packer.evict(nfa)


def test_a_recorded_run_fills_every_pair(single_device, recording):
    doc, rows = _run_app(5)
    assert rows > 0
    # the read surface: recorded wall and CPU seconds per key
    on = doc["oncpu_seconds"]
    assert set(on) == set(ONCPU_KEYS)
    ns = ledger().stage_ns()
    for k in ONCPU_KEYS:
        assert on[k]["wall"] > 0, k
        assert on[k]["wall"] * 1e9 == pytest.approx(ns[k], rel=1e-6), k
        assert 0 <= on[k]["cpu"] <= on[k]["wall"] + 1e-3, k
    issue = [e for e in tracer().to_dict()["traceEvents"]
             if e["name"].startswith("device.issue/")]
    assert issue and all(e["args"]["cpu_us"] >= 0 for e in issue)

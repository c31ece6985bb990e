"""Observability layer tests: histogram metrics, reporter lifecycle,
the per-kind launch books, span tracing, Prometheus exposition, and the
no-trackers-when-disabled contract.

(reference shapes: managment/StatisticsTestCase — here extended to the
full observability surface: core/statistics.py, plan/shapes.py's books,
core/tracing.py, service/rest.py /metrics + /stats; the books' own tests
are tests/test_launch_books.py.)"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.statistics import (BufferedEventsTracker, Counter,
                                        Gauge, Histogram, LatencyTracker,
                                        StatisticsManager, ThroughputTracker,
                                        prometheus_text)
from siddhi_tpu.core.tracing import tracer


@pytest.fixture(autouse=True)
def _clean_globals():
    """The tracer is process-global; isolate each test."""
    tracer().disable()
    tracer().clear()
    yield
    tracer().disable()
    tracer().clear()


# ---------------------------------------------------------------- histogram

def test_histogram_percentiles_match_numpy():
    """Log-bucketed percentiles within the bucket resolution (~6%) of
    numpy's exact answer on a known heavy-tailed distribution."""
    rng = np.random.default_rng(42)
    vals = rng.lognormal(mean=10.0, sigma=1.5, size=20_000).astype(np.int64)
    h = Histogram()
    for v in vals:
        h.record(int(v))
    for q in (50, 95, 99):
        est = h.percentile(q)
        ref = float(np.percentile(vals, q))
        assert abs(est - ref) / ref < 0.07, (q, est, ref)
    assert h.count == len(vals)
    assert h.max == int(vals.max())
    assert abs(h.mean() - vals.mean()) / vals.mean() < 0.01


def test_histogram_small_values_exact():
    h = Histogram()
    for v in (0, 1, 2, 5, 31):
        h.record(v)
    assert h.count == 5 and h.min == 0 and h.max == 31
    # values < 32 land in exact unit buckets
    assert h.percentile(1) == 0.0
    assert [b for b, _ in h.buckets()] == [1, 2, 3, 6, 32]


# ---------------------------------------------------------------- trackers

def test_latency_tracker_nests_and_keeps_zero_marks():
    t = LatencyTracker("t")
    t.mark_in()          # outer
    t.mark_in()          # nested (query feeding a query on one thread)
    t.mark_out()
    t.mark_out()
    assert t.count == 2
    # unmatched mark_out is a no-op, not a corruption
    t.mark_out()
    assert t.count == 2
    # a 0-ns duration is recorded (the old `if self._mark:` dropped it)
    t2 = LatencyTracker("t2")
    t2._tls.marks = [time.perf_counter_ns()]
    t2.mark_out()
    assert t2.count == 1


def test_latency_tracker_threads_do_not_corrupt_each_other():
    t = LatencyTracker("t")
    errs = []

    def worker():
        try:
            for _ in range(200):
                t.mark_in()
                t.mark_out()
        except Exception as e:  # noqa: BLE001
            errs.append(e)
    threads = [threading.Thread(target=worker) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    assert t.hist.count == t.count


def test_throughput_windowed_rate_resets_between_reads():
    t = ThroughputTracker("t")
    t.event_in(100)
    assert t.windowed_rate() > 0
    time.sleep(0.01)
    # no new events since the snapshot → windowed rate is 0, lifetime isn't
    assert t.windowed_rate() == 0.0
    assert t.rate() > 0


def test_counter_and_gauge_labels():
    c = Counter("c")
    c.inc(3, stream="S")
    c.inc(2, stream="S")
    c.inc(7, stream="T")
    assert c.value(stream="S") == 5 and c.value(stream="T") == 7
    g = Gauge("g")
    g.set(1.5, host="a")
    g.set_fn(lambda: 2.5, host="b")
    assert g.value(host="a") == 1.5 and g.value(host="b") == 2.5


def test_buffered_tracker_sums_suppliers():
    b = BufferedEventsTracker("b")
    b.register(lambda: 3)
    b.register(lambda: 4)
    assert b.buffered == 7


# ------------------------------------------------------------- reporter

def test_reporter_lifecycle_joins_thread_and_never_doubles():
    sm = StatisticsManager("app", reporter="json", interval_s=1)
    sm.start_reporting()
    t1 = sm._thread
    assert t1 is not None and t1.is_alive()
    sm.start_reporting()                 # idempotent: same thread
    assert sm._thread is t1
    sm.stop_reporting()
    assert sm._thread is None
    assert not t1.is_alive()             # joined, not abandoned
    sm.start_reporting()                 # restart after stop works
    t2 = sm._thread
    assert t2 is not None and t2.is_alive() and t2 is not t1
    sm.stop_reporting()
    assert not t2.is_alive()


def test_statistics_annotation_parsing_and_snapshot_shape():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:statistics(reporter='json', interval='1')
        define stream S (v int);
        @info(name='q') from S[v > 0] select v insert into Out;
    """)
    sm = rt.app_ctx.statistics_manager
    assert sm.reporter == "json" and sm.interval_s == 1
    assert rt.app_ctx.stats_enabled
    rt.start()
    assert sm._thread is not None and sm._thread.is_alive()
    h = rt.get_input_handler("S")
    for i in range(5):
        h.send([i + 1])
    snap = rt.statistics
    rt.shutdown()
    assert sm._thread is None            # stop_reporting joined it
    # snapshot shape: windowed rates + histogram percentiles + kernels
    assert set(snap) >= {"throughput", "latency_ms", "memory_bytes",
                         "buffered", "counters", "gauges", "kernels"}
    (tkey, tstats), = [(k, v) for k, v in snap["throughput"].items()
                       if k.endswith(".Streams.S")]
    assert tkey.startswith("io.siddhi.SiddhiApps.")
    assert tstats["count"] == 5
    assert "rate_windowed_eps" in tstats
    lat = next(iter(snap["latency_ms"].values()))
    assert set(lat) >= {"avg_ms", "count", "p50_ms", "p95_ms", "p99_ms",
                        "max_ms"}
    assert lat["count"] == 5


def test_stats_disabled_registers_zero_trackers():
    """No @app:statistics → no trackers on the hot path."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (v int);
        @info(name='q') from S[v > 0] select v insert into Out;
    """)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(3):
        h.send([i + 1])
    sm = rt.app_ctx.statistics_manager
    rt.shutdown()
    assert sm.throughput == {} and sm.latency == {} and sm.buffered == {}
    assert all(j.throughput_tracker is None
               for j in rt.junctions.values())


# ---------------------------------------------------------- launch books

def test_engine_device_path_books_kernels():
    """The device filter program shows up in ``rt.statistics`` with
    calls + a compile count."""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:statistics(reporter='console', interval='300')
        define stream S (v float);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    got = []
    rt.add_callback("Out", StreamCallback(lambda evs: got.extend(evs)))
    rt.start()
    h = rt.get_input_handler("S")
    h.send_batch({"v": np.asarray([0.5, 2.0, 3.0], np.float32)})
    rt.flush()
    snap = rt.statistics["kernels"]
    rt.shutdown()
    assert len(got) == 2
    assert "filter.program" in snap, snap
    k = snap["filter.program"]
    assert k["calls"] >= 1 and k["compiles"] >= 1


# --------------------------------------------------------------- tracing

def test_dump_trace_is_valid_chrome_trace_json(tmp_path):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:statistics(reporter='console', interval='300', tracing='true')
        define stream S (v int);
        @info(name='q') from S[v > 0] select v insert into Out;
    """)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(3):
        h.send([i + 1])
    rt.flush()
    path = str(tmp_path / "trace.json")
    rt.dump_trace(path)
    rt.shutdown()
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and evs
    names = {e["name"] for e in evs}
    assert "ingest.chunk" in names
    for e in evs:                         # perfetto-required fields
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert isinstance(e["dur"], float) and e["dur"] >= 0


def test_tracing_disabled_records_nothing():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
    """)
    rt.start()
    rt.get_input_handler("S").send([1])
    rt.shutdown()
    assert tracer().to_dict()["traceEvents"] == []


# ----------------------------------------------------------- async depth

def test_async_junction_queue_depth_wired_to_buffered_tracker():
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:statistics(reporter='console', interval='300')
        @Async(buffer.size='64')
        define stream S (v int);
        @info(name='q') from S[v > 0] select v insert into Out;
    """)
    rt.start()
    sm = rt.app_ctx.statistics_manager
    (bkey, bt), = sm.buffered.items()
    assert bkey.endswith(".Streams.S")
    h = rt.get_input_handler("S")
    for i in range(10):
        h.send([i + 1])
    assert bt.buffered >= 0               # live supplier, not the dead field
    rt.flush()
    assert bt.buffered == 0               # drained
    snap = rt.statistics
    rt.shutdown()
    assert bkey in snap["buffered"]


# ------------------------------------------------------------ exposition

def _scrape(url):
    with urllib.request.urlopen(url) as r:
        return r.headers.get("Content-Type", ""), r.read().decode()


def test_metrics_endpoint_serves_prometheus_text():
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        req = urllib.request.Request(
            f"{base}/siddhi/artifact/deploy", data=b"""
            @app:name('obsapp')
            @app:statistics(reporter='console', interval='300')
            define stream S (v float);
            @info(name='q') from S[v > 1.0] select v insert into Out;
            """, method="POST")
        urllib.request.urlopen(req).read()
        rt = svc.manager.get_siddhi_app_runtime("obsapp")
        h = rt.get_input_handler("S")
        for _ in range(4):
            h.send_batch({"v": np.asarray([0.5, 2.0, 3.0], np.float32)})
        rt.flush()
        ctype, text = _scrape(f"{base}/metrics")
        assert "text/plain" in ctype
        lines = [ln for ln in text.splitlines() if ln]
        # valid exposition: every sample line is `name{labels} value`
        for ln in lines:
            if ln.startswith("#"):
                continue
            metric, _, value = ln.rpartition(" ")
            assert metric and (value == "+Inf" or float(value) is not None)
        assert any(ln.startswith("siddhi_latency_seconds_bucket{")
                   for ln in lines)
        assert any(ln.startswith("siddhi_latency_seconds_sum{")
                   for ln in lines)
        assert any(ln.startswith("siddhi_latency_seconds_count{")
                   for ln in lines)
        assert any(ln.startswith("siddhi_throughput_events_total{")
                   for ln in lines)
        # per-kernel gauges from the device filter program
        assert any("siddhi_kernel_compile_count{" in ln for ln in lines)
        assert any("siddhi_kernel_dispatches_total{" in ln for ln in lines)
        # histogram bucket invariants: cumulative, count == +Inf bucket
        buckets = [ln for ln in lines
                   if ln.startswith("siddhi_latency_seconds_bucket{")
                   and 'name="q"' in ln]
        counts = [int(ln.rpartition(" ")[2]) for ln in buckets]
        assert counts == sorted(counts)
        count_line = next(ln for ln in lines if ln.startswith(
            "siddhi_latency_seconds_count{") and 'name="q"' in ln)
        assert counts[-1] == int(count_line.rpartition(" ")[2])

        ctype, stats = _scrape(f"{base}/stats")
        doc = json.loads(stats)
        assert "obsapp" in doc["apps"]
        assert "filter.program" in doc["kernels"]
    finally:
        svc.stop()


def test_prometheus_text_escapes_label_values():
    sm = StatisticsManager('we"ird\napp')
    sm.throughput_tracker("Streams", "S").event_in(2)
    txt = prometheus_text([sm])
    assert '\\"' in txt and "\\n" in txt


# ------------------------------------------------------------ multihost

def test_multihost_global_statistics_single_process():
    from siddhi_tpu.parallel.multihost import MultiHostAppRuntime
    rt = MultiHostAppRuntime("""
        @app:statistics(reporter='console', interval='300')
        define stream S (sym string, v float);
        partition with (sym of S) begin
        @info(name='q') from S[v > 0.0] select sym, v insert into Out;
        end;
    """)
    rt.start()
    n = rt.send_batch("S", {"sym": np.asarray(["a", "b"], object),
                            "v": np.asarray([1.0, 2.0], np.float32)},
                      np.asarray([1000, 1001], np.int64))
    rt.flush()
    stats = rt.global_statistics()
    rt.shutdown()
    assert n == 2
    skey = next(k for k in stats if ".Streams.S.count" in k)
    assert stats[skey] == 2

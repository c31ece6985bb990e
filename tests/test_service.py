"""REST service tests (reference model: siddhi-service deploy/undeploy API)."""
import json
import urllib.error
import urllib.request

from siddhi_tpu.service import SiddhiService

APP = """
@app:name('restapp')
define stream S (symbol string, price float);
@info(name='q1') from S[price > 10] select symbol, price insert into Out;
"""


def _req(method, url, body=None):
    data = body.encode() if isinstance(body, str) else (
        json.dumps(body).encode() if body is not None else None)
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_deploy_send_query_undeploy():
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        out = _req("POST", f"{base}/siddhi/artifact/deploy", APP)
        assert out == {"status": "deployed", "app": "restapp"}
        assert _req("GET", f"{base}/siddhi/apps")["apps"] == ["restapp"]
        _req("POST", f"{base}/siddhi/apps/restapp/streams/S",
             [{"data": ["IBM", 50.0]}, {"data": ["X", 5.0]}])
        health = _req("GET", f"{base}/health")
        assert health["status"] == "up" and health["ready"] is True
        assert health["apps"]["restapp"]["started"] is True
        out = _req("GET", f"{base}/siddhi/artifact/undeploy/restapp")
        assert out["status"] == "undeployed"
        assert _req("GET", f"{base}/siddhi/apps")["apps"] == []
    finally:
        svc.stop()


def test_store_query_over_http():
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", """
            @app:name('tapp')
            define stream S (symbol string, price float);
            define table T (symbol string, price float);
            from S insert into T;
        """)
        _req("POST", f"{base}/siddhi/apps/tapp/streams/S",
             [{"data": ["IBM", 42.0]}])
        out = _req("POST", f"{base}/siddhi/apps/tapp/query",
                   "from T select symbol, price")
        assert out["events"][0]["data"] == ["IBM", 42.0]
    finally:
        svc.stop()


ERR_APP = """
@app:name('errapp')
@app:errorStore(type='memory')
define stream S (v int);
@sink(type='chaos', chaos.id='resterr', retry.max.attempts='2',
      retry.base.delay.ms='1', retry.jitter='0', circuit.reset.ms='0')
define stream O (v int);
@info(name='q') from S select v insert into O;
"""


def _raw(url):
    with urllib.request.urlopen(url) as r:
        return r.status, r.read().decode()


def test_health_error_store_and_metrics_endpoints():
    """Resilience surface over HTTP: /health readiness, error-store
    list/replay/purge, and the siddhi_* resilience series on /metrics."""
    import chaos
    chaos.reset()
    chaos.SCRIPTS["resterr"] = chaos.FailureScript.fail_always()
    svc = SiddhiService(port=0).start()
    chaos.register(svc.manager)
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", ERR_APP)
        _req("POST", f"{base}/siddhi/apps/errapp/streams/S",
             [{"data": [i]} for i in range(5)])
        assert chaos.INSTANCES["resterr"].retry_join(30.0)

        out = _req("GET", f"{base}/siddhi/apps/errapp/errors")
        assert out["store"] == "InMemoryErrorStore"
        assert sum(e["events"] for e in out["errors"]) == 5
        assert all(e["origin"] == "sink" for e in out["errors"])

        health = _req("GET", f"{base}/health")
        assert health["status"] == "up"
        assert health["apps"]["errapp"]["errors_stored"] == len(
            out["errors"])

        status, text = _raw(f"{base}/metrics")
        assert status == 200
        assert "# TYPE siddhi_errors_stored_total counter" in text
        assert 'siddhi_errors_stored_total{app="errapp"' in text
        assert 'siddhi_circuit_state{app="errapp",sink="O"}' in text

        # endpoint heals → replay over HTTP drains the store
        chaos.SCRIPTS["resterr"].heal()
        out = _req("POST", f"{base}/siddhi/apps/errapp/errors/replay", {})
        assert out["replayed"] == 5
        assert chaos.INSTANCES["resterr"].retry_join(30.0)
        assert sorted(e.data[0] for e in chaos.delivered("resterr")) == \
            list(range(5))
        out = _req("GET", f"{base}/siddhi/apps/errapp/errors")
        assert out["errors"] == []

        # purge path (nothing left → purged 0)
        out = _req("POST", f"{base}/siddhi/apps/errapp/errors/purge", {})
        assert out["purged"] == 0
    finally:
        svc.stop()


def test_error_endpoints_409_without_store():
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", APP)
        try:
            _req("POST", f"{base}/siddhi/apps/restapp/errors/replay", {})
            raise AssertionError("expected HTTP 409")
        except urllib.error.HTTPError as e:
            assert e.code == 409
            assert json.loads(e.read())["error"] == \
                "no error store configured"
        out = _req("GET", f"{base}/siddhi/apps/restapp/errors")
        assert out == {"errors": [], "store": None}
    finally:
        svc.stop()


# ------------------------------------------------- exposition contract

STATS_APP = """
@app:name('expoapp')
@app:statistics(reporter='console', interval='300', telemetry='true')
define stream S (sym string, price float);
@info(name='q')
from every e1=S[price > 10.0] -> e2=S[price > e1.price]
select e1.price as p1, e2.price as p2 insert into Out;
"""


def test_metrics_exposition_is_prometheus_clean():
    """/metrics contract: the version=0.0.4 text content type, every
    emitted sample series covered by exactly one # HELP/# TYPE pair
    (PR 6-9 added series faster than the header table — kernel
    scan_ticks/live_bytes/batch_b had drifted), headers before samples."""
    import numpy as np
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", STATS_APP)
        rng = np.random.default_rng(0)
        _req("POST", f"{base}/siddhi/apps/expoapp/streams/S",
             [{"data": ["A", float(rng.uniform(5, 30))]}
              for _ in range(25)])
        svc.manager.get_siddhi_app_runtime("expoapp").flush()
        with urllib.request.urlopen(f"{base}/metrics") as r:
            ctype = r.headers.get("Content-Type", "")
            text = r.read().decode()
    finally:
        svc.stop()

    assert ctype.startswith("text/plain; version=0.0.4")

    lines = text.splitlines()
    helps, types = {}, {}
    first_sample_of = {}
    for i, ln in enumerate(lines):
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps[name] = i
        elif ln.startswith("# TYPE "):
            name = ln.split()[2]
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = i
        elif ln:
            s = ln.split("{")[0].split(" ")[0]
            first_sample_of.setdefault(s, i)
    assert set(helps) == set(types)

    def family(series):
        for suf in ("_bucket", "_sum", "_count"):
            if series.endswith(suf) and series[: -len(suf)] in helps:
                return series[: -len(suf)]
        return series

    for s, i in first_sample_of.items():
        fam = family(s)
        assert fam in helps, f"series {s} has no # HELP/# TYPE header"
        assert helps[fam] < i and types[fam] < i, \
            f"header for {s} appears after its first sample"

    # the drifted kernel series and the new telemetry series are covered
    for name in ("siddhi_kernel_scan_ticks_total",
                 "siddhi_kernel_live_bytes",
                 "siddhi_kernel_dispatches_total",
                 "siddhi_nfa_state_occupancy",
                 "siddhi_nfa_gate_pass_total"):
        assert name in helps, f"missing header for {name}"
        assert name in first_sample_of, f"no samples for {name}"


# ---------------------------------------------- rim + ledger parity

def test_rim_and_ledger_parity_across_surfaces():
    """The host-rim counters and the latency ledger must agree across
    the three read surfaces: ``rt.statistics``, ``GET /stats`` and
    ``GET /metrics``."""
    from siddhi_tpu.core.profiling import rim_stats
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        _req("POST", f"{base}/siddhi/artifact/deploy", STATS_APP)
        _req("POST", f"{base}/siddhi/apps/expoapp/streams/S",
             [{"data": ["A", 10.0 + i]} for i in range(20)])
        rt = svc.manager.get_siddhi_app_runtime("expoapp")
        rt.flush()

        snap = rt.statistics
        stats = _req("GET", f"{base}/stats")
        _, text = _raw(f"{base}/metrics")

        # rim: rt.statistics["rim"] == /stats["rim"] == the live counters
        live = rim_stats().snapshot()
        assert snap["rim"]["events_materialized"] == \
            stats["rim"]["events_materialized"] == \
            live["events_materialized"]
        assert f"siddhi_events_materialized_total " \
               f"{live['events_materialized']}" in text
        assert "siddhi_host_rim_seconds_total" not in text

        # ledger: same per-app stage histograms on both JSON surfaces
        lg_rt = snap["ledger"]["apps"]["expoapp"]["stages_ms"]
        lg_http = stats["apps"]["expoapp"]["ledger"]["apps"]["expoapp"][
            "stages_ms"]
        assert lg_rt.keys() == lg_http.keys()
        for stage in lg_rt:
            assert lg_rt[stage]["count"] == lg_http[stage]["count"], stage
        assert lg_rt["device"]["count"] >= 1
        assert "siddhi_ledger_stage_latency_ms" in text
        assert 'siddhi_ledger_stage_seconds_total{stage="device"}' in text
        assert "siddhi_event_time_lag_ms" in text
    finally:
        svc.stop()

"""Cross-tenant super-dispatch (round 14): packed-vs-unpacked equivalence.

The TenantPacker (plan/xtenant.py) buckets small automata from
DIFFERENT apps by shape class and steps every pending tenant in ONE
jitted gang dispatch per bucket per ingest wall, with all co-scheduled
match buffers riding one shared egress slab.  That must be invisible in
match semantics: randomized round-robin feeds produce bit-identical
per-app matches vs the ``SIDDHI_TPU_XTENANT=0`` kill switch, for B in
{1, 4}, with heterogeneous query kinds (pattern and sequence) sharing
one bucket, and through a forced single-tenant grow-and-replay.

Plus the structural claims: packed tenants REALLY pay fewer device
dispatches per ingest wall than the per-app path; one tenant's slot
overflow rewinds and re-keys ONLY that tenant (co-tenants keep their
gang results); shutting a packed tenant down evicts it without
disturbing co-tenants' matches; the cost model prices a packed bucket
byte-exactly against the live carries (packing changes dispatch count,
never bytes); plan dumps surface ``packed=<bucket>``; 100 create/
shutdown cycles leak no engine threads and leave the packer empty; and
the per-tenant quota + packer series render exposition-clean.
Runs on the conftest-forced virtual 8-device CPU mesh.
"""
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.ops.nfa import BATCH_ENV  # noqa: E402
from siddhi_tpu.plan.xtenant import (XTENANT_ENV,  # noqa: E402
                                     resolve_xtenant, tenant_packer)

BASE = 1_000_000


@pytest.fixture(autouse=True)
def _single_device(monkeypatch):
    # the packer's eligible population is single-device small automata
    # (meshed NFAs donate their carries and can never rewind, so they
    # never pack) — pin the operator escape hatch so the runtimes this
    # module builds come up mesh-free on the 8-device conftest CPU mesh
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    # this module counts the process-wide gang's buckets, and another
    # file of the same xdist worker may have left tenants in it (a
    # partition's device queries are not shut down with their app):
    # start every test from an empty gang, whatever ran before
    packer = tenant_packer()
    for row in list(packer.buckets.values()):
        for bucket in list(row):
            for nfa in list(bucket.tenants):
                packer.evict(nfa)


def _pattern_app(i, thr, e2="v > e1.v"):
    return (f"@app:name('mt{i}') @app:pipeline('4') "
            "define stream S (k int, v double); "
            f"@info(name='q') from every e1=S[v > {thr}] -> "
            f"e2=S[{e2}] select e1.v as a, e2.v as b insert into Out;")


def _sequence_app(i, thr):
    # a different query KIND (sequence `,` not pattern `->`) with the
    # same shape class (S=2, same captures) — heterogeneous condition
    # programs must coexist in one gang trace
    return (f"@app:name('mt{i}') @app:pipeline('4') "
            "define stream S (k int, v double); "
            f"@info(name='q') from every e1=S[v > {thr}], "
            "e2=S[v > e1.v] select e1.v as a, e2.v as b insert into Out;")


def _run_tenants(apps, seed, packed, walls=4, events=10, on_wall=None):
    """Round-robin feed `walls` walls of one block per app; returns
    (per-app sorted match tuples, per-app NFAs' final (n_slots, bucket
    label), packer snapshot).  Same seed both modes so parity is exact
    by construction.  `on_wall(wall, rts)` runs between walls (used to
    shut a tenant down mid-stream)."""
    prev = os.environ.get(XTENANT_ENV)
    os.environ[XTENANT_ENV] = "1" if packed else "0"
    try:
        m = SiddhiManager()
        matches = [[] for _ in apps]
        rts = []
        for i, app in enumerate(apps):
            rt = m.create_siddhi_app_runtime(app)
            rt.add_callback("Out", StreamCallback(
                lambda evs, _s=matches[i]: _s.extend(
                    tuple(e.data) for e in evs)))
            rt.start()
            rts.append(rt)
        rng = np.random.default_rng(seed)
        t0 = BASE
        for w in range(walls):
            for rt in rts:
                if rt is None:
                    rng.uniform(0.0, 1.0, events)   # keep streams aligned
                    continue
                h = rt.get_input_handler("S")
                h.send_batch(
                    {"k": np.arange(events, dtype=np.int64) % 4,
                     "v": rng.uniform(0.0, 1.0, events)},
                    timestamps=t0 + np.arange(events, dtype=np.int64))
            t0 += events
            if on_wall is not None:
                on_wall(w, rts)
        shapes = []
        for rt in rts:
            if rt is None:
                shapes.append(None)
                continue
            rt.flush()
            nfa = next(iter(rt.query_runtimes.values())).device_runtime.nfa
            b = getattr(nfa, "_tenant_bucket", None)
            shapes.append((nfa.spec.n_slots, b.label if b else None))
        snap = tenant_packer().snapshot()
        m.shutdown()
        return [sorted(s) for s in matches], shapes, snap
    finally:
        if prev is None:
            os.environ.pop(XTENANT_ENV, None)
        else:
            os.environ[XTENANT_ENV] = prev


# ------------------------------------------------------------------ parity

@pytest.mark.parametrize("B", [1, 4])
def test_packed_matches_unpacked(B, monkeypatch):
    """Mixed query kinds (two patterns + one sequence) share ONE bucket
    and the gang-stepped matches are bit-identical to the kill-switch
    per-app path, across randomized feeds and B in {1, 4}."""
    monkeypatch.setenv(BATCH_ENV, str(B))
    apps = [_pattern_app(0, 0.1), _sequence_app(1, 0.3),
            _pattern_app(2, 0.5)]
    total = 0
    for seed in (0, 1, 2):
        mp, sp, snap = _run_tenants(apps, seed, packed=True)
        mu, su, _ = _run_tenants(apps, seed, packed=False)
        assert mp == mu, f"B={B} seed={seed}: packed matches diverged"
        labels = {s[1] for s in sp}
        assert len(labels) == 1 and None not in labels, \
            f"tenants did not share one bucket: {sp}"
        assert len(snap["buckets"]) == 1
        assert snap["buckets"][0]["flush_total"] > 0
        assert all(s[1] is None for s in su), \
            "kill switch left tenants packed"
        total += sum(len(s) for s in mp)
    assert total > 0, "degenerate parity grid (0 matches)"


def test_packed_pays_fewer_dispatches(monkeypatch):
    """The structural point of the layer: N co-bucketed tenants fed
    round-robin pay ~O(1) gang dispatches per wall packed, O(N) with
    the SIDDHI_TPU_XTENANT=0 kill switch."""
    from siddhi_tpu.plan.shapes import shape_registry
    reg = shape_registry()
    apps = [_pattern_app(i, 0.1 * (i % 5)) for i in range(4)]

    def measured(packed):
        d0 = reg.calls
        _run_tenants(apps, 7, packed=packed, walls=3)
        return reg.calls - d0

    dp, du = measured(True), measured(False)
    assert dp < du, f"packed {dp} dispatches !< unpacked {du}"
    assert reg.kernels()["nfa.xstep"]["calls"] > 0


def test_grow_and_replay_bucket_granularity():
    """One greedy tenant overflows its K=8 slot ring (its e2 almost
    never fires, so every event parks a partial); the planner must
    rewind, grow and replay ONLY that tenant — matches stay bit-exact
    vs unpacked for greedy AND co-tenant, and the growth re-keys the
    greedy tenant into its own bucket while the co-tenant stays put."""
    apps = [_pattern_app(0, 0.0, e2="v > 0.97"),   # greedy: partials pile
            _pattern_app(1, 0.2)]                   # normal co-tenant
    mp, sp, snap = _run_tenants(apps, 3, packed=True, walls=5, events=12)
    mu, su, _ = _run_tenants(apps, 3, packed=False, walls=5, events=12)
    assert sp[0][0] > 8, \
        f"greedy tenant never overflowed K=8 (K={sp[0][0]}) — the " \
        "bucket-granularity replay path was not exercised"
    assert su[0][0] == sp[0][0], "packed grew to a different K"
    assert mp == mu, "grow-and-replay diverged from the unpacked path"
    assert sum(len(s) for s in mp) > 0
    assert sp[0][1] != sp[1][1], \
        "slot growth did not re-key the grown tenant"
    assert len(snap["buckets"]) == 2


def test_shutdown_evicts_without_disturbing_cotenants():
    """Shutting one packed tenant down mid-stream must flush its
    pending block, retire its final matches, and leave co-tenants'
    subsequent matches bit-identical to the unpacked run of the same
    scenario (their carries were never rewound or re-stepped)."""
    apps = [_pattern_app(i, 0.1 * i) for i in range(3)]

    def kill_middle(w, rts):
        if w == 2:
            rts[1].shutdown()
            rts[1] = None

    mp, sp, snap = _run_tenants(apps, 5, packed=True, walls=5,
                                on_wall=kill_middle)
    mu, _, _ = _run_tenants(apps, 5, packed=False, walls=5,
                            on_wall=kill_middle)
    assert mp == mu
    assert len(mp[0]) > 0 and len(mp[2]) > 0
    # the survivor bucket holds exactly the two remaining tenants
    assert snap["tenants_total"] == 2
    assert sorted(t for b in snap["buckets"] for t in b["tenants"]) == \
        ["mt0/q", "mt2/q"]


def test_kill_switch_and_eligibility():
    from siddhi_tpu.plan.xtenant import resolve_bucket_cap
    prev = os.environ.get(XTENANT_ENV)
    try:
        os.environ[XTENANT_ENV] = "0"
        assert resolve_xtenant() is False
        os.environ.pop(XTENANT_ENV, None)
        assert resolve_xtenant() is True
        assert resolve_xtenant(False) is False
        os.environ["SIDDHI_TPU_XTENANT_BUCKET"] = "3"
        assert resolve_bucket_cap() == 3
    finally:
        os.environ.pop("SIDDHI_TPU_XTENANT_BUCKET", None)
        if prev is None:
            os.environ.pop(XTENANT_ENV, None)
        else:
            os.environ[XTENANT_ENV] = prev


# ------------------------------------------------------------ cost model / IR

def test_cost_model_packed_bucket_byte_exact():
    """packed_bucket_state_bytes prices the bucket as the SUM of its
    tenants' live carries — packing changes dispatch count, never
    bytes — and the egress model covers every tenant's slab share."""
    from siddhi_tpu.analysis.cost_model import (nfa_egress_bytes,
                                                packed_bucket_egress_bytes,
                                                packed_bucket_state_bytes)
    from siddhi_tpu.analysis.plan_ir import automaton_ir_from_nfa
    prev = os.environ.get(XTENANT_ENV)
    os.environ[XTENANT_ENV] = "1"
    try:
        m = SiddhiManager()
        rts = [m.create_siddhi_app_runtime(a) for a in
               (_pattern_app(0, 0.1), _sequence_app(1, 0.4))]
        for rt in rts:
            rt.start()
        nfas = [next(iter(rt.query_runtimes.values())).device_runtime.nfa
                for rt in rts]
        bucket = nfas[0]._tenant_bucket
        assert bucket is not None and bucket is nfas[1]._tenant_bucket
        irs = [automaton_ir_from_nfa(n, "q") for n in nfas]
        live = sum(int(np.asarray(v).nbytes)
                   for n in nfas for v in n.carry.values())
        assert packed_bucket_state_bytes(irs) == live
        assert packed_bucket_egress_bytes(irs) == \
            sum(nfa_egress_bytes(a) for a in irs) > 0
        m.shutdown()
    finally:
        if prev is None:
            os.environ.pop(XTENANT_ENV, None)
        else:
            os.environ[XTENANT_ENV] = prev


def test_plan_ir_surfaces_packing():
    """Plan dumps and as_dict carry the bucket assignment; the kill
    switch removes it (goldens for unpacked plans are unchanged)."""
    from siddhi_tpu.analysis import extract_plan
    prev = os.environ.get(XTENANT_ENV)
    try:
        os.environ[XTENANT_ENV] = "1"
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(_pattern_app(0, 0.1))
        rt.start()
        plan = extract_plan(rt)
        a = plan.automata[0]
        assert a.packed and a.pack_bucket.startswith("S")
        assert a.as_dict()["packed"] is True
        assert a.as_dict()["pack_bucket"] == a.pack_bucket
        assert f"packed={a.pack_bucket}" in plan.dump()
        m.shutdown()

        os.environ[XTENANT_ENV] = "0"
        m2 = SiddhiManager()
        rt2 = m2.create_siddhi_app_runtime(_pattern_app(0, 0.1))
        rt2.start()
        a2 = extract_plan(rt2).automata[0]
        assert not a2.packed and a2.pack_bucket == ""
        assert "packed=" not in extract_plan(rt2).dump()
        m2.shutdown()
    finally:
        if prev is None:
            os.environ.pop(XTENANT_ENV, None)
        else:
            os.environ[XTENANT_ENV] = prev


# ------------------------------------------------------------ lifecycle

def test_hundred_apps_no_thread_or_tenant_leak():
    """100 tenant create/start/shutdown cycles: no engine threads left
    behind (the conftest sentinel would flag them too, but this pins
    the count at the source) and the packer registry drains to its
    pre-test population."""
    packer = tenant_packer()
    tenants0 = packer.snapshot()["tenants_total"]
    threads0 = {t.name for t in threading.enumerate()}
    m = SiddhiManager()
    rts = [m.create_siddhi_app_runtime(_pattern_app(i, 0.1 * (i % 7)))
           for i in range(100)]
    for rt in rts:
        rt.start()
    assert packer.snapshot()["tenants_total"] == tenants0 + 100
    # one shape class, first-fit under the default bucket cap
    from siddhi_tpu.plan.xtenant import resolve_bucket_cap
    want = -(-100 // resolve_bucket_cap())
    assert len(packer.snapshot()["buckets"]) == want
    m.shutdown()
    assert packer.snapshot()["tenants_total"] == tenants0
    assert packer.snapshot()["buckets"] == []
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith("siddhi-")
                  and t.name not in threads0]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"leaked engine threads: {leaked}"


# ------------------------------------------------------------ fair share

QUOTA_APP = ("@app:name('{name}') @app:quota(rate='{rate}', burst='{burst}') "
             "define stream S (v double); "
             "@info(name='q') from S[v > 0.5] select v insert into Out;")


def test_quota_sheds_greedy_admits_quiet():
    """Token-bucket admission at the ingest boundary: a burst beyond
    the quota is shed tail-first with reason=quota and one quota_breach
    flight emit per episode; a tenant inside its quota is untouched."""
    from siddhi_tpu.core.overload import fair_share
    m = SiddhiManager()
    greedy = m.create_siddhi_app_runtime(
        QUOTA_APP.format(name="greedy", rate=5, burst=10))
    quiet = m.create_siddhi_app_runtime(
        QUOTA_APP.format(name="quiet", rate=100, burst=200))
    seen = {"greedy": [], "quiet": []}
    for name, rt in (("greedy", greedy), ("quiet", quiet)):
        rt.add_callback("Out", StreamCallback(
            lambda evs, _s=seen[name]: _s.extend(e.data[0] for e in evs)))
        rt.start()
    vs = np.linspace(0.6, 0.9, 50)
    greedy.get_input_handler("S").send_batch({"v": vs})
    quiet.get_input_handler("S").send_batch({"v": vs[:8]})
    greedy.flush()
    quiet.flush()
    snap = fair_share().snapshot()
    assert snap["greedy"]["admitted"] == 10      # burst-capped
    assert snap["greedy"]["shed"] == 40
    assert snap["quiet"]["admitted"] == 8 and snap["quiet"]["shed"] == 0
    # shed is tail-first: exactly the first `burst` events were admitted
    assert seen["greedy"] == list(vs[:10])
    assert seen["quiet"] == list(vs[:8])
    m.shutdown()
    assert not fair_share().snapshot(), "quotas survived shutdown"


def test_tenant_metrics_exposition_clean():
    """The per-tenant quota/admission and packer series render through
    prometheus_text with exactly one HELP/TYPE header per family,
    headers before samples, every sample line `name{labels} value`."""
    from siddhi_tpu.core.overload import fair_share
    from siddhi_tpu.core.statistics import prometheus_text
    prev = os.environ.get(XTENANT_ENV)
    os.environ[XTENANT_ENV] = "1"
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            QUOTA_APP.format(name="mquota", rate=3, burst=4))
        rt.start()
        rt.get_input_handler("S").send_batch(
            {"v": np.linspace(0.6, 0.9, 20)})
        rt.flush()
        prt = m.create_siddhi_app_runtime(_pattern_app(9, 0.1))
        prt.start()
        prt.get_input_handler("S").send_batch(
            {"k": np.zeros(8, np.int64),
             "v": np.linspace(0.1, 0.9, 8)},
            timestamps=BASE + np.arange(8, dtype=np.int64))
        prt.flush()
        text = prometheus_text(
            [], tenants=[fair_share(), tenant_packer()])
    finally:
        m.shutdown()
        if prev is None:
            os.environ.pop(XTENANT_ENV, None)
        else:
            os.environ[XTENANT_ENV] = prev

    lines = text.splitlines()
    helps, types, first_sample = {}, {}, {}
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
            metric, _, value = ln.rpartition(" ")
            assert metric and (value == "+Inf" or float(value) is not None)
            first_sample.setdefault(ln.split("{")[0].split(" ")[0], i)
    assert set(helps) == set(types)
    for s, i in first_sample.items():
        assert s in helps, f"series {s} has no HELP/TYPE header"
        assert helps[s] < i and types[s] < i
    for want in ("siddhi_tenant_quota_rate", "siddhi_tenant_quota_level",
                 "siddhi_tenant_admitted_total", "siddhi_tenant_shed_total",
                 "siddhi_xtenant_tenants",
                 "siddhi_xtenant_gang_flushes_total"):
        assert want in first_sample, f"no samples for {want}"
    assert any('app="mquota"' in ln for ln in lines
               if ln.startswith("siddhi_tenant_quota_rate"))
    assert any(ln.startswith("siddhi_xtenant_tenants{bucket=")
               for ln in lines)


# ------------------------------------------------------------ REST load

@pytest.mark.slow
def test_rest_fair_share_under_concurrent_load():
    """10 tenant apps behind one REST service, hammered concurrently:
    the greedy tenants' overflow is shed by THEIR quotas, quiet tenants
    see zero shed, and /metrics stays exposition-clean with per-tenant
    series for all 10."""
    from siddhi_tpu.service import SiddhiService
    svc = SiddhiService(port=0).start()
    base = f"http://127.0.0.1:{svc.port}"

    def req(method, url, payload=None):
        data = payload.encode() if isinstance(payload, str) else payload
        r = urllib.request.Request(url, data=data, method=method)
        with urllib.request.urlopen(r) as resp:
            return resp.read().decode()

    try:
        for i in range(10):
            rate, burst = ((4, 8) if i < 5 else (10_000, 20_000))
            req("POST", f"{base}/siddhi/artifact/deploy",
                QUOTA_APP.format(name=f"ten{i}", rate=rate, burst=burst))

        body = ("[" + ",".join('{"data": [0.7]}' for _ in range(20)) + "]")

        def hammer(i, rounds):
            for _ in range(rounds):
                req("POST", f"{base}/siddhi/apps/ten{i}/streams/S", body)

        threads = [threading.Thread(
            target=hammer, args=(i, 5 if i < 5 else 2), daemon=True)
            for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)

        from siddhi_tpu.core.overload import fair_share
        snap = fair_share().snapshot()
        for i in range(5):      # greedy: 100 events vs burst 8
            assert snap[f"ten{i}"]["shed"] > 0, f"ten{i} never shed"
            assert snap[f"ten{i}"]["admitted"] >= 8
        for i in range(5, 10):  # quiet: 40 events, quota 20k
            assert snap[f"ten{i}"]["shed"] == 0, f"ten{i} was shed"
            assert snap[f"ten{i}"]["admitted"] == 40

        with urllib.request.urlopen(f"{base}/metrics") as r:
            text = r.read().decode()
        for ln in text.splitlines():
            if ln and not ln.startswith("#"):
                metric, _, value = ln.rpartition(" ")
                assert metric and (value == "+Inf"
                                   or float(value) is not None)
        for i in range(10):
            assert f'app="ten{i}"' in text
        assert "# HELP siddhi_tenant_shed_total" in text
    finally:
        svc.stop()

"""Compile-time observatory (plan/shapes.py).

Covers: shape-class signature stability, the single-choke-point rule
(``jax.jit`` appears nowhere outside the registry + a short allowlist),
compile attribution + trigger tallies, the CC001 ingest-blocking-compile
incident, /metrics exposition (one HELP/TYPE header per series, process
gauges), prewarm ladder behaviour on grow, and — via subprocesses — the
persistent compile cache surviving a process restart with bit-identical
results and identical shape-class signatures.
"""
import ast
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.flight import flight  # noqa: E402
from siddhi_tpu.plan.shapes import (LADDER_RUNGS,  # noqa: E402
                                    PREWARM_ENV, SHAPES_TYPES,
                                    _AotHandoff, nfa_shape_dims,
                                    prewarm_enabled, shape_registry,
                                    shape_signature)


@pytest.fixture(autouse=True)
def _clean(tmp_path, monkeypatch):
    """Registry and flight recorder are process-global; isolate each
    test and point incident bundles at tmp."""
    monkeypatch.setenv("SIDDHI_TPU_FLIGHT_DIR", str(tmp_path / "bundles"))
    shape_registry().reset()
    flight().reset()
    yield
    shape_registry().reset()
    flight().reset()


# ------------------------------------------------------------ signatures

def test_signature_sorted_stable_and_hashable():
    sig = shape_signature("nfa.step", {"K": 8, "B": 4, "donate": True,
                                       "caps": (16, 32)})
    assert sig == "nfa.step[B=4,K=8,caps=16x32,donate=1]"
    # order of insertion must not matter
    assert sig == shape_signature(
        "nfa.step", {"caps": [16, 32], "donate": True, "B": 4, "K": 8})
    hash(sig)


def test_signature_bools_render_as_ints():
    assert shape_signature("t", {"a": False, "b": True}) == "t[a=0,b=1]"


def test_nfa_shape_dims_contract():
    class Spec:
        units = [1, 2, 3]
        n_slots = 16
        n_rows = 2
        n_caps = 0
        telemetry = False

    d = nfa_shape_dims(Spec(), 4, 8, donate=True, ring=3)
    assert d == {"S": 3, "K": 16, "P": 4, "B": 8, "R": 2, "C": 1,
                 "telem": False, "donate": True, "ring": 3}
    assert shape_signature("nfa.bank_step", d) == (
        "nfa.bank_step[B=8,C=1,K=16,P=4,R=2,S=3,donate=1,ring=3,telem=0]")


def test_prewarm_env_kill_switch(monkeypatch):
    monkeypatch.setenv(PREWARM_ENV, "0")
    assert not prewarm_enabled()
    assert not shape_registry().prewarm_submit("t", {"n": 1}, lambda: None)


# ------------------------------------------------------- the choke point

#: The only files allowed to spell ``jax.jit`` — everything else must go
#: through shape_registry().jit()/adopt() so compiles stay attributable.
_JIT_ALLOWLIST = {
    "plan/shapes.py",         # the registry itself
    "parallel/mesh.py",       # sharded step built here, adopt()ed by the
                              # NFA compiler as nfa.mesh_step
    "parallel/multihost.py",  # cross-host stats reduction helper
    "ops/incremental_agg.py",  # standalone op-level kernels (no engine
                              # entry point routes through them)
}


def _package_nodes():
    """(path under siddhi_tpu/, AST node) of every node of every module."""
    root = os.path.join(REPO, "siddhi_tpu")
    for dirpath, _dirs, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=rel)
            for node in ast.walk(tree):
                yield rel, node


def test_jax_jit_routed_through_registry_everywhere():
    offenders = []
    for rel, node in _package_nodes():
        hit = (isinstance(node, ast.Attribute)
               and node.attr == "jit"
               and isinstance(node.value, ast.Name)
               and node.value.id == "jax")
        hit = hit or (isinstance(node, ast.ImportFrom)
                      and node.module == "jax"
                      and any(a.name == "jit" for a in node.names))
        if hit and rel not in _JIT_ALLOWLIST:
            offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, (
        "jax.jit outside the shape registry (route through "
        f"shape_registry().jit/adopt or extend the allowlist): {offenders}")


def test_no_second_wrapper_around_a_launch():
    """``RegisteredJit`` is the one wrapper a launch passes and the
    registry its one book: nothing under siddhi_tpu/ names the kernel
    profiler that used to wrap it a second time."""
    # spelled in halves, so that a grep for the names finds no file at all
    gone = {"wrap" + "_kernel", "Kernel" + "Profiler", "Profiled" + "Kernel"}
    accessor = "pro" + "filer"
    offenders = []
    for rel, node in _package_nodes():
        names = []
        if isinstance(node, ast.Name):
            names = [node.id]
        elif isinstance(node, ast.Attribute):
            names = [node.attr]
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names]
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names = [node.name]
        hit = gone & set(names)
        # the accessor called or imported, not jax's module named
        if isinstance(node, ast.Call):
            f = node.func
            hit = hit or getattr(f, "id", getattr(f, "attr", "")) == accessor
        if accessor in names and not isinstance(
                node, (ast.Name, ast.Attribute)):
            hit = True
        if hit:
            offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, offenders


# ------------------------------------------------------------ attribution

def test_registry_jit_attributes_compile_and_calls():
    import jax.numpy as jnp
    reg = shape_registry()
    rj = reg.jit("test.kernel", {"n": 7}, lambda x: x * 2 + 1)
    out = rj(jnp.arange(8))
    assert int(out[1]) == 3
    rj(jnp.arange(8))                     # second call: no new compile
    e = rj.entry
    assert e.signature == "test.kernel[n=7]"
    assert e.calls == 2
    assert e.compiles >= 1
    assert e.compile_seconds > 0          # monitoring listener credited us
    assert e.blocked_seconds > 0
    assert e.triggers == {"build": 1}
    tot = reg.totals()
    assert tot["shape_classes"] >= 1
    assert tot["compiles"] >= 1
    snap = reg.snapshot()
    assert any(d["signature"] == "test.kernel[n=7]"
               for d in snap["entries"])
    assert snap["recent_compiles"][-1]["signature"] == "test.kernel[n=7]"
    lines = reg.prometheus_lines()
    assert any(l.startswith("siddhi_compile_seconds_total")
               and 'signature="test.kernel[n=7]"' in l for l in lines)


def test_compile_seconds_split_by_phase_sum_to_the_whole():
    """trace (Python to jaxpr), lower (jaxpr to MLIR) and backend (XLA's
    compile, or the persistent cache's load) are what compile_seconds is
    made of: per entry, in the totals and on /metrics."""
    import jax.numpy as jnp
    reg = shape_registry()
    rj = reg.jit("test.phases", {"n": 3}, lambda x: jnp.tanh(x) @ x.T)
    rj(jnp.ones((8, 8)))
    rj(jnp.ones((16, 16)))                # a second shape: a re-trace
    e = rj.entry
    for f in ("trace_seconds", "lower_seconds", "backend_seconds"):
        assert getattr(e, f) > 0, f
    assert e.trace_seconds + e.lower_seconds + e.backend_seconds == \
        pytest.approx(e.compile_seconds)
    d = e.as_dict()
    assert d["trace_seconds"] + d["lower_seconds"] + d["backend_seconds"] \
        == pytest.approx(d["compile_seconds"], abs=2e-6)
    tot = reg.totals()
    assert tot["trace_seconds"] + tot["lower_seconds"] + \
        tot["backend_seconds"] == pytest.approx(tot["compile_seconds"])
    lines = [ln for ln in reg.prometheus_lines()
             if ln.startswith("siddhi_compile_phase_seconds_total")
             and 'kind="test.phases"' in ln]
    assert sorted(ln.split('phase="')[1].split('"')[0] for ln in lines) \
        == ["backend", "lower", "trace"]
    assert sum(float(ln.rsplit(" ", 1)[1]) for ln in lines) == \
        pytest.approx(e.compile_seconds, rel=1e-6)


def test_adopt_tallies_triggers_per_rebuild():
    import jax
    reg = shape_registry()
    jitted = jax.jit(lambda x: x + 1)
    reg.adopt("test.adopted", {"k": 1}, jitted, trigger="build")
    rj = reg.adopt("test.adopted", {"k": 1}, jitted, trigger="grow")
    assert rj.entry.triggers == {"build": 1, "grow": 1}
    assert rj.entry.last_trigger == "grow"


def test_blocking_compile_stall_emits_cc001():
    reg = shape_registry()
    e = reg.entry("test.stall", {"K": 64})
    # 5s blocked on a grow-triggered compile >> the 2s default threshold
    reg._note_compile(e, "grow", 1, 5.0)
    incs = [i for i in flight().incidents() if i["kind"] == "compile_stall"]
    assert len(incs) == 1
    det = flight().bundle(incs[0]["id"])["detail"]
    assert det["code"] == "CC001"
    assert det["signature"] == "test.stall[K=64]"
    assert det["trigger"] == "grow"
    assert det["blocked_ms"] == 5000.0
    # the compile row itself rides the flight ring alongside blocks
    rows = [r for r in flight().ring() if "compile" in r]
    assert rows and rows[-1]["compile"] == "test.stall[K=64]"


def test_build_trigger_never_emits_cc001():
    reg = shape_registry()
    reg._note_compile(reg.entry("test.cold", {"K": 8}), "build", 1, 30.0)
    assert not [i for i in flight().incidents()
                if i["kind"] == "compile_stall"]


# ------------------------------------------------------------ exposition

def test_metrics_single_header_per_series_and_process_gauges():
    from siddhi_tpu.core.statistics import PROCESS_TYPES, prometheus_text
    import jax.numpy as jnp
    rj = shape_registry().jit("test.metrics", {"n": 1}, lambda x: x - 1)
    rj(jnp.arange(4))
    text = prometheus_text([])
    for name, typ, _help in list(SHAPES_TYPES) + list(PROCESS_TYPES):
        assert text.count(f"# TYPE {name} ") == 1, name
        assert text.count(f"# HELP {name} ") == 1, name
        assert f"# TYPE {name} {typ}\n" in text, name
    assert 'siddhi_compile_total{kind="test.metrics"' in text
    # process series carry live values
    rss = [l for l in text.splitlines()
           if l.startswith("siddhi_process_rss_bytes ")]
    assert rss and float(rss[0].split()[1]) > 0
    up = [l for l in text.splitlines()
          if l.startswith("siddhi_process_uptime_seconds ")]
    assert up and float(up[0].split()[1]) >= 0
    assert 'siddhi_gc_collections_total{generation="0"}' in text


def test_runtime_statistics_carry_shape_snapshot(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_XTENANT", "0")
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('shapestats') "
        "define stream S (v float); "
        "@info(name='q') from S[v > 0.0] select v insert into Out;")
    rt.start()
    rt.get_input_handler("S").send([1.0])
    rt.get_input_handler("S").send([2.0])
    rt.flush()
    snap = rt.statistics["shapes"]
    assert snap["cache"]["configured"] is True
    sigs = [e["signature"] for e in snap["entries"]]
    assert any(s.startswith("filter.program[") for s in sigs)
    assert snap["totals"]["compiles"] >= 1
    rt.shutdown()


# ------------------------------------------------------- prewarm ladder

def test_grow_ladder_prewarms_next_rungs(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_XTENANT", "0")
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")   # ladder rides the
    monkeypatch.setenv(PREWARM_ENV, "1")           # per-NFA step path
    reg = shape_registry()
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('ladder') "
        "define stream S (sym string, price float); "
        "@info(name='pat') from every e1=S[price > 10] "
        "-> e2=S[price > e1.price] "
        "select e1.sym as s1, e2.price as p2 insert into Out;")
    got = []
    rt.add_callback("Out", StreamCallback(lambda evs: got.append(len(evs))))
    rt.start()
    h = rt.get_input_handler("S")
    h.send_batch({"sym": np.asarray(["A"] * 8, object),
                  "price": 11.0 + np.arange(8.0)},
                 1_000 + np.arange(8, dtype=np.int64))
    rt.flush()                      # first step call arms the ladder hook
    assert reg.prewarm_join(timeout=300)

    nfa = rt.query_runtimes["pat"].device_runtime.nfa
    k0 = nfa.spec.n_slots
    base_sig = shape_signature(
        "nfa.step", nfa_shape_dims(nfa.spec, nfa.n_partitions, nfa.batch_b,
                                   donate=nfa._effective_donate()))
    by_sig = {e["signature"]: e for e in reg.snapshot()["entries"]}
    assert by_sig[base_sig]["triggers"].get("build") == 1
    # every ladder rung is a DIFFERENT shape class, compiled ahead of need
    for mlt in LADDER_RUNGS:
        spec = nfa.spec
        rung_sig = shape_signature("nfa.step", dict(
            nfa_shape_dims(spec, nfa.n_partitions, nfa.batch_b,
                           donate=nfa._effective_donate()), K=k0 * mlt))
        assert rung_sig != base_sig
        assert by_sig[rung_sig]["compiles"] >= 1, rung_sig
        assert by_sig[rung_sig]["last_trigger"] == "prewarm"
    snap = reg.snapshot()["prewarm"]
    assert snap["compiled"] >= len(LADDER_RUNGS)
    assert snap["errors"] == 0

    # the grown-K rebuild lands on the exact shape class the ladder
    # already compiled, tallied under its own "grow" trigger
    nfa.grow_slots(k0 * LADDER_RUNGS[0])
    grown_sig = shape_signature(
        "nfa.step", nfa_shape_dims(nfa.spec, nfa.n_partitions, nfa.batch_b,
                                   donate=nfa._effective_donate()))
    assert grown_sig != base_sig
    e = {e["signature"]: e for e in reg.snapshot()["entries"]}[grown_sig]
    assert e["triggers"].get("prewarm") == 1
    assert e["triggers"].get("grow") == 1
    # ...and takes over the ladder's AOT executable outright (the
    # owner-gated handoff): no re-trace, no re-compile at grow time
    assert e["triggers"].get("prewarm-handoff") == 1
    assert e["prewarmed"] is True
    assert reg.snapshot()["prewarm"]["handoffs"] >= 1

    # the handed-over executable really runs: same block shape as the
    # ladder's abstract snapshot, so the AOT path serves the call and
    # the shape class never compiles again
    before = len(got)
    h.send_batch({"sym": np.asarray(["A"] * 8, object),
                  "price": 111.0 + np.arange(8.0)},
                 9_000 + np.arange(8, dtype=np.int64))
    rt.flush()
    assert len(got) > before
    e = {e["signature"]: e for e in reg.snapshot()["entries"]}[grown_sig]
    assert e["compiles"] == 1       # the prewarm compile — nothing since
    assert e["calls"] >= 1
    rt.shutdown()
    reg.prewarm_join(timeout=60)    # grow re-arms the ladder; drain it


def test_prewarm_handoff_is_owner_gated():
    """A shape-class signature pins array shapes, not the constants an
    owner baked into its HLO — a rebuild may only take over a prewarmed
    executable queued by the SAME owner token."""
    import jax
    import jax.numpy as jnp
    os.environ[PREWARM_ENV] = "1"
    try:
        reg = shape_registry()
        dims = {"n": 8}
        build = lambda: (lambda x: x * 3, # noqa: E731
                         (jax.ShapeDtypeStruct((8,), jnp.float32),), {})
        assert reg.prewarm_submit("hand.off", dims, build, owner="me")
        assert reg.prewarm_join(timeout=60)
        x = jnp.arange(8, dtype=jnp.float32)

        stranger = reg.jit("hand.off", dims, lambda x: x * 3,
                           prewarm_owner="not-me")
        assert not isinstance(stranger._jitted, _AotHandoff)
        mine = reg.jit("hand.off", dims, lambda x: x * 3,
                       prewarm_owner="me")
        assert isinstance(mine._jitted, _AotHandoff)
        np.testing.assert_array_equal(np.asarray(mine(x)),
                                      np.asarray(x) * 3)
        # aval mismatch falls back to the plain jit (which retraces)
        y = jnp.arange(16, dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(mine(y)),
                                      np.asarray(y) * 3)
        assert reg.snapshot()["prewarm"]["handoffs"] == 1
    finally:
        os.environ.pop(PREWARM_ENV, None)


# ------------------------------------------- cache across process restart

def _run_cachestab_worker(cache_dir):
    """cache_dir: where the child's JAX is told to cache (the one way to
    place it); None turns the cache off with JAX's own switch."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", SIDDHI_TPU_XTENANT="0",
               SIDDHI_TPU_PREWARM="0",
               JAX_ENABLE_COMPILATION_CACHE="0" if cache_dir is None
               else "1")
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "coldstart_worker.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_survives_process_restart(tmp_path):
    cache = str(tmp_path / "ccache")
    cold = _run_cachestab_worker(cache)
    assert cold["cache_misses"] > 0
    assert os.listdir(cache), "persistent cache wrote no artifacts"
    warm = _run_cachestab_worker(cache)
    # the restarted process derives the SAME shape-class signatures ...
    assert cold["signatures"] == warm["signatures"]
    assert any(s.startswith("filter.program[") for s in warm["signatures"])
    # ... hits the cache instead of recompiling ...
    assert warm["cache_hits"] > 0
    assert warm["cache_misses"] == 0
    # ... and produces bit-identical matches (cache introduces zero drift)
    assert cold["digest"] == warm["digest"]
    assert cold["matches"] == warm["matches"] > 0
    # parity against a cache-disabled process: same events, same matches
    off = _run_cachestab_worker(None)
    assert off["digest"] == cold["digest"]
    assert off["cache"]["enabled"] is False
    assert cold["cache"] == {"configured": True, "enabled": True,
                             "dir": cache}

"""Test configuration: force a virtual 8-device CPU mesh so sharding tests run
without TPU hardware.  The hard assert guards the whole suite: a run that
came up on anything but 8 CPU devices would not be testing what it says."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The engine's persistent compile cache is always on (plan/shapes.py).  The
# suite runs without it — JAX's own switch, inherited by the subprocesses
# tests spawn — so a run never depends on what an earlier one left in
# <repo>/.jax_cache, and a cold cache's writes (measured ~3% on three
# compile-heavy files) stay out of a suite that already overruns its
# window.  Tests of the cache itself turn it back on in their child's env.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

assert len(jax.devices()) == 8 and jax.devices()[0].platform == "cpu", \
    (f"test suite needs the virtual 8-device CPU mesh, got "
     f"{len(jax.devices())}x {jax.devices()[0].platform}")

_here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# Device-hit telemetry (VERDICT r2 next #6): ref_harness.run_query records
# whether each conformance test actually exercised the device engine.  At
# session end the per-suite counts are written to docs/device_hits.json;
# when the session collected every suite listed in tests/device_hit_floor
# .json (i.e. a full run), a drop below the floor FAILS the run, and the
# generated table in docs/conformance_map.md is refreshed.

_COLLECTED_FILES = set()


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; long chaos soaks opt out with it
    config.addinivalue_line(
        "markers", "slow: long-running chaos soak (excluded from tier-1)")


def pytest_collection_modifyitems(session, config, items):
    for it in items:
        _COLLECTED_FILES.add(it.nodeid.split("::")[0].split("/")[-1])


def _device_hit_counts():
    import sys
    rh = None
    for name, mod in list(sys.modules.items()):
        if name.endswith("ref_harness") and getattr(mod, "TELEMETRY", None):
            rh = mod
            break
    if rh is None:
        return None
    per = {}
    for nodeid, dev in rh.TELEMETRY:
        suite = nodeid.split("::")[0].split("/")[-1]
        test = nodeid.split(" ")[0]
        tot, hits = per.setdefault(suite, (set(), set()))
        tot.add(test)
        if dev:
            hits.add(test)
    return {s: {"tests": len(t), "device_hits": len(h)}
            for s, (t, h) in sorted(per.items())}


def pytest_sessionfinish(session, exitstatus):
    import json
    if exitstatus != 0:
        # aborted/failing runs have partial telemetry — don't clobber the
        # generated docs or mask the real failure with a floor error
        return
    counts = _device_hit_counts()
    if not counts:
        return
    floor_path = os.path.join(_here, "tests", "device_hit_floor.json")
    floor = {}
    if os.path.exists(floor_path):
        with open(floor_path) as f:
            floor = json.load(f)
    # partial run (e.g. -k filters): don't clobber full-run telemetry.
    # Guard on EXECUTED suites (a -k run still collects every file before
    # deselection, review r5) and require each to have executed at least
    # its floor's worth of tests
    if floor and not (set(floor) <= _COLLECTED_FILES and
                      all(counts.get(s, {}).get("tests", 0) >= need
                          for s, need in floor.items())):
        return
    out = os.path.join(_here, "docs", "device_hits.json")
    with open(out, "w") as f:
        json.dump(counts, f, indent=1, sort_keys=True)
    if not floor:
        return
    _refresh_conformance_map(counts)
    bad = {s: (counts.get(s, {}).get("device_hits", 0), need)
           for s, need in floor.items()
           if counts.get(s, {}).get("device_hits", 0) < need}
    if bad:
        import pytest
        pytest.exit(
            "device-hit regression: " + ", ".join(
                f"{s} hit {got}<{need}" for s, (got, need) in bad.items()),
            returncode=1)


def _refresh_conformance_map(counts):
    path = os.path.join(_here, "docs", "conformance_map.md")
    if not os.path.exists(path):
        return
    begin, end = "<!-- device-hit:begin -->", "<!-- device-hit:end -->"
    rows = "\n".join(
        f"| `{s}` | {c['tests']} | {c['device_hits']} |"
        for s, c in counts.items())
    total_t = sum(c["tests"] for c in counts.values())
    total_h = sum(c["device_hits"] for c in counts.values())
    block = (f"{begin}\n## Device-hit telemetry (generated by the test "
             f"run)\n\nPer conformance suite: how many `run_query` tests "
             f"re-executed on the DEVICE engine (planner-compiled) and "
             f"asserted backend-identical output — the floor is enforced "
             f"by `tests/device_hit_floor.json` on full runs.\n\n"
             f"| suite | harness tests | device-validated |\n|---|---|---|\n"
             f"{rows}\n| **total** | **{total_t}** | **{total_h}** |\n{end}")
    with open(path) as f:
        text = f.read()
    if begin in text:
        import re
        text = re.sub(re.escape(begin) + ".*?" + re.escape(end), block,
                      text, flags=re.S)
    else:
        text = text.rstrip() + "\n\n" + block + "\n"
    with open(path, "w") as f:
        f.write(text)


# --------------------------------------------------------------------------
# Engine-thread leak sentinel (PR 13): every engine thread carries a
# siddhi- prefixed name from core/threads.py, so after each test file we
# can assert the file joined what it started.  Non-daemon leftovers are a
# hard failure (they block interpreter exit); daemon leftovers get a
# short grace join, then fail too — a daemon junction worker still alive
# after its module means some shutdown() path was skipped.

import pytest


@pytest.fixture(autouse=True, scope="module")
def _engine_thread_leak_sentinel(request):
    yield
    import threading
    import time as _time
    from siddhi_tpu.core.threads import attribute

    deadline = _time.monotonic() + 2.0
    leftovers = [t for t in threading.enumerate()
                 if t.name.startswith("siddhi-") and t.is_alive()]
    while leftovers and _time.monotonic() < deadline:
        for t in leftovers:
            t.join(timeout=0.1)
        leftovers = [t for t in threading.enumerate()
                     if t.name.startswith("siddhi-") and t.is_alive()]
    assert not leftovers, (
        f"{request.module.__name__} leaked engine threads: "
        + "; ".join(f"{t.name} (daemon={t.daemon}) — {attribute(t.name)}"
                    for t in leftovers))


# Lock-witness arming (PR 13): the chaos/resilience/overload files run
# with the runtime lock-witness armed against the static lock graph, so
# every tier-1 run doubles as a lock-order race regression gate.  The
# teardown asserts the GLOBAL witness saw no inversions; seeded
# inversion scenarios (tests/chaos.py LockOrderInversion) use private
# LockWitness instances precisely so this gate stays meaningful.

_WITNESSED_FILES = {"test_resilience", "test_overload", "test_flight"}
_STATIC_EDGES_CACHE = []


@pytest.fixture(autouse=True, scope="module")
def _lock_witness_gate(request):
    if request.module.__name__ not in _WITNESSED_FILES:
        yield
        return
    from siddhi_tpu.core import lockwitness
    if not _STATIC_EDGES_CACHE:
        from siddhi_tpu.analysis.engine import static_lock_edges
        _STATIC_EDGES_CACHE.append(static_lock_edges())
    w = lockwitness.arm(static_edges=_STATIC_EDGES_CACHE[0])
    w.reset()
    try:
        yield
        inv = w.inversions()
        assert not inv, (
            f"{request.module.__name__}: lock-witness observed lock-order "
            f"inversions (LW001): {inv}")
    finally:
        lockwitness.disarm()
        w.reset()

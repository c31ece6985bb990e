"""Device window path (plan/dwin_compiler + ops/dwin): randomized
multi-chunk parity against the host window processors, ring growth, and
snapshot round-trips.  The per-kind emission algebra itself is pinned by
tests/test_ref_windows.py; this suite stresses chunking boundaries and
state mechanics the conformance vectors cannot reach."""
import zlib

import numpy as np
import pytest

from siddhi_tpu import (InMemoryPersistenceStore, QueryCallback,
                        SiddhiManager)

CSE = "define stream cse (symbol string, price float, volume long);\n"

KIND_QUERIES = {
    "length": "#window.length(5)",
    "lengthBatch": "#window.lengthBatch(4)",
    "time": "#window.time(1 sec)",
    "timeBatch": "#window.timeBatch(1 sec)",
    "externalTime": "#window.externalTime(volume, 500)",
    "externalTimeBatch": "#window.externalTimeBatch(volume, 500)",
    "timeLength": "#window.timeLength(1 sec, 4)",
    "delay": "#window.delay(300)",
    "batch": "#window.batch()",
    # round 5: device sort (multi-key incl. LONG hi/lo lex + desc) and
    # per-key gap sessions
    "sort": "#window.sort(3, price)",
    "sort_desc_multi": "#window.sort(4, volume, 'desc', price)",
    "session": "#window.session(700)",
    "session_keyed": "#window.session(700, symbol)",
}


def _run(app, chunks, engine=None):
    m = SiddhiManager()
    m.set_persistence_store(InMemoryPersistenceStore())
    pre = "@app:playback " + (f"@app:engine('{engine}') " if engine else "")
    rt = m.create_siddhi_app_runtime(pre + app)
    log = []
    rt.add_callback("q", QueryCallback(
        lambda ts, cur, exp: log.append(
            (ts, [(e.timestamp, tuple(e.data)) for e in (cur or [])],
             [(e.timestamp, tuple(e.data)) for e in (exp or [])]))))
    rt.start()
    h = rt.get_input_handler("cse")
    for cols, ts in chunks:
        h.send_batch(cols, timestamps=ts)
    backend = rt.query_runtimes["q"].backend
    rt.shutdown()
    return backend, log


def _random_chunks(seed, n_events=60):
    rng = np.random.default_rng(seed)
    ts, t = [], 1_000_000
    for _ in range(n_events):
        t += int(rng.integers(1, 400))
        ts.append(t)
    ts = np.asarray(ts, np.int64)
    syms = rng.choice(np.asarray(["A", "B", "C"], object), n_events)
    price = rng.uniform(0, 10, n_events).astype(np.float32)
    vol = ts - 999_000          # monotone (externalTime attr)
    chunks, i = [], 0
    while i < n_events:
        k = int(rng.integers(1, 7))
        sl = slice(i, min(i + k, n_events))
        chunks.append(({"symbol": syms[sl], "price": price[sl],
                        "volume": vol[sl]}, ts[sl]))
        i += k
    return chunks


@pytest.mark.parametrize("kind", sorted(KIND_QUERIES))
def test_randomized_chunked_parity(kind):
    app = CSE + f"@info(name='q') from cse{KIND_QUERIES[kind]} " \
        "select symbol, price, volume insert all events into out;"
    chunks = _random_chunks(seed=zlib.crc32(kind.encode()))
    bd, dev = _run(app, chunks)
    bh, host = _run(app, chunks, engine="host")
    assert bd == "device" and bh == "host"
    assert dev == host


def test_session_timer_dispatch_bounded():
    """Regression: the session gap timer must not re-arm at an instant
    <= the one it just processed.  A min-live re-arm at exactly
    min+gap — where the kernel evicts nothing — made playback
    advance_to() fire the same virtual ms forever (300k+ device
    dispatches on this 60-event stream before the fix).  Bound the
    MEASURED dispatch count, not wall time."""
    from siddhi_tpu.plan.shapes import shape_registry
    app = CSE + f"@info(name='q') from cse{KIND_QUERIES['session']} " \
        "select symbol, price, volume insert all events into out;"
    chunks = _random_chunks(seed=zlib.crc32(b"session"))
    reg = shape_registry()
    d0 = reg.calls
    bd, _ = _run(app, chunks)
    n_steps = reg.calls - d0
    assert bd == "device"
    # 18 chunks + one timer per chunk-end+gap instant, plus compile-time
    # warmup steps: orders of magnitude below the runaway regime
    assert 0 < n_steps < 500, n_steps


def test_ring_growth_preserves_contents():
    """Start capacity is 16; a 200-deep length window must grow the ring
    slabs without losing or reordering entries."""
    app = CSE + "@info(name='q') from cse#window.length(200) " \
        "select symbol, price, volume insert all events into out;"
    chunks = _random_chunks(seed=7, n_events=300)
    bd, dev = _run(app, chunks)
    _, host = _run(app, chunks, engine="host")
    assert bd == "device" and dev == host


def test_snapshot_roundtrip_device_ring():
    app = CSE + "@info(name='q') from cse#window.lengthBatch(4) " \
        "select symbol, sum(price) as t insert all events into out;"
    chunks = _random_chunks(seed=11, n_events=30)
    mid = len(chunks) // 2

    m = SiddhiManager()
    m.set_persistence_store(InMemoryPersistenceStore())
    rt = m.create_siddhi_app_runtime("@app:playback " + app)
    log = []
    rt.add_callback("q", QueryCallback(
        lambda ts, cur, exp: log.append(
            (ts, [(e.timestamp, tuple(e.data)) for e in (cur or [])],
             [(e.timestamp, tuple(e.data)) for e in (exp or [])]))))
    rt.start()
    h = rt.get_input_handler("cse")
    for cols, ts in chunks[:mid]:
        h.send_batch(cols, timestamps=ts)
    rev = rt.persist()
    rt.shutdown()

    rt2 = m.create_siddhi_app_runtime("@app:playback " + app)
    log2 = []
    rt2.add_callback("q", QueryCallback(
        lambda ts, cur, exp: log2.append(
            (ts, [(e.timestamp, tuple(e.data)) for e in (cur or [])],
             [(e.timestamp, tuple(e.data)) for e in (exp or [])]))))
    rt2.start()
    rt2.restore_revision(rev)
    h2 = rt2.get_input_handler("cse")
    for cols, ts in chunks[mid:]:
        h2.send_batch(cols, timestamps=ts)
    rt2.shutdown()

    # a fresh run over the whole stream defines the expected tail
    _, full = _run(app, chunks)
    assert log2 == full[len(log):]

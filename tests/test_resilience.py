"""Resilience contract tests (robustness PR), driven by the deterministic
chaos harness in tests/chaos.py:

  * flaky sink: N failures then recovery → 100% delivery, zero drops, and
    the junction/ingest thread never blocks on the backoff (p99 bound);
  * permanently dead sink: every event lands in the error store, and
    ``replay_errors`` drains it once the endpoint heals;
  * @OnError(action='STORE'/'WAIT') on stream junctions;
  * periodic checkpoints (@app:persist) under playback virtual time;
  * crash recovery: SIGKILL a child engine mid-stream, restart with
    ``recover=True``, replay from the last acked offset — every match at
    least once, duplicates bounded by one checkpoint interval;
  * torn snapshot writes → typed CannotRestoreStateError, atomic
    FileSystemPersistenceStore saves, numeric revision ordering;
  * snapshot ↔ NFA micro-batching compatibility (persist at B=4, restore
    at B=1, and vice versa).

Every injected failure is scripted or seeded; no assertion depends on a
wall-clock sleep (rendezvous go through ``SinkRetryWorker.join`` /
subprocess ack files / playback virtual time).
"""
import os
import signal
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chaos  # noqa: E402  (tests/ is on sys.path via conftest)
from siddhi_tpu import (FileSystemPersistenceStore,  # noqa: E402
                        InMemoryPersistenceStore, SiddhiManager,
                        StreamCallback)
from siddhi_tpu.core.resilience import (CircuitBreaker,  # noqa: E402
                                        InMemoryErrorStore, RetryPolicy,
                                        make_entry)
from siddhi_tpu.core.statistics import (LatencyTracker,  # noqa: E402
                                        prometheus_text)
from siddhi_tpu.utils.errors import CannotRestoreStateError  # noqa: E402


def _mk(app, store=None, error_store=None):
    m = SiddhiManager()
    chaos.register(m)
    if store is not None:
        m.set_persistence_store(store)
    if error_store is not None:
        m.set_error_store(error_store)
    return m, m.create_siddhi_app_runtime(app)


# ================================================================ unit layer

def test_retry_policy_deterministic_ladder():
    p = RetryPolicy(max_attempts=6, base_delay_s=0.05, multiplier=2.0,
                    max_delay_s=0.5, jitter=0.2, budget_s=None, seed=7)
    ladder = p.delays()
    assert ladder == p.delays()                     # same seed → same jitter
    assert len(ladder) == 5
    # exponential shape survives the ±10% jitter; the cap bites at 0.5 s
    assert 0.04 <= ladder[0] <= 0.06
    assert ladder[1] > ladder[0] and ladder[2] > ladder[1]
    assert all(d <= 0.5 * 1.1 for d in ladder)
    assert RetryPolicy(seed=8).delays() != RetryPolicy(seed=7).delays()


def test_retry_policy_budget_caps_ladder():
    p = RetryPolicy(max_attempts=50, base_delay_s=1.0, multiplier=1.0,
                    jitter=0.0, budget_s=3.0)
    assert p.delays() == [1.0, 1.0, 1.0]


def test_retry_policy_from_options_ms_knobs():
    p = RetryPolicy.from_options({
        "retry.max.attempts": "3", "retry.base.delay.ms": "10",
        "retry.multiplier": "3.0", "retry.max.delay.ms": "90",
        "retry.jitter": "0", "retry.budget.ms": "1000", "retry.seed": "4"})
    assert p.max_attempts == 3 and p.jitter == 0 and p.seed == 4
    assert p.delays() == [0.01, 0.03]


def test_circuit_breaker_state_machine():
    vc = chaos.VirtualClock()
    transitions = []
    b = CircuitBreaker(failure_threshold=2, reset_timeout_s=5.0, clock=vc,
                       on_transition=lambda old, new:
                       transitions.append((old, new)))
    assert b.state == "closed" and b.allow()
    b.record_failure()
    assert b.state == "closed"                      # below threshold
    b.record_failure()
    assert b.state == "open" and not b.allow() and b.state_code == 1
    vc.advance(4.9)
    assert not b.allow()
    vc.advance(0.2)
    assert b.allow() and b.state == "half_open"     # probe window
    b.record_failure()                              # probe fails → re-open
    assert b.state == "open"
    vc.advance(5.0)
    assert b.allow()
    b.record_success()
    assert b.state == "closed" and b.state_code == 0
    assert ("closed", "open") in transitions
    assert ("half_open", "open") in transitions
    assert ("half_open", "closed") in transitions


def test_error_store_roundtrip_and_purge():
    store = InMemoryErrorStore(capacity=100)

    class _E:
        def __init__(self, ts, data):
            self.timestamp, self.data = ts, data

    e1 = make_entry("app", "S", "sink", RuntimeError("boom"),
                    [_E(1000, [1, "a"]), _E(1001, [2, "b"])])
    e2 = make_entry("app", "T", "stream", ValueError("bad"), [_E(2000, [3])])
    store.store(e1)
    store.store(e2)
    assert [e.id for e in store.list("app")] == [1, 2]
    assert store.list("app", stream_id="S")[0].events == \
        [(1000, (1, "a")), (1001, (2, "b"))]
    assert store.list("other") == []
    assert store.purge("app", ids=[1]) == 1
    assert [e.stream_id for e in store.list("app")] == ["T"]
    assert e2.summary()["origin"] == "stream"
    assert "ValueError" in e2.error


def test_sqlite_error_store_roundtrip():
    from siddhi_tpu.stores.sqlite import SqliteErrorStore

    class _E:
        def __init__(self, ts, data):
            self.timestamp, self.data = ts, data

    s = SqliteErrorStore(":memory:")
    try:
        eid = s.store(make_entry("app", "S", "sink", RuntimeError("x"),
                                 [_E(5, [1.5, "z"])], attempts=3))
        assert eid == 1
        got = s.list(app_name="app")
        assert len(got) == 1 and got[0].events == [(5, (1.5, "z"))]
        assert got[0].attempts == 3 and got[0].origin == "sink"
        assert s.count("app") == 1 and s.count("nope") == 0
        assert s.purge(app_name="app", ids=[eid]) == 1
        assert s.list(app_name="app") == []
    finally:
        s.close()


def test_app_errorstore_annotation_selects_backend():
    _, rt = _mk("@app:errorStore(type='memory', capacity='7')\n"
                "define stream s (v int);\n"
                "from s select v insert into Out;")
    assert isinstance(rt.error_store, InMemoryErrorStore)
    assert rt.error_store.capacity == 7
    rt.shutdown()
    from siddhi_tpu.stores.sqlite import SqliteErrorStore
    _, rt2 = _mk("@app:errorStore(type='sqlite')\n"
                 "define stream s (v int);\n"
                 "from s select v insert into Out;")
    assert isinstance(rt2.error_store, SqliteErrorStore)
    rt2.shutdown()


# ============================================================== flaky sinks

FLAKY_APP = """
define stream s (v int);
@sink(type='chaos', chaos.id='flaky', retry.base.delay.ms='60',
      retry.jitter='0', retry.max.attempts='20',
      circuit.failure.threshold='1000')
define stream outs (v int);
@info(name='q') from s select v insert into outs;
"""


def test_flaky_sink_zero_loss_and_nonblocking_ingest():
    """A sink failing its first 10 publishes recovers: every event is
    delivered (off-thread retries), nothing is dropped, and the sender
    never waits out a backoff (p99 well under the 60 ms retry delay)."""
    chaos.reset()
    chaos.SCRIPTS["flaky"] = chaos.FailureScript.fail_n(10)
    _, rt = _mk(FLAKY_APP)
    rt.start()
    h = rt.get_input_handler("s")
    lat = LatencyTracker("ingest")
    for i in range(100):
        lat.mark_in()
        h.send([i])
        lat.mark_out()
    sink = chaos.INSTANCES["flaky"]
    assert sink.retry_join(30.0), "retry queue did not drain"
    got = sorted(e.data[0] for e in chaos.delivered("flaky"))
    assert got == list(range(100)), "flaky sink lost or duplicated events"

    m = rt.resilience_metrics
    assert m.sink_retry_total.value(sink="outs") >= 1
    assert m.sink_publish_failed_total.value(sink="outs") >= 1
    assert sum(m.sink_dropped_total.series().values()) == 0
    assert sum(m.errors_stored_total.series().values()) == 0
    # the backoff ran on the retry worker, not the ingest path
    p99 = lat.percentiles_ms()["p99_ms"]
    assert p99 < 50.0, f"ingest p99 {p99:.1f} ms — retries blocked the sender"

    text = prometheus_text([], [m])
    assert '# TYPE siddhi_sink_retry_total counter' in text
    assert 'siddhi_sink_retry_total{app="' + rt.name + '",sink="outs"}' \
        in text
    assert 'siddhi_circuit_state{app="' + rt.name + '",sink="outs"} 0' \
        in text
    rt.shutdown()


DEAD_APP = """
@app:errorStore(type='memory')
define stream s (v int);
@sink(type='chaos', chaos.id='dead', retry.max.attempts='2',
      retry.base.delay.ms='1', retry.jitter='0',
      circuit.failure.threshold='3', circuit.reset.ms='0')
define stream outd (v int);
@info(name='q') from s select v insert into outd;
"""


def test_dead_sink_routes_to_error_store_and_replay_drains():
    chaos.reset()
    chaos.SCRIPTS["dead"] = chaos.FailureScript.fail_always()
    _, rt = _mk(DEAD_APP)
    rt.start()
    h = rt.get_input_handler("s")
    for i in range(30):
        h.send([i])
    assert chaos.INSTANCES["dead"].retry_join(30.0)
    entries = rt.error_store.list(app_name=rt.name)
    assert sum(len(e.events) for e in entries) == 30, \
        "a permanently dead sink must surrender every event to the store"
    assert all(e.origin == "sink" and e.stream_id == "outd"
               for e in entries)
    assert chaos.delivered("dead") == []
    m = rt.resilience_metrics
    assert m.errors_stored_total.value(stream="outd", origin="sink") == 30

    # endpoint heals → replay re-publishes through the original sink
    chaos.SCRIPTS["dead"].heal()
    replayed = rt.replay_errors()
    assert chaos.INSTANCES["dead"].retry_join(30.0)
    assert replayed == 30
    assert rt.error_store.count(rt.name) == 0, "replay must purge successes"
    got = sorted(e.data[0] for e in chaos.delivered("dead"))
    assert got == list(range(30))
    assert m.errors_replayed_total.value(stream="outd") == 30
    rt.shutdown()


def test_retry_queue_overflow_spills_to_error_store():
    """retry.queue.size bounds the in-flight retry backlog; overflow goes
    to the error store instead of growing without bound."""
    chaos.reset()
    chaos.SCRIPTS["tiny"] = chaos.FailureScript.fail_always()
    _, rt = _mk("""
        @app:errorStore(type='memory')
        define stream s (v int);
        @sink(type='chaos', chaos.id='tiny', retry.max.attempts='1000',
              retry.base.delay.ms='200', retry.jitter='0',
              retry.queue.size='2', circuit.failure.threshold='100000')
        define stream outt (v int);
        @info(name='q') from s select v insert into outt;
    """)
    rt.start()
    h = rt.get_input_handler("s")
    for i in range(20):
        h.send([i])
    # ≥ 17 events overflowed the 2-slot queue straight into the store
    # (the worker may have dequeued at most one task into flight)
    stored = sum(len(e.events)
                 for e in rt.error_store.list(app_name=rt.name))
    assert stored >= 17
    rt.shutdown()
    # shutdown drains the worker: every event is accounted for, none lost
    stored = sum(len(e.events)
                 for e in rt.error_store.list(app_name=rt.name))
    assert stored + len(chaos.delivered("tiny")) == 20


@pytest.mark.slow
def test_chaos_soak_seeded_partial_failures_no_loss():
    """Seeded 20%-failure soak: across 2000 events every single one ends
    up delivered or stored — never silently dropped."""
    chaos.reset()
    chaos.SCRIPTS["soak"] = chaos.FailureScript(fail_rate=0.2, seed=42)
    _, rt = _mk("""
        @app:errorStore(type='memory')
        define stream s (v int);
        @sink(type='chaos', chaos.id='soak', retry.max.attempts='4',
              retry.base.delay.ms='1', retry.jitter='0',
              circuit.failure.threshold='100000')
        define stream outk (v int);
        @info(name='q') from s select v insert into outk;
    """)
    rt.start()
    h = rt.get_input_handler("s")
    for i in range(2000):
        h.send([i])
    assert chaos.INSTANCES["soak"].retry_join(60.0)
    delivered = [e.data[0] for e in chaos.delivered("soak")]
    stored = [data[0] for entry in rt.error_store.list(app_name=rt.name)
              for _, data in entry.events]
    assert sorted(delivered + stored) == list(range(2000)), \
        "chaos soak lost events"
    rt.shutdown()


# ========================================================== @OnError actions

def test_onerror_store_captures_stream_failures_and_replays():
    chaos.reset()
    _, rt = _mk("""
        @app:errorStore(type='memory')
        define stream s (v int);
        @OnError(action='STORE')
        define stream o (v int);
        @info(name='q') from s select v insert into o;
    """)
    got, fail = [], [True]

    def cb(evs):
        if fail[0]:
            raise RuntimeError("downstream down")
        got.extend(e.data[0] for e in evs)

    rt.add_callback("o", StreamCallback(cb))
    rt.start()
    h = rt.get_input_handler("s")
    h.send([1])
    h.send([2])
    assert got == []
    entries = rt.error_store.list(app_name=rt.name)
    assert [e.origin for e in entries] == ["stream", "stream"]
    assert [e.stream_id for e in entries] == ["o", "o"]
    assert rt.resilience_metrics.errors_stored_total.value(
        stream="o", origin="stream") == 2

    fail[0] = False
    assert rt.replay_errors() == 2
    assert sorted(got) == [1, 2]
    assert rt.error_store.count(rt.name) == 0
    rt.shutdown()


def test_onerror_store_without_store_falls_back_to_log():
    """No error store configured: STORE degrades to the LOG path (and the
    analyzer flags it as SA050 — see test_analyzer_flags_onerror_store)."""
    _, rt = _mk("""
        define stream s (v int);
        @OnError(action='STORE')
        define stream o (v int);
        @info(name='q') from s select v insert into o;
    """)
    errors = []
    rt.app_ctx.exception_listeners.append(errors.append)

    def cb(evs):
        raise RuntimeError("nope")

    rt.add_callback("o", StreamCallback(cb))
    rt.start()
    rt.get_input_handler("s").send([1])
    assert errors, "without a store the failure surfaces to listeners"
    rt.shutdown()


def test_onerror_wait_blocks_until_receiver_heals():
    _, rt = _mk("""
        define stream s (v int);
        @OnError(action='WAIT', retry.max.attempts='6',
                 retry.base.delay.ms='1', retry.jitter='0')
        define stream w (v int);
        @info(name='q') from s select v insert into w;
    """)
    got, fails = [], [2]

    def cb(evs):
        if fails[0] > 0:
            fails[0] -= 1
            raise RuntimeError("transient")
        got.extend(e.data[0] for e in evs)

    rt.add_callback("w", StreamCallback(cb))
    rt.start()
    rt.get_input_handler("s").send([5])     # blocks through 2 retries
    assert got == [5]
    assert rt.resilience_metrics.onerror_wait_retries_total.value(
        stream="w") >= 2
    rt.shutdown()


def test_analyzer_flags_onerror_store_without_store():
    from siddhi_tpu.analysis import analyze
    app = ("@OnError(action='STORE') define stream s (v int);\n"
           "from s select v insert into Out;")
    assert "SA050" in analyze(app).codes()
    with_store = "@app:errorStore(type='memory')\n" + app
    assert "SA050" not in analyze(with_store).codes()
    bad_action = ("@OnError(action='EXPLODE') define stream s (v int);\n"
                  "from s select v insert into Out;")
    assert "SA051" in analyze(bad_action).codes()


# ======================================================= checkpoints/recovery

SUM_APP = """
@app:name('ckapp')
define stream S (v float);
@info(name='q') from S select sum(v) as total insert into Out;
"""


def test_checkpoint_scheduler_fires_on_playback_time():
    """@app:persist checkpoints ride the app Scheduler, so playback
    virtual time drives them deterministically — no wall-clock waits."""
    store = InMemoryPersistenceStore()
    m, rt = _mk("@app:playback @app:persist(interval='1 sec')\n" + SUM_APP,
                store=store)
    assert rt.checkpoint_scheduler is not None
    assert rt.checkpoint_scheduler.interval_ms == 1000
    rt.start()
    h = rt.get_input_handler("S")
    for k in range(6):                       # ts 1.0s … 6.0s virtual
        h.send([1.0], timestamp=1_000 * (k + 1))
    revs = store.revisions(rt.name)
    assert len(revs) >= 3, f"expected ≥3 periodic checkpoints, got {revs}"
    assert all(r.endswith("_full") for r in revs)
    assert rt.resilience_metrics.checkpoints_total.value() == len(revs)
    rt.shutdown()

    # the last checkpoint restores into a fresh runtime and the sum
    # continues from the checkpointed state
    m2, rt2 = _mk(SUM_APP, store=store)
    got = []
    rt2.add_callback("Out", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt2.start()
    rt2.restore_last_revision()
    rt2.get_input_handler("S").send([1.0])
    rt2.shutdown()
    # ≥5 events were covered by the last checkpoint (the 6th may race the
    # final fire); continued sum reflects the restored accumulator
    assert got and got[-1] >= 6.0


def test_incremental_checkpoint_annotation():
    store = InMemoryPersistenceStore()
    m, rt = _mk("@app:playback "
                "@app:persist(interval='1 sec', incremental='true')\n"
                + SUM_APP, store=store)
    assert rt.checkpoint_scheduler.incremental is True
    rt.start()
    h = rt.get_input_handler("S")
    h.send([1.0], timestamp=1_000)
    base = rt.persist()                      # explicit full base
    assert base.endswith("_full")
    for k in range(3):
        h.send([1.0], timestamp=2_000 + 1_000 * k)
    assert any(r.endswith("_inc") for r in store.revisions(rt.name)), \
        "incremental='true' checkpoints must write _inc revisions"
    rt.shutdown()


def test_recover_flag_restores_last_revision():
    store = InMemoryPersistenceStore()
    m, rt = _mk(SUM_APP, store=store)
    rt.start()
    h = rt.get_input_handler("S")
    h.send([10.0])
    h.send([5.0])
    rev = rt.persist()
    rt.shutdown()

    rt2 = m.create_siddhi_app_runtime(SUM_APP, recover=True)
    assert rt2.recovered_revision == rev
    assert rt2.resilience_metrics.recovered.value() == 1
    got = []
    rt2.add_callback("Out", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt2.start()
    rt2.get_input_handler("S").send([1.0])
    rt2.shutdown()
    assert got == [pytest.approx(16.0)]


def test_recover_flag_with_empty_store_is_noop():
    m, rt = _mk(SUM_APP, store=InMemoryPersistenceStore())
    rt.shutdown()
    rt2 = m.create_siddhi_app_runtime(SUM_APP, recover=True)
    assert rt2.recovered_revision is None
    assert rt2.resilience_metrics.recovered.value() == 0
    rt2.shutdown()


# ------------------------------------------------------- kill-and-recover

CHILD_TEMPLATE = '''
import os, sys, time
sys.path.insert(0, {repo!r})
from siddhi_tpu import (FileSystemPersistenceStore, SiddhiManager,
                        StreamCallback)

K, TARGET, EXTRA = {k}, {target}, {extra}
APP = {app!r}

store = FileSystemPersistenceStore({snapdir!r})
m = SiddhiManager()
m.set_persistence_store(store)
rt = m.create_siddhi_app_runtime(APP)
outf = open({outpath!r}, "a")

def cb(evs):
    for e in evs:
        outf.write(repr(float(e.data[0])) + chr(10))
        outf.flush()
        os.fsync(outf.fileno())

rt.add_callback("Out", StreamCallback(cb))
rt.start()
h = rt.get_input_handler("S")
for i in range(1, TARGET + EXTRA + 1):
    h.send([float(i)])
    if i % K == 0 and i <= TARGET:
        rt.persist()                     # durable up to offset i …
        tmp = {ackpath!r} + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(i)); f.flush(); os.fsync(f.fileno())
        os.replace(tmp, {ackpath!r})     # … acked atomically
with open({readypath!r} + ".tmp", "w") as f:
    f.write("ready"); f.flush(); os.fsync(f.fileno())
os.replace({readypath!r} + ".tmp", {readypath!r})
while True:                              # hold unpersisted tail in memory
    time.sleep(1)
'''


def test_sigkill_recover_replay_no_event_loss(tmp_path):
    """The acceptance scenario: a child engine checkpoints every K=25
    events, is SIGKILLed holding 15 unpersisted events, and a recovered
    runtime replays from the last acked offset.  Every match appears at
    least once; duplicates are bounded by one checkpoint interval."""
    K, TARGET, EXTRA = 25, 200, 15
    snapdir = str(tmp_path / "snaps")
    outpath = str(tmp_path / "out.txt")
    ackpath = str(tmp_path / "ack")
    readypath = str(tmp_path / "ready")
    script = tmp_path / "child.py"
    script.write_text(CHILD_TEMPLATE.format(
        repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        k=K, target=TARGET, extra=EXTRA, app=SUM_APP, snapdir=snapdir,
        outpath=outpath, ackpath=ackpath, readypath=readypath))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        deadline = time.monotonic() + 180
        while not os.path.exists(readypath):
            if proc.poll() is not None:
                raise AssertionError(
                    "child engine died early:\n" +
                    proc.stderr.read().decode(errors="replace"))
            if time.monotonic() > deadline:
                raise AssertionError("child engine never reached ready")
            time.sleep(0.1)
        acked = int(open(ackpath).read())
        assert acked == TARGET
        os.kill(proc.pid, signal.SIGKILL)     # crash mid-stream
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    pre_crash = [float(line) for line in open(outpath)]
    m = SiddhiManager()
    m.set_persistence_store(FileSystemPersistenceStore(snapdir))
    rt = m.create_siddhi_app_runtime(SUM_APP, recover=True)
    assert rt.recovered_revision is not None, "recovery found no checkpoint"
    post = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: post.extend(float(e.data[0]) for e in evs)))
    rt.start()
    M = TARGET + EXTRA
    for i in range(acked + 1, M + 1):        # replay from last acked offset
        rt.get_input_handler("S").send([float(i)])
    rt.shutdown()

    # the restored accumulator held exactly sum(1..acked): replaying the
    # tail lands on the true total — state loss or tail inclusion in the
    # snapshot would both break this
    want_total = float(M * (M + 1) // 2)
    assert post[-1] == pytest.approx(want_total)
    # every match (running total T_i) observed at least once …
    want = {float(i * (i + 1) // 2) for i in range(1, M + 1)}
    seen = set(pre_crash) | set(post)
    assert want <= seen, f"lost matches: {sorted(want - seen)[:5]}"
    # … and duplicates bounded by one checkpoint interval
    dup = [v for v in post if v in set(pre_crash)]
    assert len(dup) <= K, f"{len(dup)} duplicate matches > interval K={K}"


# ========================================================= snapshot hygiene

def test_torn_snapshot_raises_typed_error(tmp_path):
    store = FileSystemPersistenceStore(str(tmp_path))
    m, rt = _mk(SUM_APP, store=store)
    rt.start()
    rt.get_input_handler("S").send([3.0])
    rev = rt.persist()
    rt.shutdown()
    blob = store.load("ckapp", rev)
    store.save("ckapp", rev, chaos.tear(blob, seed=5, mode="truncate"))

    m2, rt2 = _mk(SUM_APP, store=store)
    with pytest.raises(CannotRestoreStateError):
        rt2.restore_last_revision()
    rt2.shutdown()


def test_tearing_store_first_save_detected():
    store = chaos.TearingStore(InMemoryPersistenceStore(),
                               tear_ordinals=(1,), seed=9, mode="flip")
    m, rt = _mk(SUM_APP, store=store)
    rt.start()
    rt.get_input_handler("S").send([1.0])
    rt.persist()                                  # torn write
    rt.get_input_handler("S").send([1.0])
    rt.persist()                                  # clean write
    rt.shutdown()
    m2, rt2 = _mk(SUM_APP, store=store)
    rt2.restore_last_revision()                   # newest revision is clean
    assert store.saves == 2
    rt2.shutdown()


def test_filesystem_save_is_atomic_no_tmp_residue(tmp_path):
    fs = FileSystemPersistenceStore(str(tmp_path))
    fs.save("app", "100_app_full", b"payload")
    fs.save("app", "100_app_full", b"payload2")   # overwrite in place
    assert fs.load("app", "100_app_full") == b"payload2"
    leftovers = [p for root, _, files in os.walk(tmp_path)
                 for p in files if p.endswith(".tmp")]
    assert leftovers == [], "atomic save must not leave temp files"


def test_revision_ordering_is_numeric_not_lexicographic(tmp_path):
    fs = FileSystemPersistenceStore(str(tmp_path))
    fs.save("app", "9_app_full", b"old")
    fs.save("app", "10_app_full", b"new")         # lexicographically smaller
    assert fs.last_revision("app") == "10_app_full"
    assert fs.revisions("app") == ["9_app_full", "10_app_full"]
    mem = InMemoryPersistenceStore()
    mem.save("app", "9_app_full", b"old")
    mem.save("app", "10_app_full", b"new")
    assert mem.last_revision("app") == "10_app_full"


def test_persist_revisions_unique_under_burst():
    """Back-to-back persists within one millisecond must not collide on
    the same revision name (strictly-monotonic stamps)."""
    store = InMemoryPersistenceStore()
    m, rt = _mk(SUM_APP, store=store)
    rt.start()
    revs = [rt.persist() for _ in range(5)]
    assert len(set(revs)) == 5
    assert store.revisions(rt.name) == sorted(
        revs, key=lambda r: int(r.split("_")[0]))
    rt.shutdown()


# ==================================================== NFA batching × snapshot

PATTERN_APP = """
define stream A (v float);
@info(name='q')
from every e1=A[v > 10.0] -> e2=A[v > e1.v]
select e1.v as v1, e2.v as v2 insert into Out;
"""


@pytest.mark.parametrize("b_persist,b_restore", [(4, 1), (1, 4)])
def test_snapshot_compatible_across_nfa_batch_b(monkeypatch, b_persist,
                                                b_restore):
    """B changes the scan tick shape, not the carry layout: a snapshot
    persisted under SIDDHI_TPU_NFA_BATCH=4 restores at B=1 (and vice
    versa) and the armed partial match still completes."""
    from siddhi_tpu.ops.nfa import BATCH_ENV
    store = InMemoryPersistenceStore()
    monkeypatch.setenv(BATCH_ENV, str(b_persist))
    m = SiddhiManager()
    m.set_persistence_store(store)
    rt = m.create_siddhi_app_runtime(PATTERN_APP)
    assert rt.query_runtimes["q"].backend == "device"
    rt.start()
    rt.get_input_handler("A").send([11.0], timestamp=1_000_000)
    rev = rt.persist()
    rt.shutdown()

    monkeypatch.setenv(BATCH_ENV, str(b_restore))
    rt2 = m.create_siddhi_app_runtime(PATTERN_APP)
    out = []
    rt2.add_callback("Out", StreamCallback(
        lambda evs: out.extend(tuple(e.data) for e in evs)))
    rt2.start()
    rt2.restore_revision(rev)
    rt2.get_input_handler("A").send([12.0], timestamp=1_000_100)
    rt2.shutdown()
    assert out == [(11.0, 12.0)], \
        f"partial armed at B={b_persist} must complete after B={b_restore}"


# ============================================================ chaos harness

def test_source_connect_retries_through_chaos():
    chaos.reset()
    chaos.SCRIPTS["src"] = chaos.FailureScript.fail_n(2)
    _, rt = _mk("""
        @source(type='chaos', chaos.id='src', retry.base.delay.ms='1',
                retry.jitter='0')
        define stream s (v int);
        @info(name='q') from s select v insert into Out;
    """)
    got = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: got.extend(e.data[0] for e in evs)))
    rt.start()
    src = chaos.INSTANCES["src"]
    assert src.connected and src.connect_attempts == 3
    src.emit([7])
    rt.shutdown()
    assert got == [7]


def test_chunk_scrambler_is_seeded_deterministic():
    class Rec:
        def __init__(self):
            self.rows = []

        def receive_chunk(self, chunk):
            self.rows.extend(e.data[0] for e in chunk.to_events())

    def run():
        _, rt = _mk("define stream s (v int);\n"
                    "@info(name='q') from s select v insert into Out;")
        rec = Rec()
        sc = chaos.ChunkScrambler(rec, seed=3, duplicate_rate=0.3)
        rt.junctions["Out"].subscribe(sc)
        rt.start()
        h = rt.get_input_handler("s")
        for i in range(20):
            h.send([i])
        assert rec.rows == []                 # held until release
        sc.release()
        rt.shutdown()
        return rec.rows

    a, b = run(), run()
    assert a == b, "same seed must scramble identically"
    assert sorted(set(a)) == list(range(20))  # nothing lost
    assert len(a) > 20                        # seeded duplicates occurred
    assert a != sorted(a)                     # seeded reorder occurred


def test_inject_fault_wraps_and_restores():
    class Obj:
        def step(self, x):
            return x * 2

    o = Obj()
    script = chaos.FailureScript.fail_n(1)
    restore = chaos.inject_fault(o, "step", script, error_cls=ValueError)
    with pytest.raises(ValueError):
        o.step(1)
    assert o.step(2) == 4
    restore()
    assert script.calls == 2 and script.failures == 1

"""The in-flight queue's rule (plan/pipeline.py): a block is retired when
its result is ready — FIFO from the head, at every submit and from the
junction worker's idle hook, neither of which waits for the device — and
the pipeline depth is only the cap above which a submit blocks on the
oldest block.

Deterministic on the CPU: readiness is forced where a case needs it
(``_is_ready`` of plan/pipeline.py is what every check asks), and a
delivery is told from another by what the callback recorded, never by a
wall-clock threshold.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import ColumnarStreamCallback, SiddhiManager  # noqa: E402
from siddhi_tpu.plan import pipeline  # noqa: E402

ASYNC = "@Async(buffer.size='64', batch.size.max='65536')"
N = 20              # events per block
SPAN = 10 * N       # ms of event time per block
T0 = 1_000

_PATTERN = """@app:name('{name}') @app:playback {head}
{junction}
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

_WAGG = """@app:name('{name}') @app:playback {head}
{junction}
define stream S (sym string, price float, kind int);
partition with (sym of S) begin
@info(name='q0')
from S[price > 5.0]#window.length(4)
select sym, sum(price) as total, count() as n group by sym insert into Out0;
@info(name='q1')
from S[price > 6.0]#window.length(4)
select sym, sum(price) as total, count() as n group by sym insert into Out1;
end;
"""

_FILTER = """@app:name('{name}') @app:playback {head}
{junction}
define stream S (sym string, price float, kind int);
@info(name='q0')
from S[price > 5.0] select sym, price insert into Out0;
@info(name='q1')
from S[price > 6.0] select sym, price insert into Out1;
"""

_APPS = {"pattern": (_PATTERN, "DevicePatternRuntime"),
         "wagg": (_WAGG, "DeviceWindowedAggRuntime"),
         "filter": (_FILTER, "DeviceFilterRuntime")}
KINDS = sorted(_APPS)


@pytest.fixture
def single_device(monkeypatch):
    """One device, as a served chip has: there the patterns gang (the
    suite's eight virtual devices would mesh-shard them)."""
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    yield
    # a partition's device queries are not shut down with their app:
    # take this file's automata out of the process-wide gang again
    from siddhi_tpu.plan.xtenant import tenant_packer
    packer = tenant_packer()
    for row in list(packer.buckets.values()):
        for bucket in list(row):
            for nfa in list(bucket.tenants):
                if getattr(nfa, "_xt_label", "").startswith("ror_"):
                    packer.evict(nfa)


class _App:
    """One app of two queries on stream S; every delivery is recorded as
    (the index of the last block sent when it arrived, the blocks its
    rows belong to)."""

    def __init__(self, kind, name, head="@app:pipeline('4')", junction=""):
        text, cls = _APPS[kind]
        self.name = name
        self.rt = SiddhiManager().create_siddhi_app_runtime(
            text.format(name=name, head=head, junction=junction))
        self.sent = -1                  # index of the last block sent
        self.got = []                   # (sent then, block of the rows)
        self.order = {"Out0": [], "Out1": []}   # blocks as they arrived
        for out in self.order:
            self.rt.add_callback(out, ColumnarStreamCallback(
                lambda chunk, out=out: self._receive(out, chunk)))
        self.rt.start()
        qrs = dict(self.rt.query_runtimes)
        for pr in self.rt.partition_runtimes:
            qrs.update(pr.device_query_runtimes)
        self.devs = [qrs[q].device_runtime for q in ("q0", "q1")]
        assert [type(d).__name__ for d in self.devs] == [cls, cls]
        self.handler = self.rt.get_input_handler("S")

    def _receive(self, out, chunk):
        blocks = (np.asarray(chunk.timestamps) - T0) // SPAN
        assert len(set(blocks.tolist())) == 1, "one delivery, two blocks"
        self.got.append((self.sent, int(blocks[0])))
        self.order[out].append(int(blocks[0]))

    def send(self):
        """One block: per key a low `kind 0` event and then a higher
        `kind 1` one, twice over, so every block has rows of its own in
        every kind of app."""
        self.sent += 1
        j = np.arange(N)
        self.handler.send_batch(
            {"sym": np.asarray([f"k{i % 5}" for i in j], object),
             "price": (70.0 + 10.0 * ((j // 5) % 2) + 0.01 * j).astype(
                 np.float32),
             "kind": ((j // 5) % 2).astype(np.int32)},
            timestamps=T0 + SPAN * self.sent + 10 * j.astype(np.int64))

    def blocks_got(self):
        return sorted({b for _s, b in self.got})

    def inflight(self):
        return [len(d._inflight) for d in self.devs]

    def counters(self):
        entry = self.rt.statistics["ledger"]["apps"][self.name]
        return {k: v for k, v in entry.items() if k.startswith("retire_")}

    def close(self):
        self.rt.shutdown()


@pytest.fixture
def app(single_device, request):
    made = []

    def make(kind, **kw):
        name = "ror_" + request.node.name.translate(
            str.maketrans("[]-", "___"))
        made.append(_App(kind, name, **kw))
        return made[-1]
    yield make
    for a in made:
        a.close()


def _block_then_ready(buf):
    import jax
    jax.block_until_ready(buf)
    return True


@pytest.fixture
def all_ready(monkeypatch):
    """Every result is there when a check asks (the check waits for it
    first: what a fast device gives, without a race)."""
    monkeypatch.setattr(pipeline, "_is_ready", _block_then_ready)


@pytest.fixture
def none_ready(monkeypatch):
    monkeypatch.setattr(pipeline, "_is_ready", lambda buf: False)


# ----------------------------------------------------- (a) the next submit

@pytest.mark.parametrize("kind", KINDS)
def test_ready_block_is_retired_at_the_next_submit(app, all_ready, kind):
    a = app(kind)
    a.send()
    # the block is over for nobody yet: a pattern's gang is pending, the
    # others' fuse group is the open one
    assert a.got == [] and a.inflight() == [1, 1]
    a.send()
    # block 0 left during the second submit (the parent held it until
    # the fifth), block 1 stays: its own submit may not take it
    assert a.blocks_got() == [0] and {s for s, _b in a.got} == {1}
    assert a.inflight() == [1, 1]
    c = a.counters()
    assert c["retire_on_ready_total"] == 2 == c["retire_ready_total"]
    assert c["retire_on_depth_total"] == c["retire_on_flush_total"] == 0


# --------------------------------------------------------------- (b) FIFO

@pytest.mark.parametrize("kind", KINDS)
def test_unready_head_holds_back_a_ready_second(app, monkeypatch, kind):
    a = app(kind)
    a.send()
    # block 0's result, whatever a retire of it would read (its step's
    # own output, or the slab once its group is sealed): never ready
    heads = [d._inflight[0] for d in a.devs]

    def ready(buf):
        return all(buf is not pipeline._result_buffer(h) for h in heads)
    monkeypatch.setattr(pipeline, "_is_ready", ready)
    a.send()
    a.send()
    assert a.got == [] and a.inflight() == [3, 3]
    # the second block is ready and eligible, and still waits its turn
    assert all(pipeline.result_ready(d._inflight[1]) for d in a.devs)
    assert [d.settle() for d in a.devs] == [True, True]
    assert a.got == [] and a.inflight() == [3, 3]
    assert a.counters() == {}           # nothing blocked, nothing retired


# -------------------------------------------------------- (c) idle settle

@pytest.mark.parametrize("kind", KINDS)
def test_settle_launches_seals_and_retires_what_is_ready(
        app, all_ready, kind):
    a = app(kind)
    a.send()
    assert a.got == []
    if kind == "pattern":
        bucket = a.devs[0].nfa._tenant_bucket
        launches = bucket.flush_total
        assert all("xpend" in d._inflight[0] for d in a.devs)
    assert [d.settle() for d in a.devs] == [False, False]
    assert a.inflight() == [0, 0] and a.blocks_got() == [0]
    if kind == "pattern":
        # one gang launch for both tenants, by the first settle
        assert bucket.flush_total == launches + 1
    c = a.counters()
    assert c["retire_on_ready_total"] == 2


@pytest.mark.parametrize("kind", KINDS)
def test_settle_does_not_wait_for_an_unready_result(app, none_ready, kind):
    a = app(kind)
    a.send()
    done = []
    t = threading.Thread(
        target=lambda: done.append([d.settle() for d in a.devs]))
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and done == [[True, True]]
    assert a.got == [] and a.inflight() == [1, 1]
    # what settle could do without waiting is done: the gang is launched
    # and the block's fuse group closed
    for d in a.devs:
        h = d._inflight[0]
        assert "xpend" not in h and h["fuse"].group.sealed
    assert a.counters() == {}


# ------------------------------------------------ (d) one slab per block

def test_submit_time_checks_never_split_the_open_fuse_group(app, all_ready):
    a = app("wagg")
    fuser = a.devs[0]._fuser
    assert fuser is not None and fuser is a.devs[1]._fuser
    blocks = 6
    for _ in range(blocks):
        a.send()
    a.rt.flush()
    assert a.blocks_got() == list(range(blocks))
    # both queries' outputs of a block ride one slab: a check at q0's
    # submit that took q0's own (ready) block would have sealed a slab
    # for it alone, and q1 another
    assert fuser.d2h_count == blocks and fuser.blocks == blocks


# ------------------------------------------------------------- (e) the cap

@pytest.mark.parametrize("kind", KINDS)
def test_depth_still_caps_and_causes_add_up(app, none_ready, kind):
    a = app(kind)
    for _ in range(4):
        a.send()
    assert a.got == [] and a.inflight() == [4, 4] and a.counters() == {}
    a.send()
    # the fifth submit blocked on the first block
    assert a.blocks_got() == [0] and {s for s, _b in a.got} == {4}
    assert a.inflight() == [4, 4]
    assert a.counters()["retire_on_depth_total"] == 2
    a.rt.flush()
    assert a.blocks_got() == [0, 1, 2, 3, 4] and a.inflight() == [0, 0]
    c = a.counters()
    assert c["retire_on_flush_total"] == 8
    assert c["retire_on_ready_total"] == 0
    assert c["retire_on_ready_total"] + c["retire_on_depth_total"] \
        + c["retire_on_flush_total"] \
        == c["retire_ready_total"] + c["retire_blocked_total"] == 10


def test_depth_zero_is_synchronous_as_before(app, none_ready):
    """A synchronous junction with no annotation keeps nothing in flight,
    ready or not: rows before the send returns."""
    a = app("wagg", head="")
    assert a.devs[0].pipeline_depth == 0
    for _ in range(3):
        a.send()
        assert a.inflight() == [0, 0]
    assert [s for s, _b in a.got] == [b for _s, b in a.got]
    assert a.counters()["retire_on_depth_total"] == 6


# ----------------------------------------------------------- (f) end to end

@pytest.mark.parametrize("kind", KINDS)
def test_async_app_delivers_a_block_before_the_next_but_one_is_sent(
        app, kind):
    """Sends paced under the old 100 ms idle poll, each a block of its
    own: block k's rows are there before block k+2 is sent (the parent
    delivered them at block k+5's submit).  Told by what the callback
    recorded, not by the clock."""
    a = app(kind, head="", junction=ASYNC)
    assert a.devs[0].pipeline_depth == 4
    a.send()
    a.rt.flush()                        # compiled and warm
    assert a.blocks_got() == [0]
    blocks = 10
    for _ in range(blocks):
        a.send()
        time.sleep(0.05)
    late = [(s, b) for s, b in a.got if s > b + 1]
    assert late == [], late
    a.rt.flush()
    assert a.blocks_got() == list(range(blocks + 1))
    c = a.counters()
    # the worker's idle hook and the submits retired them, not the cap
    assert c["retire_on_depth_total"] == 0
    assert c["retire_on_ready_total"] >= 2 * (blocks - 1)
    # per query, every block once and in order
    assert a.order == {"Out0": list(range(blocks + 1)),
                       "Out1": list(range(blocks + 1))}


# ------------------------------------------------- the sharded pattern path

def test_sharded_pattern_queues_follow_the_same_rule(
        app, all_ready, monkeypatch):
    """With SIDDHI_TPU_SHARDS every shard engine has an in-flight queue
    of its own (no gang, no fused slab): the same helper retires them."""
    monkeypatch.setenv("SIDDHI_TPU_SHARDS", "2")
    a = app("pattern")
    shards = [sh for d in a.devs for sh in d.shards]
    assert len(shards) == 4
    a.send()
    # nothing stands between a shard's submit and its own ready result
    # but the rule that a submit does not wait: all_ready waits
    assert a.blocks_got() == [0]
    monkeypatch.setattr(pipeline, "_is_ready", lambda buf: False)
    a.send()
    assert a.blocks_got() == [0] and sum(
        len(sh.inflight) for sh in shards) > 0
    assert [d.settle() for d in a.devs] == [True, True]
    assert a.blocks_got() == [0]
    monkeypatch.setattr(pipeline, "_is_ready", _block_then_ready)
    assert [d.settle() for d in a.devs] == [False, False]
    assert a.blocks_got() == [0, 1]
    assert all(not sh.inflight for sh in shards)


# ------------------------------------------ the device-window processor

_DWIN = """@app:name('ror_dwin') @app:playback @app:pipeline('4')
define stream S (sym string, price float, kind int);
@info(name='q0')
from S#window.length(4) select sym, price insert into Out0;
"""


def test_device_window_queue_follows_the_same_rule(monkeypatch):
    """The mid-chain device window keeps a queue of its own
    (plan/dwin_compiler.py); the receiver's settle reaches it by walking
    the chain, as its flush does."""
    rt = SiddhiManager().create_siddhi_app_runtime(_DWIN)
    got = []
    rt.add_callback("Out0", ColumnarStreamCallback(
        lambda chunk: got.append(len(chunk))))
    rt.start()
    try:
        qr = rt.query_runtimes["q0"]
        win = qr.windows[0]
        assert type(win).__name__ == "DeviceWindowProcessor"
        assert win.pipeline_depth == 4
        recv = qr.receivers["S"]
        monkeypatch.setattr(pipeline, "_is_ready", lambda buf: False)
        j = np.arange(8)
        for i in range(2):
            rt.get_input_handler("S").send_batch(
                {"sym": np.asarray([f"k{x % 3}" for x in j], object),
                 "price": (j + 1.0).astype(np.float32),
                 "kind": (j % 2).astype(np.int32)},
                timestamps=T0 + 100 * i + j.astype(np.int64))
        assert got == [] and len(win._inflight) == 2
        assert recv.settle() is True and got == []
        monkeypatch.setattr(pipeline, "_is_ready", _block_then_ready)
        assert recv.settle() is False
        assert len(got) == 2 and not win._inflight
    finally:
        rt.shutdown()

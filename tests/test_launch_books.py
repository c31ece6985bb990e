"""The launch books: one wrapper (``RegisteredJit``), one name, one count.

Every jitted entry point is the shape registry's ``RegisteredJit`` and
nothing around it, and what a launch costs — calls, compiles, host bytes
in, device bytes back, live carry bytes, scan ticks — is booked on its
``ShapeEntry`` whether or not ``@app:statistics`` is on.  The readers:
``rt.statistics["kernels"]``, ``GET /stats``, ``siddhi_kernel_*`` on
``/metrics``, the flight ring's per-block ``dispatches`` and a watchdog
incident's ``kernel_dispatches``.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.flight import flight  # noqa: E402
from siddhi_tpu.core.ledger import ledger  # noqa: E402
from siddhi_tpu.plan.shapes import RegisteredJit, shape_registry  # noqa: E402

S = "define stream S (sym string, price float, vol long);\n"
PATTERN = ("@info(name='q') from every e1=S[vol == 0] -> "
           "e2=S[vol == 1 and price > e1.price] within 10 sec "
           "select e1.price as p1, e2.price as p2 insert into Out;")
PARTITIONED = "partition with (sym of S) begin {} end;"

#: registry kind -> (app body, environment that routes a CPU app there)
KIND_APPS = {
    "filter.program": (
        "@info(name='q') from S[price > 1.0] select sym, price "
        "insert into Out;", {}),
    "nfa.xstep": (PATTERN, {"SIDDHI_TPU_MESH": "off"}),
    "nfa.step": (PATTERN, {"SIDDHI_TPU_MESH": "off",
                           "SIDDHI_TPU_XTENANT": "0"}),
    "nfa.egress_pack": (PATTERN, {"SIDDHI_TPU_MESH": "off",
                                  "SIDDHI_TPU_XTENANT": "0"}),
    "wagg.length.step": (PARTITIONED.format(
        "@info(name='q') from S[price > 1.0]#window.length(4) "
        "select sym, sum(price) as total, count() as n group by sym "
        "insert into Out;"), {}),
    "gagg.step": (
        "@info(name='q') from S select sym, sum(price) as total, "
        "count() as n group by sym insert into Out;", {}),
    "select.step": (
        "@info(name='q') from S select sym, sum(price) as total, "
        "count() as n group by sym having total > 2.0 "
        "order by total desc limit 2 insert into Out;", {}),
    "dwin.lengthBatch.step": (
        "@info(name='q') from S#window.lengthBatch(4) "
        "select sym, price, vol insert all events into Out;", {}),
    "join.probe": (
        "define stream R (sym string, qty long);\n"
        "@info(name='q') from S#window.length(8) as a join "
        "R#window.length(8) as b on a.sym == b.sym "
        "select a.sym as sym, a.price as price, b.qty as qty "
        "insert into Out;", {}),
    "join.keyed_step": (
        "define stream R (sym string, qty long);\n"
        "@info(name='q') from S#window.time(10 sec) as a join "
        "R#window.time(10 sec) as b on a.sym == b.sym "
        "select a.sym as sym, a.price as price, b.qty as qty "
        "insert into Out;", {}),
}


def _send(rt, i, n=8):
    ts = 1_000_000 + i * 1000 + np.arange(n, dtype=np.int64) * 10
    rt.get_input_handler("S").send_batch(
        {"sym": np.asarray(["A", "B"] * (n // 2), object),
         "price": 2.0 + i * n + np.arange(n, dtype=np.float32),
         "vol": np.asarray([0, 1] * (n // 2), np.int64)},
        timestamps=ts)
    if "R" in rt.junctions:
        rt.get_input_handler("R").send_batch(
            {"sym": np.asarray(["A", "B"] * (n // 2), object),
             "qty": np.arange(n, dtype=np.int64)},
            timestamps=ts + 5)


def _metric(series, kind):
    lab = f'{series}{{kernel="{kind}"}} '
    vals = [ln[len(lab):] for ln in shape_registry().prometheus_lines()
            if ln.startswith(lab)]
    assert len(vals) == 1, (series, kind, vals)
    return int(vals[0])


@pytest.mark.parametrize("kind", sorted(KIND_APPS))
def test_every_kind_books_its_launches_without_statistics(kind, monkeypatch):
    body, env = KIND_APPS[kind]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    reg = shape_registry()
    before = reg.kernels().get(kind, {"calls": 0})["calls"]
    calls0 = reg.calls
    every0 = sum(r["calls"] for r in reg.kernels().values())
    rt = SiddhiManager().create_siddhi_app_runtime(
        "@app:playback " + S + body)
    rows = []
    rt.add_callback("Out", StreamCallback(rows.extend))
    rt.start()
    assert not rt.app_ctx.stats_enabled
    _send(rt, 0)
    _send(rt, 1)
    rt.flush()
    books = rt.statistics["kernels"]
    rt.shutdown()
    assert rows, f"{kind}: the app delivered nothing"
    # two sends: at least two launches of the kind, each on its entry
    assert kind in books, sorted(books)
    made = books[kind]["calls"] - before
    assert made >= 2, (kind, books[kind])
    assert sum(e.calls for e in reg._entries.values()
               if e.kind == kind) == books[kind]["calls"]
    # the running total the runtimes diff per block is the entries' sum
    assert reg.calls - calls0 == \
        sum(r["calls"] for r in reg.kernels().values()) - every0
    assert _metric("siddhi_kernel_dispatches_total", kind) == \
        books[kind]["calls"]
    assert _metric("siddhi_kernel_compile_count", kind) == \
        books[kind]["compiles"] >= 1


def _pattern_runtime(monkeypatch, xtenant):
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    monkeypatch.setenv("SIDDHI_TPU_XTENANT", xtenant)
    rt = SiddhiManager().create_siddhi_app_runtime(
        "@app:playback " + S + PATTERN)
    rt.add_callback("Out", StreamCallback(lambda evs: None))
    rt.start()
    _send(rt, 0)
    rt.flush()
    return rt


def _steps_nfa(monkeypatch):
    rt = _pattern_runtime(monkeypatch, "0")
    nfa = rt.query_runtimes["q"].device_runtime.nfa
    return rt, [nfa._step, nfa._egress_jit]


def _steps_gang(monkeypatch):
    rt = _pattern_runtime(monkeypatch, "1")
    bucket = rt.query_runtimes["q"].device_runtime.nfa._tenant_bucket
    return rt, [gang for gang, _caps in bucket._gangs.values()]


def _steps_of(body):
    def build(monkeypatch):
        rt = SiddhiManager().create_siddhi_app_runtime(
            "@app:playback " + S + body)
        rt.add_callback("Out", StreamCallback(lambda evs: None))
        rt.start()
        _send(rt, 0)
        rt.flush()
        qr = rt.query_runtimes.get("q")
        if qr is None:
            qr = rt.partition_runtimes[0].device_query_runtimes["q"]
        dev = qr.device_runtime
        if hasattr(dev, "_program"):
            return rt, [dev._program]
        if hasattr(dev, "cwa"):
            return rt, [dev.cwa._step]
        cga = dev.cga
        steps = [cga._step]
        if getattr(cga, "selection", None) is not None:
            steps.append(cga._select)
        return rt, steps
    return build


def _steps_bank(monkeypatch):
    from siddhi_tpu.plan.nfa_compiler import CompiledPatternBank
    apps = [S + f"from every e1=S[vol == 0 and price > {t}] -> "
            "e2=S[vol == 1] within 10 sec select e1.price as p1 "
            "insert into Out;" for t in (1.0, 5.0)]
    bank = CompiledPatternBank(apps, n_partitions=2, n_slots=4,
                               pattern_chunk=2)
    return None, [bank._step, bank.nfa._step]


COMPILERS = {
    "nfa": _steps_nfa,
    "xtenant": _steps_gang,
    "bank": _steps_bank,
    "filter": _steps_of(KIND_APPS["filter.program"][0]),
    "wagg": _steps_of(KIND_APPS["wagg.length.step"][0]),
    "gagg+select": _steps_of(KIND_APPS["select.step"][0]),
}


@pytest.mark.parametrize("compiler", sorted(COMPILERS))
def test_a_compilers_step_is_the_registered_jit_itself(compiler,
                                                       monkeypatch):
    rt, steps = COMPILERS[compiler](monkeypatch)
    try:
        assert steps
        for step in steps:
            # no wrapper around it, and none inside but the jit
            assert type(step) is RegisteredJit, type(step)
            assert not isinstance(step._jitted, RegisteredJit)
            assert step.entry.kind in shape_registry().kernels()
    finally:
        if rt is not None:
            rt.shutdown()


def test_flight_row_and_watchdog_read_the_registrys_delta(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    flight().reset()
    ledger().reset()
    reg = shape_registry()
    rt = SiddhiManager().create_siddhi_app_runtime(
        "@app:name('books') @app:playback " + S + PATTERN)
    rt.add_callback("Out", StreamCallback(lambda evs: None))
    rt.start()
    assert not rt.app_ctx.stats_enabled
    _send(rt, 0)                    # compiles; the next block is steady
    rt.flush()
    calls0, ticks0 = reg.marks()
    _send(rt, 1)
    d_calls, d_ticks = (now - was for now, was in
                        zip(reg.marks(), (calls0, ticks0)))
    rt.flush()
    row = [r for r in flight().ring() if r.get("app") == "books"
           and r.get("stream") == "S"][-1]
    assert row["dispatches"] == d_calls >= 1
    assert row["scan_ticks"] == d_ticks >= 1
    # the split by clock went: the row's ledger entry is that split
    assert "rim_ms" not in row and "kernel_ms" not in row
    per_block = ledger().dispatches_per_block()["books"]
    assert per_block > 0
    assert f'siddhi_app_dispatches_per_block{{app="books"}} ' in \
        "\n".join(ledger().prometheus_lines())
    # a WD001 incident carries the evidence with statistics off
    rt.watchdog._trip(rt.flush, 2_000_000, 10_000, 1_999_000)
    rt.shutdown()
    inc = rt.watchdog.incidents[-1]
    assert inc["code"] == "WD001"
    assert inc["kernel_dispatches"]["total_dispatches"] == reg.calls
    assert inc["kernel_dispatches"]["dispatches_per_block"]["books"] == \
        per_block


def test_h2d_bytes_counts_numpy_leaves_and_not_device_arrays():
    import jax.numpy as jnp
    step = shape_registry().jit(
        "test.h2d", {}, lambda a, b, c: a["x"].sum() + b[0].sum() + c)
    host = np.ones(16, np.float32)              # 64 bytes
    dev = jnp.ones(16, jnp.float32)
    step({"x": dev}, [dev], dev.sum())
    assert step.entry.h2d_bytes == 0
    step({"x": host}, [dev], dev.sum())
    assert step.entry.h2d_bytes == 64
    step({"x": host}, (host, dev), dev.sum())
    assert step.entry.h2d_bytes == 64 + 128
    assert step.entry.calls == 3
    assert shape_registry().kernels()["test.h2d"]["h2d_bytes"] == 192


def test_a_gang_of_equal_tenants_uploads_one_block(monkeypatch):
    """Four pattern queries over one stream hold one block of a chunk
    (ops/nfa.SharedPlanes), and the gang passes each distinct array
    once: ``h2d_bytes`` of `nfa.xstep` for a gang call of the four is
    one block's bytes, and a second flush of the same tenants sharing
    the same planes compiles nothing."""
    from siddhi_tpu.plan import xtenant
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")
    flushes = []
    step = xtenant.TenantBucket._gang_step

    def spy(self, entries):
        flushes.append([block for _nfa, block, _h in entries])
        return step(self, entries)
    monkeypatch.setattr(xtenant.TenantBucket, "_gang_step", spy)
    reg = shape_registry()
    body = "".join(
        PATTERN.replace("'q'", f"'q{q}'").replace("vol == 0",
                                                  f"vol == 0 and price > {q}")
        .replace("insert into Out", f"insert into Out{q}") for q in range(4))
    rt = SiddhiManager().create_siddhi_app_runtime(
        "@app:playback\n@Async(buffer.size='64', batch.size.max='4096')\n"
        + S + body)
    rows = []
    for q in range(4):
        rt.add_callback(f"Out{q}", StreamCallback(rows.extend))
    rt.start()
    books = []
    for i in range(3):
        _send(rt, i)
        rt.flush()
        books.append(dict(reg.kernels()["nfa.xstep"]))
    rt.shutdown()
    assert rows and [len(f) for f in flushes] == [4, 4, 4]
    for blocks, before, after in zip(flushes[1:], books, books[1:]):
        one = sum(plane.nbytes for plane in blocks[0].values())
        assert after["calls"] - before["calls"] == 1
        assert after["h2d_bytes"] - before["h2d_bytes"] == one
        assert sum(p.nbytes for b in blocks for p in b.values()) == 4 * one
        assert after["compiles"] == before["compiles"]


def test_retire_books_the_bytes_it_read_back(monkeypatch):
    """A fused slab's read is booked once, under the kind of the eager
    concat that made it (``other``); unfused, the same buffers' bytes
    are booked on the entry whose launch made them."""
    reg = shape_registry()
    got = {}
    for fuse, kind in (("1", "other"), ("0", "filter.program")):
        monkeypatch.setenv("SIDDHI_TPU_EGRESS_FUSE", fuse)
        d0 = {k: r["d2h_bytes"] for k, r in reg.kernels().items()}
        rt = SiddhiManager().create_siddhi_app_runtime(
            "@app:playback " + S + "@info(name='q') from S[price > 1.0] "
            "select sym, price * 2.0 as p2 insert into Out;")
        rt.add_callback("Out", StreamCallback(lambda evs: None))
        rt.start()
        _send(rt, 0)
        rt.flush()
        rt.shutdown()
        moved = {k: r["d2h_bytes"] - d0.get(k, 0)
                 for k, r in reg.kernels().items()
                 if r["d2h_bytes"] != d0.get(k, 0)}
        assert list(moved) == [kind], moved
        got[fuse] = moved[kind]
    # the slab carries the 8-event bool mask widened to int32
    assert got["1"] == got["0"] + 3 * 8 > 3 * 8, got


def test_live_bytes_is_the_sum_over_the_engines_of_a_shape_class():
    """Same-shape engines share a ShapeEntry: each books its own carry
    there, a grown one moves its share to its new class, and a dropped
    engine's share goes with it."""
    import gc

    from siddhi_tpu.plan.nfa_compiler import CompiledPatternNFA
    app = S + PATTERN

    def make():
        return CompiledPatternNFA(app, n_partitions=4, n_slots=4, mesh=None)

    def carry_bytes(n):
        return sum(int(v.nbytes) for v in n.carry.values())

    gc.collect()
    a = make()
    e4 = a._step.entry
    base = e4.live_bytes - carry_bytes(a)      # other tests' engines
    b = make()
    assert b._step.entry is e4
    one = carry_bytes(a)
    assert e4.live_bytes == base + 2 * one
    b.grow_slots(8)
    e8 = b._step.entry
    assert e8 is not e4
    assert e4.live_bytes == base + one
    assert e8.live_bytes >= carry_bytes(b) > one
    in8 = e8.live_bytes
    b.grow(8)                                   # same step, wider carry
    assert e8.live_bytes - in8 == carry_bytes(b) - carry_bytes(b) // 2
    in8 = e8.live_bytes
    held = carry_bytes(b)
    del b
    gc.collect()
    assert e8.live_bytes == in8 - held
    assert e4.live_bytes == base + one
    assert shape_registry().kernels()["nfa.step"]["live_bytes"] >= one

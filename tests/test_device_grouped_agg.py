"""Device grouped/running aggregation conformance (VERDICT r2 next #4+#8):
group-by finer than the partition key, no-window running aggregates,
minForever/maxForever, and EXACT INT/LONG sums on the device kernel
(ops/grouped_agg.py via plan/gagg_compiler.py) — byte-identical to the
host oracle through the public API.

Reference: query/selector/QuerySelector.java:44-224 (per-group aggregator
maps), GroupByKeyGenerator.java, SumAttributeAggregatorExecutor typed
variants."""
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback

STREAM = "define stream S (sym string, user string, price float, " \
         "volume long);\n"


def run_app(app, sends, engine=None, batch=None):
    prefix = "@app:playback "
    if engine:
        prefix += f"@app:engine('{engine}') "
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(prefix + app)
    out = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: out.extend(tuple(e.data) for e in evs)))
    rt.start()
    if batch is not None:
        rt.get_input_handler("S").send_batch(batch[0], timestamps=batch[1])
    else:
        for row, ts in sends:
            rt.get_input_handler("S").send(row, timestamp=ts)
    backends = {n: q.backend for n, q in rt.query_runtimes.items()}
    prs = rt.partition_runtimes
    device = any(b == "device" for b in backends.values()) or \
        any(pr.device_mode for pr in prs)
    rt.shutdown()
    return device, out


def _norm(rows):
    """Float payloads compare through float32 (the conformance-corpus
    convention, tests/ref_harness._norm): the host accumulates float64,
    the device Kahan-compensated float32 — equal at f32 precision."""
    return [tuple(float(np.float32(v)) if isinstance(v, float) else v
                  for v in r) for r in rows]


def assert_parity(app, sends=None, batch=None, expect_device=True,
                  unordered=False):
    """unordered: host partition clones process a chunk's events grouped
    by key (an oracle chunking artifact — the reference routes per event,
    which is the order the device path preserves), so batch sends through
    partitions compare as multisets."""
    _, host = run_app(app, sends, engine="host", batch=batch)
    dev_hit, dev = run_app(app, sends, batch=batch)
    assert dev_hit == expect_device, f"device={dev_hit}"
    norm = (lambda x: sorted(_norm(x), key=repr)) if unordered else _norm
    assert norm(host) == norm(dev), \
        f"host={host[:6]}... dev={dev[:6]}..."
    assert len(host) > 0
    return host


def _rows(n=40, seed=2, n_sym=3, n_user=5, vol_max=1000):
    rng = np.random.default_rng(seed)
    sends = []
    for i in range(n):
        sends.append(([f"s{rng.integers(0, n_sym)}",
                       f"u{rng.integers(0, n_user)}",
                       float(np.float32(rng.uniform(1, 100))),
                       int(rng.integers(-vol_max, vol_max))],
                      1_000_000 + i * 100))
    return sends


def test_groupby_in_length_window():
    app = STREAM + """
        @info(name='q') from S#window.length(5)
        select sym, sum(price) as t, count() as c, avg(price) as a
        group by sym insert into Out;"""
    assert_parity(app, _rows())


def test_mixed_aggregate_arguments():
    """Distinct aggregate arguments — float AND int banks in one query."""
    app = STREAM + """
        @info(name='q') from S#window.length(4)
        select sym, sum(volume) as tv, avg(price) as ap,
               max(price) as mp, min(volume) as mv
        group by sym insert into Out;"""
    assert_parity(app, _rows(vol_max=2_000_000_000))


def test_groupby_two_keys():
    app = STREAM + """
        @info(name='q') from S#window.length(4)
        select sym, user, sum(price) as t group by sym, user
        insert into Out;"""
    assert_parity(app, _rows())


def test_running_aggregates_no_window():
    app = STREAM + """
        @info(name='q') from S[price > 10.0]
        select sym, sum(price) as t, min(price) as mn, max(price) as mx
        group by sym insert into Out;"""
    assert_parity(app, _rows())


def test_exact_int_sum_window_and_running():
    app = STREAM + """
        @info(name='q') from S#window.length(3)
        select sym, sum(volume) as tv, min(volume) as mn,
               max(volume) as mx
        group by sym insert into Out;"""
    host = assert_parity(app, _rows(vol_max=2_000_000_000))
    assert all(isinstance(r[1], (int, np.integer)) for r in host)

    app2 = STREAM + """
        @info(name='q') from S select sum(volume) as tv insert into Out;"""
    assert_parity(app2, _rows(vol_max=2_000_000_000))


def test_min_max_forever():
    app = STREAM + """
        @info(name='q') from S#window.length(2)
        select sym, maxForever(price) as mf, minForever(price) as nf
        group by sym insert into Out;"""
    assert_parity(app, _rows())


def test_partitioned_finer_groupby():
    """Partition by sym, group by user — the VERDICT #4 shape: lanes are
    partition keys, groups are finer."""
    app = """
    define stream S (sym string, user string, price float, volume long);
    partition with (sym of S) begin
    @info(name='q') from S#window.length(3)
    select sym, user, sum(price) as t, count() as c group by user
    insert into Out;
    end;"""
    sends = _rows(n=60)
    batch = ({"sym": np.asarray([r[0][0] for r in sends], object),
              "user": np.asarray([r[0][1] for r in sends], object),
              "price": np.asarray([r[0][2] for r in sends], np.float32),
              "volume": np.asarray([r[0][3] for r in sends], np.int64)},
             np.asarray([r[1] for r in sends], np.int64))
    host = assert_parity(app, batch=batch, unordered=True)
    assert len(host) == 60
    # per-event sends: exact order parity (no oracle chunking artifact)
    assert_parity(app, sends[:30])


def test_partitioned_running_int_sum():
    app = """
    define stream S (sym string, user string, price float, volume long);
    partition with (sym of S) begin
    @info(name='q') from S select user, sum(volume) as tv group by user
    insert into Out;
    end;"""
    assert_parity(app, _rows(n=50, vol_max=1_500_000_000))


def test_group_capacity_growth():
    """More groups than the initial slab capacity (G_START=8)."""
    app = STREAM + """
        @info(name='q') from S#window.length(3)
        select user, sum(price) as t group by user insert into Out;"""
    assert_parity(app, _rows(n=120, n_user=40))


def test_snapshot_restore_grouped():
    app = STREAM + """
        @info(name='q') from S#window.length(3)
        select sym, sum(volume) as tv group by sym insert into Out;"""
    sends = _rows(n=30, vol_max=1_000_000_000)

    def run(engine, restore_mid):
        m = SiddhiManager()
        pre = f"@app:playback @app:engine('{engine}') " if engine else \
            "@app:playback "
        rt = m.create_siddhi_app_runtime(pre + app)
        out = []
        cb = StreamCallback(lambda evs: out.extend(tuple(e.data)
                                                   for e in evs))
        rt.add_callback("Out", cb)
        rt.start()
        h = rt.get_input_handler("S")
        for i, (row, ts) in enumerate(sends):
            h.send(row, timestamp=ts)
            if restore_mid and i == 14:
                snap = rt.snapshot()
                rt.shutdown()
                rt = m.create_siddhi_app_runtime(pre + app)
                rt.restore(snap)
                rt.add_callback("Out", cb)
                rt.start()
                h = rt.get_input_handler("S")
        rt.shutdown()
        return out

    assert run("host", False) == run(None, True)


def test_oversized_int_value_is_data_error():
    """|v| >= 2^31 cannot ride i32 lanes: the chunk is a runtime data
    error routed through the junction's @OnError boundary (LOG mode drops
    it), never a silently wrong sum."""
    app = STREAM + """
        @info(name='q') from S select sum(volume) as tv insert into Out;"""
    dev_hit, out = run_app(
        app, [(["s0", "u0", 1.0, 100], 1_000_000),
              (["s0", "u0", 1.0, 3_000_000_000], 1_000_100),
              (["s0", "u0", 1.0, 50], 1_000_200)])
    assert dev_hit
    # first chunk aggregated; the oversized chunk dropped with a logged
    # error; stream keeps running
    assert out[0] == (100,) and out[-1][0] <= 150


def test_device_rejects_unsupported_to_host():
    """Selection shapes the egress select kernel cannot express fall
    back from the grouped-agg kernel with a recorded reason.  (having
    on a float-sum output used to be in this list wholesale; it now
    compiles into the device selection step — plan/select_compiler.py —
    as lengthBatch and stdDev moved off this list in earlier rounds.)"""
    for frag in (
            # exact int64 sums do not fit the two-float compare lanes
            "select sym, sum(volume) as t group by sym having t > 10",
            # avg needs float64 division at selection time
            "select sym, avg(price) as m group by sym having m > 1.0",):
        app = STREAM + f"@info(name='q') from S{'' if frag.startswith('s') else ''}" \
            + ("" if frag.startswith("#") else " ") + frag + \
            " insert into Out;"
        dev_hit, _ = run_app(app, _rows(n=10))
        assert not dev_hit, frag
    # limit over a sliding window shares selector slots with expired
    # rows: the dwin hybrid may still own the window buffer, but the
    # selection tail itself must report the host route
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:playback " + STREAM + "@info(name='q') from "
        "S#window.length(4) select sym, sum(price) as t group by sym "
        "limit 2 insert into Out;")
    route = rt.query_runtimes["q"].selection_route
    assert route["backend"] == "host", route
    assert "expired" in route["reason"], route
    rt.shutdown()
    # burned-down shape: float-sum having now rides the device path
    app = STREAM + "@info(name='q') from S select sym, sum(price) as t " \
        "group by sym having t > 10.0 insert into Out;"
    dev_hit, _ = run_app(app, _rows(n=10))
    assert dev_hit, "float-sum having should ride the device select step"
    app = STREAM + "@info(name='q') from S#window.lengthBatch(3) " \
        "select sum(price) as t insert into Out;"
    dev_hit, _ = run_app(app, _rows(n=10))
    assert dev_hit, "lengthBatch should ride the device window path"


def test_int_minmax_only_has_no_count_bound():
    """Running min/max/count of ints need no exact-sum guard: groups can
    exceed 2^15 events (review finding: the INT_GROUP_MAX guard must key
    on sum/avg outputs, not on any int lane existing)."""
    app = STREAM + """
        @info(name='q') from S
        select min(volume) as mn, max(volume) as mx, count() as c
        insert into Out;"""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("@app:playback " + app)
    out = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: out.extend(tuple(e.data) for e in evs)))
    rt.start()
    h = rt.get_input_handler("S")
    n = (1 << 15) + 10
    rng = np.random.default_rng(0)
    vols = rng.integers(-1000, 1000, n)
    h.send_batch({"sym": np.full(n, "a", object),
                  "user": np.full(n, "u", object),
                  "price": np.ones(n, np.float32),
                  "volume": vols.astype(np.int64)},
                 timestamps=1_000_000 + np.arange(n, dtype=np.int64))
    assert rt.query_runtimes["q"].backend == "device"
    rt.shutdown()
    assert out[-1] == (int(vols.min()), int(vols.max()), n)


def test_infinite_float_values_propagate():
    """±inf inputs must reach min/max outputs (host parity), not clamp at
    ±F32_MAX (review finding: forever-lane sentinels)."""
    app = STREAM + """
        @info(name='q') from S
        select sym, min(price) as mn, maxForever(price) as mf
        group by sym insert into Out;"""
    sends = [(["a", "u", float("inf"), 1], 1_000_000),
             (["a", "u", 5.0, 1], 1_000_100),
             (["a", "u", float("-inf"), 1], 1_000_200)]
    assert_parity(app, sends)


def test_filtered_out_keys_allocate_no_groups():
    """Filter-rejected events must not grow the group slab (review
    finding: gid allocation ran before the ok mask)."""
    app = STREAM + """
        @info(name='q') from S[price > 1000.0]
        select user, sum(price) as t group by user insert into Out;"""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("@app:playback " + app)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(50):       # 50 distinct users, all filtered out
        h.send([f"s", f"u{i}", 1.0, 1], timestamp=1_000_000 + i * 100)
    qr = rt.query_runtimes["q"]
    assert qr.backend == "device"
    # an unpartitioned group-by steps one lane per group: no lane was
    # handed out, and the lanes never grew past their first capacity
    dev = qr.device_runtime
    cga = dev.cga
    assert cga.group_lanes and len(cga.gid_map) == 0
    assert dev.group_lanes.n == 0 and cga.n_lanes == 8, \
        (dev.group_lanes.n, cga.n_lanes)
    rt.shutdown()


def test_time_window_groupby_device():
    """Grouped sliding time windows on device: masked-expiry ring with a
    gid plane (ops/grouped_agg.build_grouped_time_step)."""
    app = STREAM + """
        @info(name='q') from S#window.time(1 sec)
        select sym, sum(price) as t, count() as c, min(price) as mn
        group by sym insert into Out;"""
    sends = []
    rng = np.random.default_rng(8)
    for i in range(40):
        sends.append(([f"s{rng.integers(0, 3)}", "u",
                       float(np.float32(rng.uniform(1, 100))), 1],
                      1_000_000 + i * 150))   # expiries interleave
    assert_parity(app, sends)


def test_external_time_window_groupby_int_sum_device():
    app = """
    define stream S (sym string, ets long, volume long);
    @info(name='q') from S#window.externalTime(ets, 1 sec)
    select sym, sum(volume) as tv, count() as c group by sym
    insert into Out;"""
    sends = []
    rng = np.random.default_rng(9)
    ets = 5_000_000
    for i in range(40):
        ets += int(rng.integers(50, 400))
        sends.append(([f"s{rng.integers(0, 3)}", ets,
                       int(rng.integers(-1_000_000_000, 1_000_000_000))],
                      1_000_000 + i * 100))
    assert_parity(app, sends)


def test_time_window_ring_growth_replay():
    """More in-window entries than the initial ring capacity (64): the
    grouped time ring must grow-and-replay, exactly."""
    app = STREAM + """
        @info(name='q') from S#window.time(10 sec)
        select sym, sum(price) as t, count() as c group by sym
        insert into Out;"""
    sends = []
    rng = np.random.default_rng(10)
    for i in range(200):                 # all within 10s of each other
        sends.append(([f"s{rng.integers(0, 2)}", "u",
                       float(np.float32(rng.uniform(1, 100))), 1],
                      1_000_000 + i * 40))
    host = assert_parity(app, sends)
    assert len(host) == 200


def test_partitioned_time_window_finer_groupby():
    app = """
    define stream S (sym string, user string, price float, volume long);
    partition with (sym of S) begin
    @info(name='q') from S#window.time(1 sec)
    select sym, user, sum(volume) as tv group by user insert into Out;
    end;"""
    assert_parity(app, _rows(n=50, vol_max=1_000_000_000))


def test_external_time_junk_ts_on_rejected_rows():
    """Filter-rejected rows carrying junk timestamps (ets=0 beside
    epoch-ms values) must not pin or blow the i32 time base (review:
    rebase must consider ACCEPTED rows only)."""
    app = """
    define stream S (sym string, ets long, volume long, kind int);
    @info(name='q') from S[kind == 1]#window.externalTime(ets, 1 sec)
    select sym, sum(volume) as tv group by sym insert into Out;"""
    epoch = 1_700_000_000_000
    sends = [(["a", epoch, 7, 1], 1_000_000),
             (["a", 0, 999, 0], 1_000_100),        # rejected, junk ets
             (["a", epoch + 500, 9, 1], 1_000_200)]
    out = assert_parity(app, sends)
    assert out == [("a", 7), ("a", 16)]


def test_group_count_bound_raises_with_consistent_state():
    """ADVICE r3: the >=2^15-events running-int-sum bound must restore
    the pre-block carry before raising, so @OnError continuation sees the
    offending chunk fully un-applied (not half-aggregated)."""
    from siddhi_tpu.ops.grouped_agg import INT_GROUP_MAX
    from siddhi_tpu.plan.gagg_compiler import CompiledGroupedAgg
    import siddhi_tpu.ops.grouped_agg as ga
    app = STREAM + """
        @info(name='q') from S select sum(volume) as tv insert into Out;"""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("@app:playback " + app)
    out = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: out.extend(tuple(e.data) for e in evs)))
    rt.start()
    qr = rt.query_runtimes["q"]
    assert qr.backend == "device"
    cga = qr.device_runtime.cga
    h = rt.get_input_handler("S")
    h.send(["a", "u", 1.0, 7], timestamp=1_000_000)
    carry_before = [np.asarray(a).copy() for a in cga.carry]
    # force the bound: pretend the group already accumulated 2^15 events
    cga.carry = type(cga.carry)(*[
        a if i != cga.carry._fields.index("gcnt")
        else np.full_like(np.asarray(a), INT_GROUP_MAX)
        for i, a in enumerate(cga.carry)])
    carry_forced = [np.asarray(a).copy() for a in cga.carry]
    h.send(["a", "u", 1.0, 9], timestamp=1_000_100)   # raises via @OnError
    after = [np.asarray(a) for a in cga.carry]
    # the offending chunk is fully un-applied: carry == pre-chunk carry
    assert all((x == y).all() for x, y in zip(carry_forced, after))
    rt.shutdown()
    assert out == [(7,)]


def test_stddev_randomized_parity():
    """stdDev lowers onto sum/sum-of-squares lanes (TwoSum pairs); device
    matches the host's float64 mean/meanSq formula at f32-normalized
    precision (the suite-wide float contract, _norm)."""
    app = STREAM + """
        @info(name='q') from S
        select sym, stdDev(price) as sd group by sym insert into Out;"""
    assert_parity(app, _rows(n=60))
    app2 = STREAM + """
        @info(name='q') from S#window.length(5)
        select stdDev(price) as sd insert into Out;"""
    assert_parity(app2, _rows(n=40))

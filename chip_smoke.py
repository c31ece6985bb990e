#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

One process drives every device kernel family once, through the entry
points a user calls (SiddhiManager -> send_batch -> device runtime ->
ColumnarStreamCallback), at deployment sizes, under @app:engine('device'),
and compares what comes out with the same app under @app:engine('host') on
the same seeded chunks.  It is a smoke, not a benchmark: the seconds it
prints are observations of one run, not measurements.

    python3 chip_smoke.py [--seed N] [--chunks N] [--blocks N]

Exit 0 and a last stdout line {"ok": true, "device": {...}} only when the
platform is tpu, every query ran with backend == "device", every required
registry kind compiled at least once, and every comparison held.  There is
no CPU mode: without an accelerator it exits 2 before any stage runs
(tests/test_chip_smoke.py rehearses the stage functions tiny on the CPU).
No stage is wrapped in a handler; whatever raises ends the run.

Sizes (cut chunk/block counts if the time limit forces it, never widths or
key counts — a cut is printed):
  pattern   BASELINE.json config 3's stream, one query: 10,000 random
            string keys, >= 4 chunks of 65,536 events, @Async ingest
  agg       config 2's shape, one query of the hundred: length(1000)
            sum/avg/count over 1,000 keys as wagg ring, as gagg + select
            tail, and a #window.time ring
  flagship  CompiledPatternBank, 1,000 patterns x 10,000 partitions, K=8,
            chunk 200, T=64 — the raw class (ROADMAP B1: the planner
            cannot reach 1k patterns yet), default stacking and batching
  families  filter.program, dwin.lengthBatch.step, join.probe, nfa.xstep
  pallas    the wagg ring kernel compiled by Mosaic at W=64 and W=1000
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T0 = 1_000_000          # first event timestamp (ms, playback time)
STEP_MS = 2             # inter-arrival of the generated streams

PATTERN_KEYS, PATTERN_CHUNK, PATTERN_CHUNKS = 10_000, 65_536, 4
AGG_KEYS, AGG_WINDOW, AGG_CHUNK, AGG_CHUNKS = 1_000, 1_000, 65_536, 4
BANK_PATTERNS, BANK_PARTITIONS, BANK_SLOTS = 1_000, 10_000, 8
BANK_CHUNK, BANK_T, BANK_RING, BANK_BLOCKS = 200, 64, 32, 3
BANK_CHECK = (0, 333, 666, 999)      # pattern rows compared with the oracle
BANK_WITHIN_MS = 40_000


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ helpers

def _keys(prefix: str, n: int) -> np.ndarray:
    return np.asarray([f"{prefix}{i}" for i in range(n)], object)


def _collect(rt, stream: str) -> list:
    """Keep every chunk a ColumnarStreamCallback delivers on `stream`."""
    from siddhi_tpu import ColumnarStreamCallback
    got = []
    rt.add_callback(stream, ColumnarStreamCallback(
        lambda ch: got.append((np.array(ch.timestamps), np.array(ch.types),
                               {k: np.array(v)
                                for k, v in ch.columns.items()}))))
    return got


def _table(chunks: list) -> dict:
    """Delivered chunks -> one column table (plus __ts / __type)."""
    if not chunks:
        return {"__ts": np.empty(0, np.int64)}
    out = {"__ts": np.concatenate([c[0] for c in chunks]),
           "__type": np.concatenate([c[1] for c in chunks])}
    for name in chunks[0][2]:
        out[name] = np.concatenate([c[2][name] for c in chunks])
    return out


def _backends(rt) -> dict:
    """{query: (backend, reason, selection backend)} over plain queries and
    device-mode partition queries; a host-cloned partition reports host."""
    qrs = dict(rt.query_runtimes)
    out = {}
    for pr in rt.partition_runtimes:
        if pr.device_mode:
            qrs.update(pr.device_query_runtimes)
        else:
            out[pr.name] = ("host", pr.fallback_reason, None)
    for name, qr in qrs.items():
        sel = qr.selection_route
        out[name] = (qr.backend, qr.backend_reason,
                     sel["backend"] if sel else None)
    return out


def run_app(app: str, engine: str, feeds, streams=("Out",), inspect=None):
    """One app under one engine mode over `feeds` [(stream, cols, ts)].
    -> ({stream: table}, {query: backend info}, inspect(rt) result)."""
    from siddhi_tpu import SiddhiManager
    if engine == "host":
        # the oracle ingests synchronously: under @Async the sender
        # advances the playback clock ahead of the worker, so timer-driven
        # host windows expire early by a race, not by law
        app = re.sub(r"@Async\([^)]*\)", "", app)
    rt = SiddhiManager().create_siddhi_app_runtime(
        f"@app:engine('{engine}') {app}")
    sinks = {s: _collect(rt, s) for s in streams}
    rt.start()
    handlers = {}
    for stream, cols, ts in feeds:
        if stream not in handlers:
            handlers[stream] = rt.get_input_handler(stream)
        handlers[stream].send_batch(cols, timestamps=ts)
    rt.flush()
    info = _backends(rt)
    seen = inspect(rt) if inspect is not None else None
    rt.shutdown()
    return {s: _table(c) for s, c in sinks.items()}, info, seen


def compare(host: dict, dev: dict, sort_by=None, rtol=0.0, atol=0.0) -> dict:
    """Row equality the way the repo's parity tests take it: same rows in
    the same order — delivered order for sort_by=(), else after a stable
    sort of both sides by `sort_by` (None: by every column, for engines
    that may emit in different orders) — strings and integers exact,
    floats within (rtol, atol), 0 meaning bit-equal."""
    n_h, n_d = len(host["__ts"]), len(dev["__ts"])
    res = {"rows_host": n_h, "rows_dev": n_d, "equal": False,
           "max_abs_dev": 0.0}
    if n_h != n_d or set(host) != set(dev):
        return res
    names = sorted(host)

    def norm(t):
        cols = {}
        for k in names:
            a = t[k]
            cols[k] = a.astype(str) if a.dtype == object else \
                a.astype(np.float64) if a.dtype.kind == "f" else a
        return cols
    h, d = norm(host), norm(dev)
    keys = list(sort_by) if sort_by is not None else names
    if keys:
        oh = np.lexsort([h[k] for k in reversed(keys)])
        od = np.lexsort([d[k] for k in reversed(keys)])
    else:                               # delivered order
        oh = od = np.arange(n_h)
    ok = True
    for k in names:
        a, b = h[k][oh], d[k][od]
        if a.dtype.kind == "f":
            both_nan = np.isnan(a) & np.isnan(b)
            dev_abs = np.where(both_nan, 0.0, np.abs(a - b))
            if n_h:
                res["max_abs_dev"] = max(res["max_abs_dev"],
                                         float(np.nanmax(dev_abs)))
            ok &= bool(np.all(both_nan |
                              (dev_abs <= atol + rtol * np.abs(a))))
        else:
            ok &= bool(np.array_equal(a, b))
    res["equal"] = ok
    return res


def _report(stage: str, n_in: int, cmp: dict, info: dict, **extra) -> dict:
    """Print one stage's outcome, then fail the run unless its rows equal
    the oracle's, there are rows, and every query — and selection tail —
    ran on the device."""
    rep = {"stage": stage, "events_in": n_in, "rows_out": cmp["rows_dev"],
           "rows_oracle": cmp["rows_host"], "rows_equal": cmp["equal"],
           "max_abs_dev": cmp["max_abs_dev"], "queries": info, **extra}
    log(f"[{stage}] events_in={n_in} rows_out={cmp['rows_dev']} "
        f"rows_oracle={cmp['rows_host']} rows==oracle={cmp['equal']} "
        f"max_abs_dev={cmp['max_abs_dev']:.3g}")
    for q, (backend, reason, sel) in info.items():
        log(f"[{stage}]   query {q}: backend={backend} "
            f"backend_reason={reason!r} selection={sel}")
    for k, v in extra.items():
        log(f"[{stage}]   {k}: {v}")
    bad = {q: v for q, v in info.items()
           if v[0] != "device" or v[2] == "host"}
    if bad:
        raise SystemExit(f"chip_smoke: [{stage}] not on the device: {bad}")
    if not cmp["equal"] or cmp["rows_dev"] == 0:
        raise SystemExit(f"chip_smoke: [{stage}] rows differ from the host "
                         f"oracle: {rep}")
    return rep


def _stream_feeds(rng, keys, chunk, chunks, stream="S", arrival_ms=None):
    """`chunks` chunks of `chunk` events: a key drawn at random per event,
    a price in [0, 100), a 0/1 kind.  Timestamps are STEP_MS apart, or —
    with arrival_ms — every event of a chunk carries the chunk's arrival
    time, as a source that stamps micro-batches on receipt would."""
    feeds = []
    for c in range(chunks):
        ts = np.full(chunk, T0 + c * arrival_ms, np.int64) \
            if arrival_ms is not None else \
            T0 + (c * chunk + np.arange(chunk, dtype=np.int64)) * STEP_MS
        feeds.append((stream, {
            "sym": keys[rng.integers(0, len(keys), chunk)],
            "price": rng.uniform(0, 100, chunk).astype(np.float32),
            "kind": rng.integers(0, 2, chunk).astype(np.int64)}, ts))
    return feeds


# ------------------------------------------------------------ pattern stage

def pattern_app(chunk: int) -> str:
    return f"""@app:playback
@Async(buffer.size='64', batch.size.max='{chunk}')
define stream S (sym string, price float, kind int);
partition with (sym of S) begin
@info(name='q')
from every e1=S[kind == 0 and price > 90.0]
    -> e2=S[kind == 1 and price > e1.price] within 40 sec
select e1.price as p1, e2.price as p2 insert into Out;
end;
"""


def _pattern_placement(rt) -> dict:
    """Where the pattern runtime's carry lives: mesh, shards, device set."""
    dr = rt.partition_runtimes[0].device_query_runtimes["q"].device_runtime
    if dr.shards is not None:
        devs = [sorted({str(d) for v in sh.engine.carry.values()
                        for d in v.sharding.device_set}) for sh in dr.shards]
        return {"mesh": None, "shards": len(dr.shards),
                "carry_devices": sorted({d for ds in devs for d in ds}),
                "per_shard_devices": devs,
                "lanes": [sh.engine.n_partitions for sh in dr.shards]}
    nfa = dr.nfa
    return {"mesh": None if nfa.mesh is None else int(nfa.mesh.devices.size),
            "shards": 0,
            "carry_devices": sorted({str(d) for v in nfa.carry.values()
                                     for d in v.sharding.device_set}),
            "lanes": nfa.n_partitions, "slots": nfa.spec.n_slots}


def stage_pattern(n_keys=PATTERN_KEYS, chunk=PATTERN_CHUNK,
                  chunks=PATTERN_CHUNKS, seed=0, shard_runs=(0, 2)) -> list:
    """Partitioned `every A -> B within` over random string keys through
    the @Async pipelined device path, once per entry of `shard_runs`
    (0 = the default placement, n >= 2 = shard-out over n shards,
    SIDDHI_TPU_SHARDS); rows exactly equal the host oracle each time.

    The default placement is decided by what the engine sees: on one
    device every live pattern automaton joins the tenant gang (nfa.xstep,
    even alone in its bucket); on several it is mesh-sharded
    (nfa.mesh_step).  Shard-out is the route to the plain per-engine step
    (nfa.step + nfa.egress_pack)."""
    import jax
    from siddhi_tpu.parallel.shards import SHARDS_ENV
    feeds = _stream_feeds(np.random.default_rng(seed), _keys("k", n_keys),
                          chunk, chunks)
    app = pattern_app(chunk)
    t = time.perf_counter()
    host, _, _ = run_app(app, "host", feeds)
    host_s = time.perf_counter() - t
    n_dev = len(jax.devices())
    prev = os.environ.pop(SHARDS_ENV, None)
    reps = []
    try:
        for shards in shard_runs:
            if shards:
                os.environ[SHARDS_ENV] = str(shards)
            else:
                os.environ.pop(SHARDS_ENV, None)
            t = time.perf_counter()
            dev, info, place = run_app(app, "device", feeds,
                                       inspect=_pattern_placement)
            dev_s = time.perf_counter() - t
            if shards:
                want = min(shards, n_dev)
                if place["shards"] != shards or \
                        len(place["carry_devices"]) != want:
                    raise SystemExit(
                        f"chip_smoke: shard-out wanted {shards} shards on "
                        f"{want} devices, got {place}")
            elif n_dev > 1 and (place["mesh"] != n_dev or
                                len(place["carry_devices"]) != n_dev):
                raise SystemExit(f"chip_smoke: pattern carry not sharded "
                                 f"over {n_dev} devices: {place}")
            reps.append(_report(
                f"pattern.shards{shards}" if shards else "pattern",
                chunk * chunks, compare(host["Out"], dev["Out"]), info,
                keys=n_keys, chunk=chunk, chunks=chunks, placement=place,
                device_wall_s=round(dev_s, 2),
                oracle_wall_s=round(host_s, 2)))
    finally:
        os.environ.pop(SHARDS_ENV, None)
        if prev is not None:
            os.environ[SHARDS_ENV] = prev
    return reps


# -------------------------------------------------------- aggregation stage

def stage_agg(n_keys=AGG_KEYS, window=AGG_WINDOW, chunk=AGG_CHUNK,
              chunks=AGG_CHUNKS, time_ms=1_500, arrival_ms=1_000,
              seed=1) -> list:
    """length(window) sum/avg/count over n_keys keys three ways: the keyed
    partition form (wagg ring), group-by with a having / order-by tail and
    a running group-by with having / order-by / limit (gagg + select.step),
    and a keyed #window.time ring.  Float aggregates ride f32 lanes on the
    device and f64 on the host, so they compare at f32 resolution.

    The time form is fed arrival-stamped chunks (one timestamp per chunk):
    the host TimeWindowProcessor expires once per chunk at its last
    timestamp while the device ring expires per event, so the two agree
    by law only where a chunk does not straddle an expiry boundary."""
    head = (f"@app:playback @Async(buffer.size='64', "
            f"batch.size.max='{chunk}') "
            "define stream S (sym string, price float, kind int);\n")
    keyed = head + f"""partition with (sym of S) begin
@info(name='q')
from S[price > 5.0]#window.length({window})
select sym, sum(price) as total, avg(price) as ap, count() as n
group by sym insert into Out;
end;
"""
    # the sliding-window tail keeps off `limit` (host by law there: the
    # host selector slices CURRENT and EXPIRED rows together); the running
    # query carries it
    grouped = head + f"""@info(name='qwin')
from S[price > 5.0]#window.length({window})
select sym, sum(price) as total, count() as n, max(price) as hi
group by sym having total > 60.0 order by total desc insert into Out;
@info(name='qrun')
from S select sym, sum(price) as total, count() as n
group by sym having total > 1000.0 order by total desc limit 8
insert into Top;
"""
    timed = head + f"""partition with (sym of S) begin
@info(name='q')
from S[price > 5.0]#window.time({time_ms})
select sym, sum(price) as total, count() as n
group by sym insert into Out;
end;
"""
    keys = _keys("g", n_keys)
    feeds = _stream_feeds(np.random.default_rng(seed), keys, chunk, chunks)
    stamped = _stream_feeds(np.random.default_rng(seed), keys, chunk, chunks,
                            arrival_ms=arrival_ms)
    n_in = chunk * chunks
    reps = []
    f32 = dict(rtol=1e-5, atol=1e-3)
    for name, app, feed, streams, sort_by in (
            ("agg.wagg_length", keyed, feeds, ("Out",), ("__ts",)),
            # the select tail orders rows inside an emission
            ("agg.gagg_select", grouped, feeds, ("Out", "Top"), ()),
            # per key the running count rises through a chunk
            ("agg.wagg_time", timed, stamped, ("Out",),
             ("__ts", "sym", "n"))):
        t = time.perf_counter()
        dev, info, _ = run_app(app, "device", feed, streams)
        dev_s = time.perf_counter() - t
        host, _, _ = run_app(app, "host", feed, streams)
        host_s = time.perf_counter() - t - dev_s
        for s in streams:
            cmp = compare(host[s], dev[s], sort_by, **f32)
            reps.append(_report(
                name if s == "Out" else f"{name}.{s}", n_in, cmp, info,
                keys=n_keys, window=window, chunk=chunk, chunks=chunks,
                device_wall_s=round(dev_s, 2),
                oracle_wall_s=round(host_s, 2)))
    return reps


# ------------------------------------------------------------ flagship bank

BANK_STREAM = "define stream S (partition int, price float, kind int);"


def _bank_query(thr: float, name: str = "q", out: str = "Out") -> str:
    """One bank pattern — the same text compiles into the bank's parameter
    lanes and, for the checked rows, into the host oracle."""
    return (f"@info(name='{name}') from every e1=S[kind == 0 and price > "
            f"{thr}] -> e2=S[kind == 1 and price > e1.price and "
            f"price > 0.0] within {BANK_WITHIN_MS} milliseconds "
            f"select e1.price as p1, e2.price as p2 insert into {out};")


def stage_bank(n_patterns=BANK_PATTERNS, n_partitions=BANK_PARTITIONS,
               n_slots=BANK_SLOTS, pattern_chunk=BANK_CHUNK, t_blk=BANK_T,
               ring=BANK_RING, blocks=BANK_BLOCKS, check=BANK_CHECK,
               seed=2) -> dict:
    """The flagship width as the raw CompiledPatternBank (the only route
    to 1k patterns today), default stacking (C chunks in one dispatch) and
    default B-batching.  Arrival is round-robin over the lanes with a
    per-lane gap of n_partitions ms, so live partials per lane stay
    <= within/gap + 1 = 5 < K and `dropped == 0` is a law, not luck.
    Per-pattern match counts on `check` rows equal the host oracle."""
    import jax
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.ops.nfa import pack_blocks
    from siddhi_tpu.plan.nfa_compiler import CompiledPatternBank
    thrs = np.linspace(90.0, 99.9, n_patterns)
    t = time.perf_counter()
    bank = CompiledPatternBank([f"{BANK_STREAM} {_bank_query(x)}"
                                for x in thrs],
                               n_partitions=n_partitions, n_slots=n_slots,
                               pattern_chunk=min(pattern_chunk, n_patterns),
                               ring=ring)
    bank.base_ts = T0
    build_s = time.perf_counter() - t
    rng = np.random.default_rng(seed)
    gap = n_partitions                  # ms between a lane's events
    counts = np.zeros(n_patterns, np.int64)
    flats, block_s = [], []
    for b in range(blocks):
        n = n_partitions * t_blk
        pids = np.tile(np.arange(n_partitions, dtype=np.int64), t_blk)
        ts = T0 + b * t_blk * gap + \
            np.repeat(np.arange(t_blk, dtype=np.int64), n_partitions) * gap \
            + pids
        cols = {"partition": pids.astype(np.float32),
                "price": rng.uniform(0, 100, n).astype(np.float32),
                "kind": rng.integers(0, 2, n).astype(np.float32)}
        flats.append((pids, cols, ts))
        block = pack_blocks(pids, cols, ts, np.zeros(n, np.int32),
                            n_partitions, base_ts=T0)
        assert block["__ts"].shape == (n_partitions, t_blk), \
            block["__ts"].shape
        # block 0 pays the compile; after it, blocks are closed in turn
        # by jax.block_until_ready and by the D2H read of the counts
        # alone — the two agree when the barrier is a true one
        by_read = b > 0 and b % 2 == 0
        t = time.perf_counter()
        out = bank.process_block(block)
        blk_counts = out[0] if ring else out
        if by_read:
            np.asarray(blk_counts)
        else:
            jax.block_until_ready(out)
        block_s.append(("d2h_read" if by_read else "block_until_ready",
                        round(time.perf_counter() - t, 4)))
        counts += np.asarray(blk_counts, np.int64)
    dropped = bank.total_dropped()
    carry_devs = sorted({str(d) for c in bank.carries for v in c.values()
                         for d in v.sharding.device_set})

    queries = "\n".join(_bank_query(thrs[i], f"q{i}", f"Out{i}")
                        for i in check)
    rt = SiddhiManager().create_siddhi_app_runtime(
        f"@app:playback @app:engine('host') {BANK_STREAM} "
        f"partition with (partition of S) begin {queries} end;")
    expect = {i: 0 for i in check}
    for i in check:
        def cb(evs, _i=i):
            expect[_i] += len(evs)
        rt.add_callback(f"Out{i}", StreamCallback(cb))
    rt.start()
    h = rt.get_input_handler("S")
    t = time.perf_counter()
    for pids, cols, ts in flats:
        h.send_batch({"partition": pids.astype(np.int32),
                      "price": cols["price"],
                      "kind": cols["kind"].astype(np.int32)}, timestamps=ts)
    rt.shutdown()
    oracle_s = time.perf_counter() - t
    got = {i: int(counts[i]) for i in check}
    rep = {"stage": "flagship.bank (raw CompiledPatternBank)",
           "events_in": blocks * n_partitions * t_blk,
           "patterns": n_patterns, "partitions": n_partitions,
           "slots": n_slots, "pattern_chunk": bank.chunk, "T": t_blk,
           "ring": ring, "blocks": blocks, "stacked": bank.stacked,
           "chunks_per_dispatch": bank.n_chunks if bank.stacked else 1,
           "batch_b": bank.nfa.batch_b, "matches_total": int(counts.sum()),
           "counts": got, "oracle": expect, "counts_equal": got == expect,
           "dropped": dropped, "carry_devices": carry_devs,
           "build_s": round(build_s, 2), "block_s": block_s,
           "oracle_wall_s": round(oracle_s, 2)}
    for k, v in rep.items():
        log(f"[flagship] {k}: {v}")
    if got != expect or sum(expect.values()) == 0 or dropped != 0:
        raise SystemExit(f"chip_smoke: [flagship] bank counts {got} vs "
                         f"oracle {expect}, dropped={dropped}")
    return rep


# --------------------------------------------------- every other family once

def stage_families(n=16_384, batch_len=1_000, join_n=2_048, join_win=512,
                   tenants=2, seed=3) -> list:
    """filter.program, a lengthBatch plain projection (dwin), a windowed
    join with a range condition (join.probe) and `tenants` small pattern
    apps in one process (nfa.xstep — the gang is on by default where the
    automata stay single-device)."""
    import jax
    from siddhi_tpu import SiddhiManager
    rng = np.random.default_rng(seed)
    keys = _keys("f", 64)
    feeds = _stream_feeds(rng, keys, n, 2)
    head = "@app:playback define stream S (sym string, price float, " \
           "kind int);\n"
    reps = []
    for name, app in (
            ("filter", head + "@info(name='q') from S[price > 50.0 and "
             "kind == 1] select sym, price, price * 2.0 as dbl "
             "insert into Out;"),
            ("dwin.lengthBatch", head + f"@info(name='q') from "
             f"S#window.lengthBatch({batch_len}) select sym, price "
             "insert into Out;")):
        dev, info, _ = run_app(app, "device", feeds)
        host, _, _ = run_app(app, "host", feeds)
        reps.append(_report(
            name, 2 * n, compare(host["Out"], dev["Out"], ()), info))

    join_app = f"""@app:playback
define stream L (sym string, price float);
define stream R (sym string, lo float, hi float);
@info(name='q')
from L#window.length({join_win}) as l join R#window.length({join_win}) as r
    on l.price > r.lo and l.price < r.hi
select l.sym as ls, r.sym as rs, l.price as lp, r.lo as lo insert into Out;
"""
    lo = rng.uniform(0, 99, join_n).astype(np.float32)
    jfeeds = []
    for c in range(2):
        base = T0 + 2 * c * join_n * STEP_MS
        ts = base + np.arange(join_n, dtype=np.int64) * STEP_MS
        jfeeds.append(("R", {"sym": keys[rng.integers(0, 64, join_n)],
                             "lo": lo, "hi": lo + np.float32(0.5)}, ts))
        jfeeds.append(("L", {
            "sym": keys[rng.integers(0, 64, join_n)],
            "price": rng.uniform(0, 100, join_n).astype(np.float32)},
            ts + join_n * STEP_MS))
    dev, info, _ = run_app(join_app, "device", jfeeds)
    host, _, _ = run_app(join_app, "host", jfeeds)
    reps.append(_report(
        "join.range", 4 * join_n, compare(host["Out"], dev["Out"], ()),
        info))

    # `tenants` apps, one small pattern each, fed round-robin: single-
    # device automata of one shape class share a gang dispatch
    def tenant_app(i, engine):
        return (f"@app:name('smoke_t{i}_{engine}') @app:playback "
                f"@app:engine('{engine}') @app:pipeline('4') "
                "define stream S (k int, v float); @info(name='q') "
                f"from every e1=S[v > 0.{5 + i}] -> e2=S[v > e1.v] "
                "select e1.v as a, e2.v as b insert into Out;")
    walls = [rng.uniform(0.0, 1.0, (tenants, 64)).astype(np.float32)
             for _ in range(4)]

    def run_tenants(engine):
        m = SiddhiManager()
        rts = [m.create_siddhi_app_runtime(tenant_app(i, engine))
               for i in range(tenants)]
        sinks = [_collect(rt, "Out") for rt in rts]
        for rt in rts:
            rt.start()
        for w, vals in enumerate(walls):
            for i, rt in enumerate(rts):
                rt.get_input_handler("S").send_batch(
                    {"k": np.arange(64, dtype=np.int64) % 4, "v": vals[i]},
                    timestamps=T0 + w * 64 + np.arange(64, dtype=np.int64))
        for rt in rts:
            rt.flush()
        info, packed = {}, []
        for i, rt in enumerate(rts):
            info.update({f"t{i}.{q}": v for q, v in _backends(rt).items()})
            dr = rt.query_runtimes["q"].device_runtime
            b = getattr(getattr(dr, "nfa", None), "_tenant_bucket", None)
            packed.append(b.label if b is not None else None)
        for rt in rts:
            rt.shutdown()
        return [_table(s) for s in sinks], info, packed

    dev_t, info, packed = run_tenants("device")
    host_t, _, _ = run_tenants("host")
    if len(jax.devices()) == 1 and None in packed:
        raise SystemExit(f"chip_smoke: tenants not gang-packed: {packed}")
    for i in range(tenants):
        reps.append(_report(
            f"tenants.t{i}", 4 * 64, compare(host_t[i], dev_t[i]),
            {k: v for k, v in info.items() if k.startswith(f"t{i}.")},
            bucket=packed[i]))
    return reps


# ------------------------------------------------------------- pallas stage

def stage_pallas(shapes=((1_024, 64, 16), (1_024, 1_000, 16)),
                 seed=4) -> list:
    """The repo's one Pallas kernel (ops/windowed_agg.build_wagg_step_
    pallas), built the way its only caller builds it —
    CompiledWindowedAgg(use_pallas=True), never interpret — and stepped
    twice next to the jnp build_wagg_step twin on the same blocks.  The
    served path does not use it (the planner passes use_pallas=False)."""
    from siddhi_tpu.plan.wagg_compiler import CompiledWindowedAgg
    reps = []
    for P, W, T in shapes:
        app = ("define stream S (k int, v float); @info(name='q') "
               f"from S[v > 2.0]#window.length({W}) select k, sum(v) as "
               "total, count() as n, min(v) as lo, max(v) as hi "
               "group by k insert into Out;")
        rng = np.random.default_rng(seed)
        twins = [CompiledWindowedAgg(app, n_partitions=P, t_per_block=T,
                                     use_pallas=up) for up in (True, False)]
        equal, worst = True, 0.0
        t = time.perf_counter()
        for _ in range(2):
            block = {"k": np.zeros((P, T), np.float32),
                     "v": rng.uniform(0, 10, (P, T)).astype(np.float32),
                     "__ts": np.zeros((P, T), np.int32),
                     "__valid": rng.random((P, T)) < 0.9}
            a, b = (tw.process_block(block) for tw in twins)
            for x, y in zip(a, b):
                x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
                # an empty lane's min/max is +-inf on both sides
                fin = np.isfinite(x) & np.isfinite(y)
                equal &= bool(np.array_equal(x[~fin], y[~fin]))
                if fin.any():
                    d = np.abs(x[fin] - y[fin])
                    worst = max(worst, float(d.max()))
                    equal &= bool(np.all(d <= 1e-3 + 1e-5 * np.abs(y[fin])))
        rep = {"stage": f"pallas.wagg P={P} W={W} T={T}",
               "mosaic_compiled": twins[0].use_pallas, "equal": equal,
               "max_abs_dev": worst,
               "wall_s": round(time.perf_counter() - t, 2)}
        log(f"[pallas] {rep}")
        if not equal:
            raise SystemExit(f"chip_smoke: [pallas] kernel differs from "
                             f"build_wagg_step: {rep}")
        reps.append(rep)
    return reps


# -------------------------------------------------------------------- main

def registry_table() -> dict:
    """Shape registry folded per kind: compiles, attributed compile
    seconds, persistent-cache hits/misses (Pallas builds split out)."""
    from siddhi_tpu.plan.shapes import shape_registry
    table = {}
    for e in shape_registry().snapshot()["entries"]:
        kind = e["kind"] + (".pallas" if e["dims"].get("pallas") else "")
        row = table.setdefault(kind, {"shapes": 0, "compiles": 0,
                                      "compile_s": 0.0, "cache_hits": 0,
                                      "cache_misses": 0})
        row["shapes"] += 1
        row["compiles"] += e["compiles"]
        row["compile_s"] = round(row["compile_s"] + e["compile_seconds"], 3)
        row["cache_hits"] += e["cache_hits"]
        row["cache_misses"] += e["cache_misses"]
    return table


def required_kinds(n_devices: int) -> list:
    """Registry kinds that must show >= 1 compile.  With more than one
    device every planner-built pattern automaton is mesh-sharded, so the
    step is nfa.mesh_step and the single-device gang never forms."""
    kinds = ["nfa.step", "nfa.egress_pack", "nfa.bank_step",
             "wagg.length.step", "wagg.time.step", "gagg.step",
             "select.step", "filter.program", "dwin.lengthBatch.step",
             "join.probe", "wagg.length.step.pallas"]
    return kinds + ["nfa.xstep" if n_devices == 1 else "nfa.mesh_step"]


def device_header() -> dict:
    """Platform, kind, count and versions; exits 2 without a TPU."""
    import importlib.metadata as md

    from siddhi_tpu.core.profiling import device_info
    dev = device_info()
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            vers[pkg] = "absent"
    log(f"[device] platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} jax={vers['jax']} jaxlib={vers['jaxlib']} "
        f"libtpu={vers['libtpu']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform="
              f"{dev['platform']} ({dev['count']} device(s)); there is no "
              f"CPU mode", file=sys.stderr)
        raise SystemExit(2)
    return dev


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunks", type=int, default=PATTERN_CHUNKS,
                    help="65,536-event chunks per engine stage")
    ap.add_argument("--blocks", type=int, default=BANK_BLOCKS,
                    help="flagship bank blocks")
    args = ap.parse_args()
    t_start = time.perf_counter()
    dev = device_header()
    if args.chunks != PATTERN_CHUNKS or args.blocks != BANK_BLOCKS:
        log(f"[cut] chunks={args.chunks} (default {PATTERN_CHUNKS}) "
            f"blocks={args.blocks} (default {BANK_BLOCKS})")

    from siddhi_tpu.native_ext import native_status
    from siddhi_tpu.plan.shapes import configure_compile_cache
    native = native_status()
    log(f"[native] _native.so built_now={native['built']} "
        f"loaded={native['loaded']} error={native['error']!r}")
    if not native["loaded"]:
        raise SystemExit("chip_smoke: the native packer is required "
                         "(make -C native)")
    cache = configure_compile_cache()
    log(f"[cache] dir={cache['dir']} enabled={cache['enabled']} "
        f"JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '')!r}")

    s = args.seed
    stage_pattern(chunks=args.chunks, seed=s,
                  shard_runs=(0, max(dev["count"], 2)))
    stage_agg(chunks=args.chunks, seed=s + 1)
    stage_bank(blocks=args.blocks, seed=s + 2)
    stage_families(seed=s + 3)
    stage_pallas(seed=s + 4)
    if dev["count"] > 1:
        log(f"[placement] {dev['count']} devices: the partitioned pattern "
            f"ran mesh-sharded and again shard-out; on device 0 only: wagg, "
            f"gagg+select, filter, dwin, join.probe, CompiledPatternBank, "
            f"pallas wagg; small pattern apps are meshed too, so the "
            f"single-device gang (nfa.xstep) did not form")

    table = registry_table()
    log("[registry] kind | shapes | compiles | compile_s | "
        "cache_hits | cache_misses")
    for kind in sorted(table):
        r = table[kind]
        log(f"[registry] {kind} | {r['shapes']} | {r['compiles']} | "
            f"{r['compile_s']} | {r['cache_hits']} | {r['cache_misses']}")
    tot = {k: round(sum(r[k] for r in table.values()), 3)
           for k in ("compiles", "compile_s", "cache_hits", "cache_misses")}
    log(f"[registry] total {tot}")
    missing = [k for k in required_kinds(dev["count"])
               if table.get(k, {}).get("compiles", 0) < 1]
    if missing:
        raise SystemExit(f"chip_smoke: registry kinds never compiled: "
                         f"{missing}")
    log(f"[done] wall_s={time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()

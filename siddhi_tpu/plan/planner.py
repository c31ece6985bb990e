"""Device/host query planner — routes each query to compiled TPU execution
or the host oracle.

This is the role the reference's QueryParser plays (util/parser/
QueryParser.java:83-249: object model → runtime graph); here the planner
additionally *chooses a backend* per query: pattern chains lower to the
batched NFA kernel (plan/nfa_compiler.py + ops/nfa.py), anything the device
path cannot express falls back to the host oracle with a recorded reason.

Engine selection:
  - `@app:engine('host'|'device'|'auto')` app annotation, else
  - env `SIDDHI_TPU_ENGINE`, else 'auto'.
  'auto'   — try the device compile; a shape the device path cannot
             express falls back to host with the reason recorded.
  'device' — device or raise (surface the incompatibility).
  'host'   — never touch the device (the conformance oracle runs this way).
Only a plan-time rejection (SiddhiAppCreationError) or a trace-time type
incompatibility routes away from the device.  A JaxRuntimeError — XLA or
Mosaic refusing to compile, the device out of memory — propagates in
every mode: it says the device path is broken, not inapplicable.
"""
from __future__ import annotations

import os
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np
from jax.errors import JaxRuntimeError

from ..query_api import StateInputStream, find_annotation
from ..query_api.definition import Attribute, AttrType, StreamDefinition
from ..query_api.expression import Variable
from ..query_api.query import OutputEventsFor
from ..utils.errors import (SiddhiAppCreationError,
                            SiddhiAppRuntimeException)
from ..core.keyfactor import (IdTable, KeyIds, KeyInterner, column_factor,
                              intern_values, memoized)
from ..core.ledger import ABSENT_COUNTERS, ON_FLUSH, ledger as _ledger
from ..core.stateschema import Keyed, persistent_schema
from ..ops.nfa import SharedPlanes
from ..parallel.shards import build_shards, resolve_shards, split_rows
from .join_compiler import CompiledKeyedJoin, compact_rows, plan_keyed_join
from .nfa_compiler import CompiledPatternNFA
from .pipeline import (PipelinedDeviceIngest, note_retire,
                       retire_after_submit, settle_inflight, stamp_submit)
from .shapes import shape_registry

ENGINE_ENV = "SIDDHI_TPU_ENGINE"
DEFAULT_SLOTS = 8
GROW_START = 8          # initial keyed-lane capacity (doubles on demand)


def initial_lanes(app, n_shards: int = 0) -> int:
    """``@app:lanes('N')`` — declared distinct-key population.  Keyed
    slabs start at the next power of two ≥ N instead of GROW_START, so a
    known-large key domain (1M keys, say) skips the
    log2(N/8) grow ladder and its per-double jit retrace.  Sharded
    runtimes split the population: each shard pre-sizes to ceil(N/S)."""
    ann = find_annotation(app.annotations, "app:lanes") or \
        find_annotation(app.annotations, "lanes")
    n = GROW_START
    if ann is not None:
        pos = ann.positional()
        n = int(pos[0] if pos else ann.get("n", GROW_START))
    if n_shards >= 2:
        n = -(-n // n_shards)
    n = max(n, GROW_START)
    return 1 << (n - 1).bit_length()


def _record_block(rt_obj, marks, stream: str, batch: int, junction=None,
                  telemetry=None) -> None:
    """Per-ingest-block accounting shared by every device runtime:
    ``marks`` is ``shape_registry().marks()`` as the ingest began, so the
    launches and scan ticks this block cost are two subtractions.  They
    go to the latency ledger with the per-app stage fold + SLO evaluation
    (core/ledger.py: the ``siddhi_app_dispatches_per_block`` gauge), and
    onto the flight-recorder ring record (core/flight.py)."""
    from ..core.flight import flight
    from ..core.ledger import ledger
    calls, ticks = shape_registry().marks()
    d = calls - marks[0]
    app = getattr(rt_obj.qr, "app_runtime", None)
    fl = flight()
    # per-block stage waterfall: bank the stage deltas since this
    # runtime's previous block for the per-app histograms, evaluate the
    # app's SLO (an SLO001 bundle fires here on sustained breach), and
    # keep the row for the flight record below (only built when the
    # flight ring will actually store it)
    led = ledger()
    ledger_row = led.note_block(rt_obj.app_name, rt_obj, runtime=app,
                                want_row=fl.enabled, dispatches=d)
    if not fl.enabled:
        return
    sched = getattr(app.app_ctx, "scheduler", None) if app is not None \
        else None
    if junction is None and app is not None:
        junction = app.junctions.get(stream)
    fuser = getattr(app, "_egress_fuser", None) if app is not None else None
    extra = ({"egress_bytes": fuser.last_slab_bytes}
             if fuser is not None and fuser.last_slab_bytes else None)
    bucket = getattr(getattr(rt_obj, "nfa", None), "_tenant_bucket", None)
    if bucket is not None:
        # per-tenant attribution for packed runtimes: which shared
        # bucket this app's blocks ride, and how many tenants co-pay
        # the gang launch (flight rows already carry the app label)
        extra = dict(extra or {}, xtenant={"bucket": bucket.label,
                                           "tenants": len(bucket.tenants)})
    fl.record_block(rt_obj.app_name, stream=stream, batch=batch,
                    dispatches=d, scan_ticks=ticks - marks[1],
                    junction=junction, scheduler=sched, telemetry=telemetry,
                    extra=extra, ledger=ledger_row)


def _lane_of(key_lanes: Dict[Any, int], key) -> int:
    """The key's lane, the next free one where it has none yet."""
    lane = key_lanes.get(key)
    if lane is None:
        lane = key_lanes[key] = len(key_lanes)
    return lane


class KeyLanes(dict):
    """key → lane map, the durable one that snapshots hold, with two
    caches beside it for steady state.

    A block whose keys its partition interned (``KeyIds``) gets its lanes
    by a gather from ``lane_of_id``, a table by key id (core/keyfactor.py
    ``IdTable``; a restore makes a new map and so a new table).  A plain
    key array (the sharded ingests) gets them, after the key population
    stops growing, by one np.searchsorted over the batch's DISTINCT keys
    — zero dict probes.  That cache (sorted key array + parallel lane
    array) is rebuilt lazily whenever the population size changed; lanes
    are append-only, so a length check is a complete staleness test."""

    __slots__ = ("_vkeys", "_vlanes", "_vn", "lane_of_id")

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._vkeys = None
        self._vlanes = None
        self._vn = -1
        self.lane_of_id = IdTable(np.int64)

    def of(self, keys: KeyIds) -> np.ndarray:
        """Every event's lane.  Keys this map has not met are admitted
        in the order the array path below hands lanes out in: of their
        strings over 64 events, of first sight up to 64."""
        return self.lane_of_id.gather(keys, self, partial(_lane_of, self),
                                      first_sight=len(keys.ids) <= 64)

    def lookup(self, uniq: np.ndarray) -> Optional[np.ndarray]:
        """Lanes for ``uniq`` (sorted distinct keys) when EVERY key is
        already mapped; None → caller falls back to the probing path
        (which admits the new keys and implicitly invalidates us)."""
        if len(self) != self._vn:
            if not self:
                return None
            ks = np.asarray(list(self.keys()))
            if ks.dtype.kind not in "USiu":
                return None        # mixed/object keys: no vector order
            order = np.argsort(ks, kind="stable")
            self._vkeys = ks[order]
            self._vlanes = np.fromiter(self.values(), np.int64,
                                       len(self))[order]
            self._vn = len(self)
        vk = self._vkeys
        if vk is None or vk.dtype.kind != uniq.dtype.kind:
            return None
        pos = np.searchsorted(vk, uniq)
        if pos.size and int(pos.max()) >= len(vk):
            return None
        if not (vk[pos] == uniq).all():
            return None
        return self._vlanes[pos]


def map_keys_to_lanes(key_lanes: Dict[Any, int], keys,
                      capacity: int, grow_fn) -> np.ndarray:
    """Assign each key a stable lane index, growing the device slab (via
    grow_fn(new_capacity)) when the key population exceeds capacity.
    ``keys`` is a block's keys as its partition executor interned them
    (core/keyfactor.py ``KeyIds``, for a ``KeyLanes``: a gather by id,
    and only keys the map has not met are looked at), or a plain
    sequence, which string AND integer keys let factor here.  Over 64
    events either way lanes are handed out in the order of the sorted
    distinct keys: one dict probe per DISTINCT key of a plain batch, and
    zero in steady state when key_lanes is a KeyLanes with a warm cache
    (one searchsorted over the distinct keys).  Up to 64 events, and
    keys with no vector order: lanes in the order of first sight."""
    if isinstance(keys, KeyIds):
        lanes = key_lanes.of(keys)
    else:
        lanes = _lanes_of_array(key_lanes, keys)
    if key_lanes and len(key_lanes) > capacity:
        cap = capacity
        while cap < len(key_lanes):
            cap *= 2
        grow_fn(cap)
    return lanes


class GroupLanes:
    """A grouped query's group tuples -> lanes, one lane per group
    (plan/gagg_compiler.py ``group_lanes``): the durable map that
    snapshots hold, ``{other columns' tuple: {key: lane}}``, with the
    caches that serve a block beside it.

    One group column is the key (the first string one, else the first):
    its values are interned as a keyed join interns its key
    (core/keyfactor.py ``KeyInterner``: one dict probe per event, ids
    that outlive the block).  The other columns are factored by their
    distinct values in the block, and the events of each distinct tuple
    of them gather their lanes by key id from that tuple's ``IdTable``,
    which admits the keys it has not met.  Lanes are dense from 0 and
    only ever handed out."""

    def __init__(self, group_attrs: List[str], key_attr: str):
        self.key_attr = key_attr
        self.rest_attrs = [a for a in group_attrs if a != key_attr]
        self.interner = KeyInterner()
        self.lanes: Dict[tuple, Dict[Optional[str], int]] = {}
        self._tables: Dict[tuple, IdTable] = {}
        self.n = 0

    def _admit(self, durable: Dict[Optional[str], int], key) -> int:
        lane = durable[key] = self.n
        self.n += 1
        return lane

    def factor(self, columns: Dict[str, Any]):
        """A block's group tuples, factored: (per event the key's id in
        ``interner``, ``NULL`` for a null key; per event the place of its
        other columns' tuple; those tuples)."""
        from ..core.keyfactor import NULL
        col = np.asarray(columns[self.key_attr])
        keys = intern_values(
            self.interner, col, None,
            lambda: [None if v is None else str(v) for v in col.tolist()])
        ids = keys.ids
        if keys.keep is not None:
            ids = np.full(len(col), NULL, np.intp)
            ids[keys.keep] = keys.ids
        place = np.zeros(len(col), np.int64)
        tuples = [()]
        for a in self.rest_attrs:
            arr = np.asarray(columns[a])
            if arr.dtype.kind in "iub" and len(arr) and \
                    arr.min() == arr.max():
                # one value in the block (a day, a region): no sort
                tuples = [t + (arr[0].item(),) for t in tuples]
                continue
            if arr.dtype.kind in "iubU":
                uniq, inv = np.unique(arr, return_inverse=True)
                vals = uniq.tolist()
            else:                       # objects: the host's own equality
                seen: Dict[Any, int] = {}
                inv = np.fromiter((seen.setdefault(v, len(seen))
                                   for v in arr.tolist()), np.int64,
                                  len(arr))
                vals = list(seen)
            combo, place = np.unique(place * len(vals) + inv.reshape(-1),
                                     return_inverse=True)
            m = len(vals)
            tuples = [tuples[c // m] + (vals[c % m],)
                      for c in combo.tolist()]
            place = place.reshape(-1)
        return keys, ids, place, tuples

    def lanes_of(self, factored) -> np.ndarray:
        """Every event's lane, new groups admitted."""
        keys, ids, place, tuples = factored
        out = np.empty(len(ids), np.int64)
        if len(tuples) == 1:
            parts = [(tuples[0], None)]
        else:
            order = np.argsort(place, kind="stable")
            cut = np.flatnonzero(np.diff(place[order])) + 1
            parts = list(zip(tuples, np.split(order, cut)))
        for rest, at in parts:
            durable = self.lanes.setdefault(rest, {})
            table = self._tables.get(rest)
            if table is None:
                table = self._tables[rest] = IdTable(np.int64)
            sub = ids if at is None else ids[at]
            null = sub < 0
            lanes = np.empty(len(sub), np.int64)
            if null.any():
                lane = durable.get(None)
                lanes[null] = self._admit(durable, None) if lane is None \
                    else lane
            some = KeyIds(self.interner, sub[~null] if null.any() else sub,
                          keys.raw_str, None, 0)
            lanes[~null] = table.gather(some, durable,
                                        partial(self._admit, durable))
            if at is None:
                out = lanes
            else:
                out[at] = lanes
        return out

    def as_state(self) -> dict:
        """The durable map as plain data, for the runtime's snapshot."""
        return {"n": self.n, "lanes": [[list(rest), dict(d)]
                                       for rest, d in self.lanes.items()]}

    def load_state(self, state: dict) -> None:
        self.n = int(state["n"])
        self.lanes = {tuple(rest): dict(d) for rest, d in state["lanes"]}
        self._tables = {}


def _lanes_of_array(key_lanes: Dict[Any, int], keys) -> np.ndarray:
    arr = np.asarray(keys)
    if arr.dtype.kind in "USiu" and len(keys) > 64:
        uniq, inv = np.unique(arr, return_inverse=True)
        lane_of = None
        if isinstance(key_lanes, KeyLanes):
            lane_of = key_lanes.lookup(uniq)
        if lane_of is None:
            lane_of = np.fromiter(map(partial(_lane_of, key_lanes),
                                      uniq.tolist()), np.int64, len(uniq))
        return lane_of[inv.reshape(-1)]
    return np.fromiter(map(partial(_lane_of, key_lanes), keys), np.int64,
                       len(keys))


def _factored_keys(executor, data, app_name: str):
    """A keyed device ingest's first step: the chunk's keys as the
    partition executor factors them (once per chunk, whichever of the
    partition's queries comes first), counted per app, and the chunk
    without its null-key events.  -> (data, key ids)"""
    kf, reused = executor.factor(data)
    led = _ledger()
    led.note_key_factor(app_name, reused)
    if not reused:
        led.note_key_intern(app_name, len(data), kf.hits)
    if kf.keep is not None:
        # one chunk of the keyed events for all the partition's queries:
        # what they factor and pack of it is shared as the chunk's is
        data = memoized(data, ("keyed", executor),
                        partial(data.mask, kf.keep))[0]
    return data, kf


def _note_pack(app_name: str, events: int, block, planes=None) -> None:
    """One dense block packed (``ops/nfa.pack_blocks``): its events and
    its P x T cells go to the app's lane-occupancy counters, and its
    planes with those of them that another query of the partition had
    made already (``planes``, a pattern handle's; a block packed in one
    piece shares none) to its plane counters.  A statically dead
    automaton packs nothing."""
    if block is not None:
        _ledger().note_pack(app_name, events, block["__valid"].size)
        _ledger().note_planes(app_name, *(planes or (len(block), 0)))


def _check_shard_count(shards, snap_shards) -> None:
    """Shard-count mismatch on restore is a routing change: key→shard
    assignment is modular in the shard count, so a snapshot taken at S
    shards only restores into S shards.  Raises the typed SC005 error
    naming expected-vs-found counts and the pinned routing digest (the
    same diagnostic the envelope verifier emits before restore_state is
    ever reached — this guard is the defense in depth for snapshots
    restored through code paths that skip the envelope)."""
    have = len(shards) if shards else 0
    want = len(snap_shards) if snap_shards else 0
    if have != want:
        from ..core.stateschema import shard_mismatch_message
        from ..utils.errors import CannotRestoreStateError
        raise CannotRestoreStateError(
            "SC005: " + shard_mismatch_message(have, want), code="SC005")


def _scan_fns(e, pred) -> bool:
    """True if any AttributeFunction node in the expression satisfies pred."""
    from ..query_api.expression import AttributeFunction
    if isinstance(e, AttributeFunction) and pred(e):
        return True
    for f in getattr(e, "__dataclass_fields__", {}):
        v = getattr(e, f)
        if isinstance(v, list):
            if any(hasattr(x, "__dataclass_fields__") and _scan_fns(x, pred)
                   for x in v):
                return True
        elif hasattr(v, "__dataclass_fields__") and _scan_fns(v, pred):
            return True
    return False


def _is_time_fn(e) -> bool:
    return (e.namespace or "") == "" and \
        e.name.lower() in ("eventtimestamp", "currenttimemillis")


def engine_mode(app) -> str:
    ann = find_annotation(app.annotations, "app:engine") or \
        find_annotation(app.annotations, "engine")
    if ann is not None:
        pos = ann.positional()
        mode = str(pos[0] if pos else ann.get("mode", "auto")).lower()
    else:
        mode = os.environ.get(ENGINE_ENV, "auto").lower()
    if mode not in ("auto", "device", "host"):
        raise SiddhiAppCreationError(f"Unknown engine mode '{mode}'")
    return mode


class _DeviceIngress:
    """Junction-side adapter: one per input stream of a device query.
    Looks like a Processor head so ProcessStreamReceiver wraps it with the
    query lock / latency tracker / debugger IN check."""

    def __init__(self, runtime: "DevicePatternRuntime", stream_code: int,
                 stream_id: str):
        self.runtime = runtime
        self.stream_code = stream_code
        self.stream_id = stream_id
        self.next = None

    def process(self, chunk):
        self.runtime.ingest(self.stream_code, self.stream_id, chunk)

    def flush(self):
        # synchronous runtimes (filter/gagg/wagg — nothing in flight)
        # have no flush; pipelined ones retire their in-flight work
        f = getattr(self.runtime, "flush", None)
        if f is not None:
            f()

    def settle(self) -> bool:
        # the junction worker's idle hook: flush's counterpart that
        # never waits for the device; -> is work still in flight
        return self.runtime.settle()


@persistent_schema(
    "keyed-pattern", version=1, schema=Keyed("nfa"),
    doc="per-key NFA lanes: one flat slab or per-shard sections keyed "
        "by the pinned FNV-1a routing")
class DevicePatternRuntime:
    """Pattern query running on the batched NFA kernel.

    Non-partitioned queries run a single lane (P=1); keyed mode (driven by
    core/partition.py) maps partition-key values to lanes of a slab that
    doubles on demand — the device replacement for the reference's per-key
    runtime clones (partition/PartitionRuntime.java:255-308).
    """

    backend = "device"

    def __init__(self, query_runtime, sis: StateInputStream, factory,
                 key_executors: Optional[Dict[str, Any]] = None,
                 n_slots: int = DEFAULT_SLOTS):
        from ..core.event import dtype_for
        from ..core.query_runtime import ProcessStreamReceiver

        qr = query_runtime
        app = qr.app_runtime
        q = qr.query
        sel = q.selector
        if sel.group_by or sel.having is not None or sel.order_by or \
                sel.limit is not None or sel.offset is not None:
            raise SiddhiAppCreationError(
                "device pattern path: group-by/having/order-by/limit are "
                "host-only")
        self.keyed = key_executors is not None
        self.key_executors = key_executors or {}
        telemetry = bool(getattr(app.app_ctx, "telemetry_enabled", False))
        # partition shard-out (round 15, parallel/shards.py): with
        # SIDDHI_TPU_SHARDS=N (N>=2) a keyed runtime splits its key space
        # over N engine clones pinned to their own devices.  The shard
        # router owns the partition axis, so mesh sharding is superseded
        # (mesh=None) for the shard set
        want_shards = resolve_shards() if self.keyed else 0
        capacity = initial_lanes(app.app, want_shards) if self.keyed else 1
        self.nfa = CompiledPatternNFA(
            app.app, n_partitions=capacity, n_slots=n_slots, query=q,
            mesh=None if want_shards >= 2 else "auto",
            telemetry=telemetry)
        self.key_lanes: Dict[Any, int] = KeyLanes()
        self.shards: Optional[List[Any]] = None
        self.shard_reason: Optional[str] = None
        if want_shards >= 2:
            # shard-eligibility gates: these features aggregate across
            # the whole key space through ONE engine's carry, so the app
            # stays monolithic (single slab) with the reason recorded —
            # surfaced by the SA080 diagnostic and partition shard_report
            if self.nfa.has_absent:
                self.shard_reason = ("absent (`not ... for`) deadline "
                                     "timers arm off one engine's carry")
            elif telemetry:
                self.shard_reason = ("on-device telemetry aggregates one "
                                     "engine's occupancy planes")
            elif self.nfa.statically_dead:
                self.shard_reason = "statically dead automaton"
        self._shard_want = want_shards
        self.qr = qr
        self._dtype_for = dtype_for
        # mesh path: host-side upper bound on the fullest lane's live
        # partials; when a chunk could overflow the slot ring, sync the
        # true count and grow.  Single-device path: sync-free
        # grow-and-replay instead (the dropped counter rides the packed
        # egress; a dropping chunk replays from the pre-chunk carry).
        # Either way the host oracle's pending lists are unbounded, so
        # drops must never lose matches.
        self._ub_active = 0
        self._dropped_seen = 0

        # output definition straight from the capture-decode plan
        # (encoded string captures decode back to STRING)
        target = getattr(q.output_stream, "target_id", "") or qr.name
        attrs = [Attribute(name, self.nfa.output_type(attr))
                 for (name, _idx, attr, _w) in self.nfa.select_outputs]
        out_def = StreamDefinition(target, attrs)
        self.head = qr._finish_device_chain(out_def, factory)
        # outputs decoding from maybe-unmatched rows (or-sides, min-0
        # kleene) can be None → those columns ride object dtype
        self._nullable_out = {name for (name, row, _a, _w)
                              in self.nfa.select_outputs
                              if row in self.nfa.nullable_rows}
        self._scheduled_deadline = -1
        self._in_timer = False
        self._shutdown = False
        # the engine's absent counters as last handed to the ledger
        self._absent_seen = np.zeros(len(ABSENT_COUNTERS), np.int64)

        # one receiver per distinct input stream, on the global junctions
        for stream_id, code in self.nfa.stream_codes.items():
            recv = ProcessStreamReceiver(
                _DeviceIngress(self, code, stream_id), qr.lock,
                app.latency_tracker_for(qr.name), qr.name, app.app_ctx)
            app.junction_of(stream_id).subscribe(recv)
            qr.receivers[stream_id] = recv

        # ingest pipelining: a chunk's handle stays in flight until its
        # result is ready (at most `depth` of them), so the step and its
        # egress read overlap later dispatches (plan/pipeline.py holds
        # the rule).  Absent patterns pipeline too (round 5): the
        # earliest pending deadline rides the egress tail, so the host
        # TIMER is scheduled off the retired carry with no extra device
        # read — in-kernel deadline passes keep deadline-vs-event
        # ordering exact for deadlines that expire during later chunks,
        # and the junction's idle settle bounds the wall-clock tail
        from .pipeline import resolve_depth, egress_fuser_for
        self._inflight: "deque" = deque()
        self.pipeline_depth = resolve_depth(
            app.app, [app.junction_of(sid)
                      for sid in self.nfa.stream_codes])
        # fused per-app egress: the NFA's compacted match buffers ride
        # the app-wide slab — one D2H per ingest block across runtimes
        self.app_name = app.name
        self.nfa.egress_fuser = egress_fuser_for(app)
        self._junctions = {sid: app.junction_of(sid)
                           for sid in self.nfa.stream_codes}
        # on-device telemetry sink (@app:statistics(telemetry='true')):
        # per-state occupancy / gate rates mirrored on /metrics
        self._telemetry_sink = getattr(app, "device_telemetry", None)
        # cross-tenant super-dispatch (plan/xtenant.py): eligible small
        # automata from DIFFERENT apps bucket by shape class and step as
        # one gang launch per bucket per block.  No-op when the
        # SIDDHI_TPU_XTENANT kill switch is off or the NFA is meshed/
        # dead/donated; with pipeline depth 0 the bucket flushes inside
        # every ingest and dispatch counts match the unpacked path.
        from .xtenant import tenant_packer
        if self._shard_want >= 2 and self.shard_reason is None:
            # fused egress concatenates buffers on ONE device; sharded
            # engines live on several, so they take the async-copy
            # egress path instead.  Shard 0 adopts the template engine
            # (pinned); siblings are fresh-state clones sharing its
            # jitted step.  Sharded NFAs never join the cross-tenant
            # packer — gang launches assume co-resident carries.
            self.nfa.egress_fuser = None
            self.shards = build_shards(self.nfa, self._shard_want)
            for sh in self.shards:
                sh.key_lanes = KeyLanes()
        else:
            tenant_packer().register(self.nfa, app=app.name, query=qr.name)

    # ------------------------------------------------------------ ingest

    @staticmethod
    def _column_factor(data, keys: Optional[KeyIds], name: str):
        """What an encoded string column of ``data`` is encoded from: the
        partition key's ids where the column is the one the key was
        taken from, as it came; else the column's factor, made once per
        chunk."""
        if keys is not None and keys.source == name and keys.raw_str:
            return keys
        return column_factor(data, name)

    def _lanes_for_keys(self, keys) -> np.ndarray:
        def grow(cap):
            # partition-axis growth invalidates the pre-carries held by
            # in-flight chunks (their P is the old width): retire them
            # first so grow-and-replay never mixes carry widths
            self.flush()
            self.nfa.grow(cap)
        return map_keys_to_lanes(self.key_lanes, keys,
                                 self.nfa.n_partitions, grow)

    def _event_cols(self, data, n: int) -> Dict[str, np.ndarray]:
        """Kernel input columns for a chunk (float32 lanes, raw string
        columns for dictionary encoding, exact-int companion lanes).
        Shared by the monolithic and sharded ingest paths — the attr
        metadata lives on the spec, identical across shard clones."""
        cols = {}
        for a in self.nfa.attr_names:
            if a in self.nfa.derived:
                # string ORDER lane: computed by dispatch_events from the
                # raw source column (passed through below)
                src = self.nfa.derived[a][0]
                cols[src] = (data.columns.get(src)
                             if data.columns.get(src) is not None
                             else np.full(n, None, object))
                continue
            if a in self.nfa.int_exact_src:
                # exact integer companion lane: split from the RAW column
                # (the base f32 cast below would round above 2^24)
                src = self.nfa.int_exact_src[a]
                raw = data.columns.get(src)
                cols[a] = self.nfa.int_exact_lane(
                    a, raw if raw is not None else np.zeros(n, np.int64))
                continue
            col = data.columns.get(a)
            if a in self.nfa.encoded_attrs:
                # raw string column — the NFA dictionary-encodes it
                cols[a] = (col if col is not None
                           else np.full(n, None, object))
            else:
                cols[a] = (np.asarray(col, np.float32) if col is not None
                           else np.zeros(n, np.float32))
        return cols

    # ------------------------------------------------------- sharded path

    def _ingest_sharded(self, stream_code: int, data, keys_arr: np.ndarray,
                        n: int) -> None:
        """Route the chunk by consistent key hash and dispatch each
        shard's sub-block on that shard's own engine/device.  One hash
        pass per batch (split_rows); per-key event order is preserved
        (row indices ascend inside each sub-block); NO collectives —
        every dispatch runs on operands committed to the shard's
        device."""
        cols = self._event_cols(data, n)
        ts_arr = np.asarray(data.timestamps, np.int64)
        for sid, rows in split_rows(keys_arr, len(self.shards)):
            sh = self.shards[sid]

            def grow(cap, sh=sh):
                # shard-local growth: only THIS engine's in-flight
                # pre-carries go stale, so only its queue is retired and
                # only its slab re-keys — sibling shards' carries are
                # untouched (tests assert object identity)
                self._flush_shard(sh)
                sh.engine.grow(cap)
                sh.grows += 1

            pids = map_keys_to_lanes(sh.key_lanes, keys_arr[rows],
                                     sh.engine.n_partitions, grow)
            sub_cols = {k: np.asarray(v)[rows] for k, v in cols.items()}
            codes = np.full(len(rows), stream_code, np.int32)
            with _ledger().span("device"):
                h = sh.engine.dispatch_events(pids, sub_cols, ts_arr[rows],
                                              stream_codes=codes,
                                              pad_t_pow2=True)
            _note_pack(self.app_name, len(rows), h["block"], h.get("planes"))
            sh.inflight.append(h)
            sh.events += len(rows)
            sh.dispatches += 1
            retire_after_submit(sh.inflight, self.pipeline_depth,
                                partial(self._retire_shard, sh))

    def _retire_shard(self, sh, cause: Optional[int] = None) -> None:
        """Per-shard twin of _retire_one: read the shard's oldest
        in-flight chunk; on slot-ring overflow rewind/grow/replay THIS
        shard only.  (A shard's handles carry no submit stamp: their
        retires are not counted, whatever the cause.)"""
        h = sh.inflight.popleft()
        eng = sh.engine
        with _ledger().span("device"):
            pids, ts, cols = eng.retire_events(h)
        dropped = eng.last_dropped_total
        if dropped > sh.dropped_seen and eng.replayable:
            pending = [h] + list(sh.inflight)
            sh.inflight.clear()
            eng.carry = h["pre_carry"]
            eng.base_ts = h["pre_base"]
            eng.grow_slots(eng.spec.n_slots * 2)
            sh.grows += 1
            for e in pending:
                while True:
                    pre_carry, pre_base = eng.carry, eng.base_ts
                    with _ledger().span("device"):
                        r = eng.replay_block(e)
                        pids, ts, cols = eng.retire_events(r)
                    if eng.last_dropped_total <= sh.dropped_seen:
                        break
                    eng.carry = pre_carry
                    eng.base_ts = pre_base
                    eng.grow_slots(eng.spec.n_slots * 2)
                    sh.grows += 1
                self._emit_columns(pids, ts, cols)
            self._note_count(eng)
            return
        sh.dropped_seen = max(dropped, sh.dropped_seen)
        self._emit_columns(pids, ts, cols)
        self._note_count(eng)

    def _flush_shard(self, sh) -> None:
        while sh.inflight:
            self._retire_shard(sh)

    def shard_stats(self) -> Optional[List[dict]]:
        if self.shards is None:
            return None
        return [sh.stats_row() for sh in self.shards]

    def ingest(self, stream_code: int, stream_id: str, chunk) -> None:
        from ..core.event import CURRENT, EventChunk
        data = chunk.only(CURRENT)
        if data.is_empty:
            return
        marks = shape_registry().marks()
        led = _ledger()
        n = len(data)
        if self.keyed:
            ex = self.key_executors.get(stream_id)
            if ex is None:
                raise SiddhiAppCreationError(
                    f"device pattern path: stream '{stream_id}' has no "
                    f"partition key executor")
            with led.span("dispatch", "keys"):
                data, keys = _factored_keys(ex, data, self.app_name)
                n = len(data)
            if n == 0:
                return
            if self.shards is not None:
                self._ingest_sharded(stream_code, data, keys.keys(), n)
                _record_block(self, marks, stream_id, n,
                              junction=self._junctions.get(stream_id))
                return
            with led.span("dispatch", "lanes"):
                pids = self._lanes_for_keys(keys)
        else:
            keys = None
            pids = np.zeros(n, np.int64)
        if self.nfa.mesh is not None:
            t_max = int(np.bincount(pids, minlength=1).max())
            if self._ub_active + t_max > self.nfa.spec.n_slots:
                actual = self.nfa.max_active_slots()
                need = actual + t_max
                if need > self.nfa.spec.n_slots:
                    self.nfa.grow_slots(1 << (need - 1).bit_length())
                self._ub_active = actual
            self._ub_active = min(self._ub_active + t_max,
                                  self.nfa.spec.n_slots)
        with led.span("dispatch", "cols"):
            cols = self._event_cols(data, n)
            ts_arr = np.asarray(data.timestamps, np.int64)
            codes = np.full(n, stream_code, np.int32)
        with led.span("device"):
            h = self.nfa.dispatch_events(
                pids, cols, ts_arr, stream_codes=codes, pad_t_pow2=True,
                factor_of=partial(self._column_factor, data, keys),
                shared=memoized(data, "planes", SharedPlanes)[0])
        _note_pack(self.app_name, n, h["block"], h.get("planes"))
        stamp_submit(h)
        self._inflight.append(h)
        # with depth 0 every chunk retires here (synchronous: matches
        # delivered before ingest returns); with depth D the chunks whose
        # result is ready retire here, in order, and D only caps how many
        # may stay in flight while the device works (≙ the ingest/compute
        # overlap of the reference's @Async disruptor junction,
        # stream/StreamJunction.java:280-316)
        retire_after_submit(self._inflight, self.pipeline_depth,
                            self._retire_one)
        tel = self.nfa.last_telemetry
        _record_block(self, marks, stream_id, n,
                      junction=self._junctions.get(stream_id),
                      telemetry=(tel.sum(axis=0) if tel is not None
                                 else None))

    def _retire_one(self, cause: int) -> None:
        """Read the oldest in-flight chunk (blocking if its result is not
        there yet), handle slot-ring overflow (grow-and-replay: restore
        that chunk's pre-carry, double the ring, replay it and every
        later in-flight chunk), decode columnar, emit."""
        h = self._inflight.popleft()
        note_retire(self.app_name, h, cause)
        with _ledger().span("device", "retire", block=h.get("seq"),
                            app=self.app_name):
            pids, ts, cols = self.nfa.retire_events(h)
        if self._telemetry_sink is not None and \
                self.nfa.last_telemetry is not None:
            self._telemetry_sink.update_nfa(
                self.qr.name, self.nfa.last_telemetry,
                len(self.nfa.spec.units),
                [u.kind for u in self.nfa.spec.units])
        dropped = self.nfa.last_dropped_total
        if dropped > self._dropped_seen and self.nfa.replayable:
            # slot overflow would LOSE matches (the oracle's pending lists
            # never drop): every chunk from this one on ran on a dropping
            # ring — rewind to this chunk's pre-carry, grow, replay all
            pending = [h] + list(self._inflight)
            self._inflight.clear()
            # packed tenant (plan/xtenant.py): later in-flight chunks may
            # still sit in the bucket queue; gang-step them NOW, before
            # the rewind.  Otherwise grow_slots' rebucket would flush
            # them onto the rewound carry AND the loop below would replay
            # them — the same block applied twice
            for e in pending:
                if "xpend" in e:
                    e["xpend"].resolve(e)
            self.nfa.carry = h["pre_carry"]
            self.nfa.base_ts = h["pre_base"]
            self.nfa.grow_slots(self.nfa.spec.n_slots * 2)
            for e in pending:
                while True:
                    pre_carry, pre_base = self.nfa.carry, self.nfa.base_ts
                    with _ledger().span("device"):
                        r = self.nfa.replay_block(e)
                        pids, ts, cols = self.nfa.retire_events(r)
                    if self.nfa.last_dropped_total <= self._dropped_seen:
                        break
                    self.nfa.carry = pre_carry
                    self.nfa.base_ts = pre_base
                    self.nfa.grow_slots(self.nfa.spec.n_slots * 2)
                self._emit_columns(pids, ts, cols)
            self._note_count(self.nfa)
            if self.nfa.has_absent:
                self._note_absent()
                self._schedule_absent(self.nfa.last_min_deadline)
            return
        self._dropped_seen = max(dropped, self._dropped_seen)
        self._emit_columns(pids, ts, cols, h.get("seq"))
        self._note_count(self.nfa)
        if self.nfa.has_absent:
            # schedule off the retired chunk's carry — the deadline and
            # the counters rode the egress tail, no extra device read
            # (see egress_dispatch)
            self._note_absent()
            self._schedule_absent(self.nfa.last_min_deadline)

    def settle(self) -> bool:
        """The junction worker's idle hook (plan/pipeline.py
        settle_inflight): launch this tenant's pending gang, then retire
        what is ready, without waiting for the device.
        -> is work still in flight"""
        if not self._inflight and not any(
                sh.inflight for sh in self.shards or ()):
            return False
        with self.qr.lock:
            busy = settle_inflight(self._inflight, self._retire_one)
            for sh in self.shards or ():
                if sh.inflight:
                    busy = settle_inflight(
                        sh.inflight, partial(self._retire_shard, sh)) or busy
            return busy

    def flush(self) -> None:
        """Retire every in-flight chunk, blocking on each: a junction's
        barrier and drain, and before any state read.  Takes the query
        lock (re-entrant) — state reads can race the junction worker's
        ingest."""
        with self.qr.lock:
            if self.shards is not None:
                for sh in self.shards:
                    self._flush_shard(sh)
            while self._inflight:
                self._retire_one(ON_FLUSH)

    def _emit_columns(self, pids, ts, cols, block=None) -> None:
        from ..core.event import EventChunk
        if not len(ts):
            return
        names = [o[0] for o in self.nfa.select_outputs]
        # no stage span here (the downstream head.process work carries
        # its own nested spans): an annotation that hands the retired
        # block's id on to them
        with _ledger().span(None, "match.scatter", block=block,
                            app=self.app_name):
            self.head.process(EventChunk.from_columns(names, ts, cols))

    def _emit(self, matches) -> None:
        from ..core.event import EventChunk
        if not matches:
            return
        names = [o[0] for o in self.nfa.select_outputs]
        out_cols: Dict[str, np.ndarray] = {}
        for (name, _idx, attr, _w) in self.nfa.select_outputs:
            vals = [m[2][name] for m in matches]
            dt = self._dtype_for(self.nfa.output_type(attr))
            if name in self._nullable_out or dt is object:
                col = np.empty(len(vals), object)
                col[:] = vals
            else:
                col = np.asarray(vals, dt)
            out_cols[name] = col
        ts = np.asarray([m[1] for m in matches], np.int64)
        self.head.process(EventChunk.from_columns(names, ts, out_cols))

    # -------------------------------------------------- absent-state timers

    def _note_count(self, eng) -> None:
        """Hand the ledger what an engine's count counters grew by."""
        if eng.has_count:
            delta = eng.take_count_delta()
            if delta.any():
                _ledger().note_count(self.app_name, delta)

    def _note_absent(self) -> None:
        """Hand the ledger what the engine's absent counters grew by."""
        cur = np.append(self.nfa.absent_counts, self.nfa.timer_rows_total)
        if (cur != self._absent_seen).any():
            _ledger().note_absent(self.app_name, cur - self._absent_seen)
            self._absent_seen = cur

    def _schedule_absent(self, dl: Optional[int] = "read") -> None:
        """Arm a host TIMER at the earliest pending `not … for t` deadline
        (≙ AbsentStreamPreStateProcessor scheduling wakeups via
        util/Scheduler.java).  Retirement passes the egress-borne value;
        start/restore/timer paths read the live carry.

        Every block carries its clock and fires, inside its own step, the
        deadlines its events have reached (ops/nfa.build_block_step).  The
        TIMER is for time this runtime's own events do not bring: a send
        on a stream the pattern does not consume, playback's idle
        heartbeat, an explicit advance, the wall clock.  Whoever moves the
        app's clock, the TIMER takes its turn behind the chunks already
        sent to this runtime (`_on_clock`), and there it finds out
        whether they brought the time themselves."""
        if dl == "read":
            dl = self.nfa.min_pending_deadline()
        if dl == self._scheduled_deadline or self._shutdown or \
                self._in_timer:
            return
        if dl is None or self._brought(dl):
            self._scheduled_deadline = -1   # a TIMER still out is stale
            return
        self._scheduled_deadline = dl
        sched = self.qr.app_runtime.app_ctx.scheduler

        def fire(now, _dl=dl):
            # (the time a send advanced to, not the app's clock: that one
            # moves before the send's chunk is in the queue)
            now = max(now, _dl, sched.advanced_to)
            for j in self._junctions.values():
                j.call_in_order(partial(self._on_clock, _dl, now))
        if dl <= sched.advanced_to:
            # playback stood there before this deadline was known
            # (another stream's send, or this one's still queued): no
            # advance would come for it
            fire(dl)
        else:
            sched.notify_at(dl, fire)

    def _brought(self, dl: int) -> bool:
        """Do this runtime's own events bring the time `dl`?  A block in
        flight carries its clock there: it fires `dl` in its own step,
        and its retire arms what is pending then."""
        return bool(self._inflight) and (self.nfa.clock or 0) >= dl

    def _on_clock(self, dl: int, now: int) -> None:
        """The app's clock passed the TIMER armed at `dl` and stood at
        `now`; called in junction order.  If this runtime's own blocks
        brought the time meanwhile: no flush, no TIMER row, no launch.
        Else step one TIMER row at `now`: it lands every due slot at its
        own deadline."""
        with self.qr.lock:
            if self._shutdown or dl != self._scheduled_deadline:
                return          # a retire has armed a later one since
            self._scheduled_deadline = -1
            if self._brought(dl):
                return
            with _ledger().span("device", "timer"):
                self._in_timer = True   # (the flush's retires arm nothing)
                try:
                    self.flush()
                    matches = self.nfa.process_timer(now)
                finally:
                    self._in_timer = False
                self._note_absent()
                self._emit(matches)
            self._schedule_absent()

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        if self.nfa.spec.lead_absent and not self.keyed:
            # the leading absent partial waits from ENGINE START
            # (reference AbsentStreamPreStateProcessor.start).  Keyed
            # lanes arm on their FIRST event instead (kernel ensure-arm)
            # — the oracle's per-key clone is created on first sight of
            # the key, so its wait starts there too
            now = self.qr.app_runtime.app_ctx.timestamp_generator \
                .current_time()
            self.nfa.arm_leading(now)
            self._schedule_absent()

    def shutdown(self) -> None:
        self.flush()
        self._shutdown = True
        # packed tenants leave their bucket on shutdown; co-tenants'
        # shared-gang state is untouched (plan/xtenant.py evict contract).
        # Sharded NFAs never registered, and evict is a no-op for them.
        from .xtenant import tenant_packer
        tenant_packer().evict(self.nfa)

    # ------------------------------------------------------------ snapshot

    def current_state(self) -> dict:
        with self.qr.lock:
            self.flush()
            if self.shards is not None:
                # shard-granular checkpoint: each slab snapshots
                # independently (keys route by the pinned FNV hash, so a
                # restored shard's keys still land on it)
                return {"shards": [{"nfa": sh.engine.current_state(),
                                    "key_lanes": dict(sh.key_lanes)}
                                   for sh in self.shards]}
            return {"nfa": self.nfa.current_state(),
                    "key_lanes": dict(self.key_lanes)}

    def restore_state(self, state: dict) -> None:
        with self.qr.lock:
            self.flush()
            snap_shards = state.get("shards")
            if snap_shards is not None or self.shards is not None:
                _check_shard_count(self.shards, snap_shards)
                for sh, s in zip(self.shards, snap_shards):
                    sh.engine.restore_state(s["nfa"])
                    sh.engine.pin_to_device(sh.device)
                    sh.key_lanes = KeyLanes(s.get("key_lanes") or {})
                    sh.dropped_seen = int(
                        np.asarray(sh.engine.carry["dropped"]).sum())
                return
            self.nfa.restore_state(state["nfa"])
            # the restored carry's lanes are only meaningful with the
            # snapshot's key→lane map; dropping it would hand restored
            # lanes of one key to fresh keys
            self.key_lanes = KeyLanes(state.get("key_lanes") or {})
            # force the overflow guard to re-sync against the restored
            # carry
            self._ub_active = self.nfa.spec.n_slots
        self._dropped_seen = int(
            np.asarray(self.nfa.carry["dropped"]).sum())
        if self.nfa.has_absent:
            self._scheduled_deadline = -1
            self._schedule_absent()


@persistent_schema(
    "keyed-window-agg", version=1, schema=Keyed("cwa"))
class DeviceWindowedAggRuntime(PipelinedDeviceIngest):
    """Partitioned length-window aggregation on the sliding-window kernel
    (ops/windowed_agg.py): partition keys become group lanes of one ring
    slab (BASELINE config 2 — the reference's per-key window buffers +
    per-group aggregator maps, QuerySelector.java:171).  Ingest is
    pipelined (round 5, plan/pipeline.py)."""

    backend = "device"

    def __init__(self, query_runtime, sis, factory,
                 key_executors: Dict[str, Any]):
        from ..core.event import dtype_for
        from ..core.query_runtime import ProcessStreamReceiver
        from .expr_compiler import ExprCompiler, Scope
        from .wagg_compiler import CompiledWindowedAgg

        qr = query_runtime
        app = qr.app_runtime
        q = qr.query
        sel = q.selector
        if sel.having is not None or sel.order_by or \
                sel.limit is not None or sel.offset is not None:
            raise SiddhiAppCreationError(
                "device wagg path: having/order-by/limit are host-only")
        if getattr(q.output_stream, "events_for",
                   OutputEventsFor.CURRENT) != OutputEventsFor.CURRENT:
            raise SiddhiAppCreationError(
                "device wagg path: expired-event output is host-only")
        # always keyed (partition-driven); shard-out splits the key space
        # over engine clones when SIDDHI_TPU_SHARDS >= 2
        self._shard_want = resolve_shards()
        self.cwa = CompiledWindowedAgg(
            app.app, n_partitions=initial_lanes(app.app, self._shard_want),
            query=q, use_pallas=False)
        # the kernel sees int32 ts offsets while the host-twin emission
        # filter sees true int64 — absolute-timestamp filters would diverge
        if any(_scan_fns(e, _is_time_fn) for e in self.cwa.filter_exprs):
            raise SiddhiAppCreationError(
                "device wagg path: timestamp functions need int64 host "
                "evaluation")
        if self.cwa.value is not None and \
                self.cwa.value.type in (AttrType.INT, AttrType.LONG):
            raise SiddhiAppCreationError(
                "device wagg path: INT/LONG aggregate values ride float32 "
                "lanes (exact integer sums need the host path)")
        ex = key_executors.get(self.cwa.stream_id)
        if ex is None:
            raise SiddhiAppCreationError(
                f"device wagg path: stream '{self.cwa.stream_id}' has no "
                f"partition key executor")
        # group-by must be the partition key itself (lanes isolate keys);
        # a finer grouping needs the host per-key selector
        pt_expr = getattr(ex, "pt", None)
        pt_expr = getattr(pt_expr, "expression", None)
        for v in sel.group_by:
            if not (isinstance(pt_expr, Variable) and
                    v.attribute == pt_expr.attribute):
                raise SiddhiAppCreationError(
                    "device wagg path: group-by must equal the partition "
                    "key")
        self.key_executor = ex
        self.qr = qr
        self.key_lanes: Dict[Any, int] = KeyLanes()
        self._dtype_for = dtype_for

        # host-side twin of the filters for emission masking (same exprs,
        # numpy backend)
        scope = Scope()
        scope.add_primary(self.cwa.stream_id, sis.stream_ref,
                          self.cwa.input_definition)
        host_compiler = ExprCompiler(scope, np)
        self._host_filters = [host_compiler.compile(e)
                              for e in self.cwa.filter_exprs]

        # output definition with host-parity types
        vt = self.cwa.value.type if self.cwa.value is not None else None
        attrs = []
        for (name, kind, attr) in self.cwa.outputs:
            if kind == "key":
                t = dict((a.name, a.type) for a in
                         self.cwa.input_definition.attributes)[attr]
            elif kind == "count":
                t = AttrType.LONG
            elif kind == "sum":
                t = (AttrType.DOUBLE if vt in (AttrType.FLOAT,
                                               AttrType.DOUBLE, None)
                     else AttrType.LONG)
            elif kind in ("min", "max"):
                t = vt if vt is not None else AttrType.DOUBLE
            else:                                  # avg
                t = AttrType.DOUBLE
            attrs.append(Attribute(name, t))
        target = getattr(q.output_stream, "target_id", "") or qr.name
        out_def = StreamDefinition(target, attrs)

        # trace the kernel BEFORE wiring the output tail (all-invalid
        # block) so unsupported expressions — e.g. string-typed filters —
        # reject at PLAN time while fallback to DeviceGroupedAggRuntime
        # is still clean: a rejected wagg must not leave an output
        # definition bound for the gagg fallback to rewire against
        # (ADVICE r3 #3)
        try:
            P = self.cwa.n_partitions
            warm = {a.name: np.zeros((P, 1), np.float32)
                    for a in self.cwa.input_definition.attributes
                    if self._dtype_for(a.type) is not object}
            warm["__ts"] = np.zeros((P, 1), np.int32)
            warm["__ts64"] = np.zeros((P, 1), np.int64)
            warm["__valid"] = np.zeros((P, 1), bool)
            self.cwa.process_block(warm)
        except (SiddhiAppCreationError, JaxRuntimeError):
            raise
        except Exception as e:
            raise SiddhiAppCreationError(
                f"device wagg path: kernel not traceable ({e})") from e
        self.head = qr._finish_device_chain(out_def, factory)

        recv = ProcessStreamReceiver(
            _DeviceIngress(self, 0, self.cwa.stream_id), qr.lock,
            app.latency_tracker_for(qr.name), qr.name, app.app_ctx)
        app.junction_of(self.cwa.stream_id).subscribe(recv)
        qr.receivers[self.cwa.stream_id] = recv
        self._init_pipeline(app, [self.cwa.stream_id])
        from .pipeline import egress_fuser_for
        self.app_name = app.name
        self._fuser = egress_fuser_for(app)
        self.shards: Optional[List[Any]] = None
        if self._shard_want >= 2:
            # fused egress concatenates on one device — sharded engines
            # span several, so each shard's outputs ride async copies.
            # Built AFTER the warm trace so every clone shares the
            # template's already-compiled step.
            self._fuser = None
            self.shards = build_shards(self.cwa, self._shard_want)
            for sh in self.shards:
                sh.key_lanes = KeyLanes()

    # ------------------------------------------------------------ ingest

    def _grow(self, cap: int) -> None:
        # lane growth re-shapes the [P, ...] blocks: retire in-flight
        # work first so replay never mixes widths
        self.flush()
        self.cwa.grow(cap)

    def ingest(self, stream_code: int, stream_id: str, chunk) -> None:
        from ..core.event import CURRENT
        from ..ops.nfa import pack_blocks
        data = chunk.only(CURRENT)
        if data.is_empty:
            return
        marks = shape_registry().marks()
        led = _ledger()
        with led.span("dispatch", "keys"):
            data, keys = _factored_keys(self.key_executor, data,
                                        self.app_name)
        if data.is_empty:
            return
        n = len(data)
        if self.shards is not None:
            self._ingest_sharded(data, keys.keys())
            _record_block(self, marks, stream_id, n)
            return
        with led.span("dispatch", "lanes"):
            lanes = map_keys_to_lanes(self.key_lanes, keys,
                                      self.cwa.n_partitions, self._grow)
        P = self.cwa.n_partitions
        with led.span("dispatch", "cols"):
            cols = {a.name: np.asarray(data.columns[a.name])
                    for a in self.cwa.input_definition.attributes
                    if a.name in data.columns and
                    data.columns[a.name].dtype != object}
            ts_arr = np.asarray(data.timestamps, np.int64)
        # this runtime packs under `dispatch`, the pattern runtime under
        # `device` (inside dispatch_events): the sub-span is named for
        # the stage it is in
        with led.span("dispatch", "pack"):
            block, rows = pack_blocks(lanes, cols, ts_arr,
                                      np.zeros(n, np.int32), P,
                                      base_ts=int(ts_arr[0]),
                                      pad_t_pow2=True, return_rows=True)
            if self.cwa.window_kind == "time":
                # absolute i64 ts lanes: the time kernel's expiry must
                # be comparable ACROSS blocks (packed __ts is per-block
                # offsets); externalTime reads the event's ts attribute
                src = (np.asarray(data.columns[self.cwa.ts_attr], np.int64)
                       if self.cwa.ts_attr else ts_arr)
                ts64 = np.zeros(block["__ts"].shape, np.int64)
                ts64[lanes, rows] = src
                block["__ts64"] = ts64
        _note_pack(self.app_name, n, block)
        with led.span("device"):
            outs = self.cwa.process_block(block)
        token = None
        if self._fuser is not None:
            # outputs ride the app's per-ingest-block slab: one shared
            # D2H at retire instead of a read per runtime
            token = self._fuser.register(self, list(outs))
        else:
            for o in outs:
                o.copy_to_host_async()
        self._submit({"outs": outs, "fuse": token, "data": data,
                      "lanes": lanes, "rows": rows})
        _record_block(self, marks, stream_id, n)

    def _ingest_sharded(self, data, keys_arr: np.ndarray) -> None:
        """Hash-route the chunk and run each shard's sub-block through
        its own window slab.  The retire path is untouched: a work item
        carries its own lanes/rows/data, and _retire never mutates
        engine state, so shard works share the pipeline queue safely."""
        from ..ops.nfa import pack_blocks
        ts_all = np.asarray(data.timestamps, np.int64)
        for sid, rows_idx in split_rows(keys_arr, len(self.shards)):
            sh = self.shards[sid]
            m = np.zeros(len(data), bool)
            m[rows_idx] = True
            sub = data.mask(m)
            n = len(sub)

            def grow(cap, sh=sh):
                # same width contract as _grow; the full flush is cheap
                # (retire only reads) and keeps one code path
                self.flush()
                sh.engine.grow(cap)
                sh.grows += 1

            lanes = map_keys_to_lanes(sh.key_lanes, keys_arr[rows_idx],
                                      sh.engine.n_partitions, grow)
            P = sh.engine.n_partitions
            cols = {a.name: np.asarray(sub.columns[a.name])
                    for a in self.cwa.input_definition.attributes
                    if a.name in sub.columns and
                    sub.columns[a.name].dtype != object}
            ts_arr = ts_all[rows_idx]
            block, rows = pack_blocks(lanes, cols, ts_arr,
                                      np.zeros(n, np.int32), P,
                                      base_ts=int(ts_arr[0]),
                                      pad_t_pow2=True, return_rows=True)
            if self.cwa.window_kind == "time":
                src = (np.asarray(sub.columns[self.cwa.ts_attr], np.int64)
                       if self.cwa.ts_attr else ts_arr)
                ts64 = np.zeros(block["__ts"].shape, np.int64)
                ts64[lanes, rows] = src
                block["__ts64"] = ts64
            _note_pack(self.app_name, n, block)
            with _ledger().span("device"):
                outs = sh.engine.process_block(block)
            for o in outs:
                o.copy_to_host_async()
            sh.events += n
            sh.dispatches += 1
            self._submit({"outs": outs, "fuse": None, "data": sub,
                          "lanes": lanes, "rows": rows})

    def shard_stats(self) -> Optional[List[dict]]:
        if self.shards is None:
            return None
        return [sh.stats_row() for sh in self.shards]

    def _retire(self, work) -> None:
        from ..core.event import EventChunk
        outs, data = work["outs"], work["data"]
        lanes, rows = work["lanes"], work["rows"]
        n = len(data)
        # fetching the step's outputs: this runtime's counterpart of the
        # pattern runtime's `device.retire`, named for the stage it is in
        # (the D2H read inside it keeps its own)
        with _ledger().span("decode", "fetch"):
            if work.get("fuse") is not None:
                outs = work["fuse"].fetch()
            else:
                with _ledger().span("egress_d2h"):
                    outs = [np.asarray(o) for o in outs]
        sums = outs[0]
        counts = outs[1]
        mins = outs[2] if len(outs) > 2 else None
        maxs = outs[3] if len(outs) > 3 else None

        # host-side twin filter decides which input events emit output rows
        from .expr_compiler import EvalCtx
        okm = np.ones(n, bool)
        ctx = EvalCtx(data.columns, data.timestamps, n)
        for f in self._host_filters:
            m = np.asarray(f.fn(ctx), bool)
            okm &= np.broadcast_to(m, okm.shape)
        if not okm.any():
            return
        sel_l = lanes[okm]
        sel_r = rows[okm]
        ev_sums = sums[sel_l, sel_r].astype(np.float64)
        ev_counts = counts[sel_l, sel_r].astype(np.int64)
        names = [o[0] for o in self.cwa.outputs]
        cols: Dict[str, np.ndarray] = {}
        for (name, kind, attr) in self.cwa.outputs:
            if kind == "key":
                cols[name] = np.asarray(data.columns[attr])[okm]
            elif kind == "sum":
                cols[name] = ev_sums
            elif kind == "count":
                cols[name] = ev_counts
            elif kind == "min":
                cols[name] = mins[sel_l, sel_r]
            elif kind == "max":
                cols[name] = maxs[sel_l, sel_r]
            else:
                with np.errstate(invalid="ignore", divide="ignore"):
                    cols[name] = np.where(ev_counts > 0,
                                          ev_sums / np.maximum(ev_counts, 1),
                                          np.nan)
        out_ts = np.asarray(data.timestamps)[okm]
        self.head.process(EventChunk.from_columns(names, out_ts, cols))

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        pass

    # ------------------------------------------------------------ snapshot

    def shutdown(self) -> None:
        self.flush()

    def current_state(self) -> dict:
        with self.qr.lock:
            self.flush()
            if self.shards is not None:
                return {"shards": [{"cwa": sh.engine.current_state(),
                                    "key_lanes": dict(sh.key_lanes)}
                                   for sh in self.shards]}
            return {"cwa": self.cwa.current_state(),
                    "key_lanes": dict(self.key_lanes)}

    def restore_state(self, state: dict) -> None:
        with self.qr.lock:
            self.flush()
            snap_shards = state.get("shards")
            if snap_shards is not None or self.shards is not None:
                _check_shard_count(self.shards, snap_shards)
                for sh, s in zip(self.shards, snap_shards):
                    sh.engine.restore_state(s["cwa"])
                    sh.engine.pin_to_device(sh.device)
                    sh.key_lanes = KeyLanes(s["key_lanes"])
                return
            self.cwa.restore_state(state["cwa"])
            self.key_lanes = KeyLanes(state["key_lanes"])


@persistent_schema(
    "keyed-join", version=1, schema=Keyed("join"),
    doc="per-key window rings of a keyed join; one slab (a join is no "
        "partition's, so it is never sharded)")
class DeviceKeyedJoinRuntime(PipelinedDeviceIngest):
    """Keyed inner window join on the ring step (ops/keyed_join.py): the
    join key's values become lanes of one slab, each windowed side a
    ring per lane, and an arriving event probes its own lane only — the
    device replacement for the reference's per-event ``find()`` over the
    opposite window (JoinProcessor.java:36-122), which core/join.py
    evaluates as an ``[n, m]`` mask over the whole window.  The select
    stays the host selector's, over the matched rows.

    Both sides of a stream joined with itself arrive in one chunk and
    are stepped in one block, in arrival order: tick order within a lane
    is arrival order, and a lane holds one key."""

    backend = "device"
    shards = None

    def __init__(self, query_runtime, jis, factory):
        from ..core.join import joined_scope
        from ..core.query_runtime import ProcessStreamReceiver
        from .expr_compiler import Scope
        from .pipeline import egress_fuser_for

        qr = query_runtime
        app = qr.app_runtime

        def kind_of(stream_id):
            for kind, has in (("table", app.has_table),
                              ("named window", app.has_named_window)):
                if has(stream_id):
                    return kind, None
            if stream_id in app.aggregations:
                return "aggregation", None
            return "stream", app.definition_of(stream_id)

        self.plan = plan = plan_keyed_join(jis, qr.query, kind_of)
        self.qr = qr
        self.app_name = app.name
        self.join = CompiledKeyedJoin(plan, initial_lanes(app.app),
                                      DEFAULT_SLOTS)
        self.key_lanes: Dict[Any, int] = KeyLanes()
        self.interner = KeyInterner()
        # per input stream: the sides its events may be on
        self._present = {
            sid: tuple(s.stream_id == sid for s in plan.sides)
            for sid in dict.fromkeys(s.stream_id for s in plan.sides)}
        # trace every stream's step before the output tail is wired, so
        # a condition jnp cannot express rejects while the fall-back to
        # core/join.py is still clean
        try:
            for here in self._present.values():
                self.join.trace(here)
        except (SiddhiAppCreationError, JaxRuntimeError):
            raise
        except Exception as e:
            raise SiddhiAppCreationError(
                f"device keyed join: step not traceable ({e})") from e

        self._filters = []
        for side in plan.sides:
            scope = Scope()
            scope.add_primary(side.stream_id, side.ref, side.definition)
            compiler = factory(scope)
            self._filters.append([compiler.compile(e)
                                  for e in side.filters])
        scope, self.union_def = joined_scope(plan.sides)
        qr._finish_chain([], scope, self.union_def, factory)
        self.head = qr._chain_head([])
        for sid in self._present:
            recv = ProcessStreamReceiver(
                _DeviceIngress(self, 0, sid), qr.lock,
                app.latency_tracker_for(qr.name), qr.name, app.app_ctx)
            app.junction_of(sid).subscribe(recv)
            qr.receivers[sid] = recv
        self._init_pipeline(app, self._present)
        self._fuser = egress_fuser_for(app)

    # ------------------------------------------------------------ ingest

    def _grow(self, cap: int) -> None:
        # lane growth re-shapes the carry: retire in-flight work first,
        # so a replay never starts from a narrower one
        self.flush()
        self.join.grow(cap)

    def _sides_of(self, data, present) -> np.ndarray:
        """Per event the sides whose filters it passes, as the step's
        bits (ops/keyed_join.LEFT | RIGHT)."""
        from .expr_compiler import EvalCtx
        n = len(data)
        ctx = EvalCtx(data.columns, data.timestamps, n)
        bits = np.zeros(n, np.int32)
        for i in (0, 1):
            if not present[i]:
                continue
            ok = np.ones(n, bool)
            for f in self._filters[i]:
                ok &= np.broadcast_to(np.asarray(f.fn(ctx), bool), ok.shape)
            bits |= ok.astype(np.int32) << i
        return bits

    def ingest(self, stream_code: int, stream_id: str, chunk) -> None:
        from ..core.event import CURRENT
        data = chunk.only(CURRENT)
        if data.is_empty:
            return
        marks = shape_registry().marks()
        led = _ledger()
        arrived = len(data)
        present = self._present[stream_id]
        with led.span("dispatch", "cols"):
            bits = self._sides_of(data, present)
            keep = bits != 0
            if not keep.all():
                data, bits = data.mask(keep), bits[keep]
        with led.span("dispatch", "keys"):
            key = self.plan.sides[present.index(True)].key
            col = data.columns[key]
            keys = intern_values(
                self.interner, np.asarray(col), key,
                lambda: [None if v is None else str(v)
                         for v in col.tolist()])
            led.note_key_factor(self.app_name, False)
            led.note_key_intern(self.app_name, len(data), keys.hits)
            if keys.keep is not None:       # a null key meets nothing
                data, bits = data.mask(keys.keep), bits[keys.keep]
        n = len(data)
        led.note_join_events(self.app_name, arrived, arrived)
        if n == 0:
            return
        with led.span("dispatch", "lanes"):
            lanes = map_keys_to_lanes(self.key_lanes, keys,
                                      self.join.n_lanes, self._grow)
        with led.span("dispatch", "cols"):
            offs = self.join.offsets(np.asarray(data.timestamps, np.int64),
                                     self.flush)
            # the events that carry a value the step reads, the values
            floats, groups = self.join.event_planes(present, data.columns,
                                                    bits)
        self._dispatch(data, lanes, offs, bits, floats, groups, present)
        _record_block(self, marks, stream_id, n)

    def _dispatch(self, data, lanes, offs, bits, floats, groups,
                  present) -> None:
        """Pack the placed events into one block, step it and put it in
        flight: the float planes ride the dense scatter of the block
        itself, each group of int planes goes up as compact rows of the
        events that carry it (the step scatters them on the device)."""
        from ..ops.nfa import pack_blocks
        led = _ledger()
        with led.span("device"):
            with led.span("device", "pack"):
                dense = {}
                for name, (at, v) in floats.items():
                    dense[name] = np.zeros(len(lanes), np.float32)
                    dense[name][at] = v
                packed, prow = pack_blocks(
                    lanes, dense, offs, bits, self.join.n_lanes,
                    pad_t_pow2=True, return_rows=True)
                P, T = packed["__ts"].shape
                block = {"ts": packed["__ts"], "side": packed["__stream"],
                         **{name: packed[name] for name in floats}}
                block["rows"] = tuple(
                    compact_rows(lanes[at], prow[at], vals, P, T)
                    for at, vals in groups)
            for (at, _v), (idx, _r) in zip(groups, block["rows"]):
                led.note_join_build(self.app_name, len(at), len(idx))
            _note_pack(self.app_name, len(lanes), packed)
            work = {"data": data, "lanes": lanes, "prow": prow,
                    "block": block, "present": present,
                    "pre": self.join.carry}
            self._step(work)
            if self._fuser is not None:
                # the rows and the tail ride the app's per-block slab
                work["fuse"] = self._fuser.register(self, work["outs"])
            else:
                for o in work["outs"]:
                    o.copy_to_host_async()
        self._submit(work)

    def _step(self, work) -> None:
        """Launch the step over ``work``'s block from the engine's carry
        and leave the un-read result on it."""
        rows, tail, cap = self.join.process_block(work["block"],
                                                  work["present"])
        T = work["block"]["ts"].shape[1]
        work.update(outs=[rows, tail], cap=cap, fuse=None,
                    shape=(T, self.join.n_slots, self.join.n_lanes))

    # ------------------------------------------------------------ retire

    def _retire(self, work) -> None:
        with _ledger().span("device", "retire"):
            if work["fuse"] is not None:
                rows, tail = work["fuse"].fetch()
            else:
                with _ledger().span("egress_d2h"):
                    rows, tail = (np.asarray(o) for o in work["outs"])
                self.join.step_for(work["present"]).entry.d2h_bytes += \
                    rows.nbytes + tail.nbytes
        if not (tail[1] or tail[0] > work["cap"]):
            self._deliver(work, rows, tail)
            return
        # a lane's ring was full of live entries, or the block's rows
        # outgrew the egress buffer: this block and every later one in
        # flight ran on from a result that is not whole.  Go back to the
        # carry this block started from, widen, and replay them in order
        pending = [work, *self._inflight]
        self._inflight.clear()
        for w in pending:
            while tail is None or tail[1] or tail[0] > w["cap"]:
                if tail is not None:
                    self.join.carry = w["pre"]
                    grown = self.join.widen(tail, w["shape"][0])
                    _ledger().note_join(self.app_name, (0,) * 5, grown)
                w["pre"] = self.join.carry
                self._step(w)
                rows, tail = (np.asarray(o) for o in w["outs"])
            self._deliver(w, rows, tail)
            tail = None

    def _deliver(self, work, rows: np.ndarray, tail: np.ndarray) -> None:
        """Hand the ledger the counters the tail carries, decode the
        block's rows, put them in arrival order and emit them."""
        from ..core.event import CURRENT, dtype_for
        from ..core.join import joined_chunk
        delta = self.join.count_delta(tail)
        if delta.any():
            _ledger().note_join(self.app_name, delta)
        count = int(tail[0])
        if count == 0:
            return
        data, plan = work["data"], self.plan
        T, _K, P = work["shape"]
        side, lane, tick, seq, entry = self.join.decode(
            rows[:count], work["shape"], work["present"])
        event_at = np.zeros(P * T, np.int32)
        event_at[work["lanes"] * T + work["prow"]] = np.arange(len(data))
        ev = event_at[lane * T + tick]
        # by probing event; a left event's rows before its right ones;
        # the matched entries in their arrival order
        order = np.lexsort((seq, side, ev))
        side, ev = side[order], ev[order]
        cols_of = []
        for i, s in enumerate(plan.sides):
            probing = side == i
            other = plan.sides[1 - i]
            cols = {}
            for a in s.definition.attributes:
                dt = dtype_for(a.type)
                col = None
                if not probing.all():       # rows this side's ring gave
                    if a.name in entry.get(i, ()):
                        col = entry[i][a.name][order]
                    elif a.name == s.key:   # equal to the probing key
                        col = data.columns[other.key][ev]
                        if dt is not object:
                            col = col.astype(dt)
                    else:                   # read by nobody
                        col = np.zeros(count, dt) if dt is not object \
                            else np.full(count, None, object)
                if probing.any():
                    mine = data.columns[a.name][ev]
                    if col is None:
                        col = mine
                    else:
                        if col.dtype != object:     # strings stay objects
                            col = col.astype(mine.dtype)
                        col[probing] = mine[probing]
                cols[a.name] = col
            cols_of.append(cols)
        with _ledger().span(None, "match.scatter", block=work.get("seq"),
                            app=self.app_name):
            self.head.process(joined_chunk(
                plan.sides, self.union_def, cols_of,
                np.asarray(data.timestamps)[ev], CURRENT))

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        pass

    def shutdown(self) -> None:
        self.flush()

    def current_state(self) -> dict:
        with self.qr.lock:
            self.flush()
            return {"join": self.join.current_state(),
                    "key_lanes": dict(self.key_lanes)}

    def restore_state(self, state: dict) -> None:
        with self.qr.lock:
            self.flush()
            self.join.restore_state(state["join"])
            self.key_lanes = KeyLanes(state["key_lanes"])


@persistent_schema(
    "keyed-grouped-agg", version=1, schema=Keyed("cga"))
class DeviceGroupedAggRuntime(PipelinedDeviceIngest):
    """Aggregation query on the grouped/running device kernel
    (plan/gagg_compiler.CompiledGroupedAgg → ops/grouped_agg): group-by
    keys finer than (or different from) the partition key, no-window
    running aggregates, minForever/maxForever, and exact INT/LONG sums.
    Keyed mode maps partition keys to lanes (like DevicePatternRuntime);
    unkeyed mode runs one lane.  Ingest is pipelined (round 5): each
    chunk's kernel step dispatches immediately, the egress read + decode
    retires when the result is ready, at most `pipeline_depth` chunks
    later (plan/pipeline.py)."""

    backend = "device"

    def __init__(self, query_runtime, sis, factory,
                 key_executors: Optional[Dict[str, Any]] = None):
        from ..core.event import dtype_for
        from ..core.query_runtime import ProcessStreamReceiver
        from ..query_api.query import OutputEventsFor
        from .gagg_compiler import CompiledGroupedAgg

        qr = query_runtime
        app = qr.app_runtime
        q = qr.query
        sel = q.selector
        # having/order-by/limit no longer reject wholesale: the gagg
        # compiler lowers expressible selection tails into a device
        # egress program (plan/select_compiler.py) and rejects — with
        # the blocking reason — only the shapes the host QuerySelector
        # must keep
        if getattr(q.output_stream, "events_for",
                   OutputEventsFor.CURRENT) != OutputEventsFor.CURRENT:
            raise SiddhiAppCreationError(
                "device grouped-agg path: expired-event output is "
                "host-only")
        if any(_scan_fns(e, _is_time_fn)
               for e in [oa.expr for oa in sel.attributes] +
               [h.expr for h in sis.handlers
                if hasattr(h, "expr")]):
            raise SiddhiAppCreationError(
                "device grouped-agg path: timestamp functions need int64 "
                "host evaluation")
        if app.has_named_window(sis.stream_id):
            raise SiddhiAppCreationError(
                "device grouped-agg path: named-window input is host-only")
        self.keyed = key_executors is not None
        self._shard_want = resolve_shards() if self.keyed else 0
        self.cga = CompiledGroupedAgg(
            app.app, q,
            n_lanes=initial_lanes(app.app, self._shard_want)
            if self.keyed else 1,
            keyed=self.keyed,
            definition=None if self.keyed else app.definition_of(
                sis.stream_id, sis.is_inner, sis.is_fault))
        # one lane per group tuple (gagg_compiler ``group_lanes``): the
        # tuples' lanes are this runtime's, and @app:lanes sizes them
        self.group_lanes: Optional[GroupLanes] = None
        if self.cga.group_lanes:
            types = {a.name: a.type
                     for a in self.cga.input_definition.attributes}
            attrs = self.cga.group_attrs
            self.group_lanes = GroupLanes(attrs, next(
                (a for a in attrs if types[a] == AttrType.STRING),
                attrs[0]))
            self.cga.grow_lanes(initial_lanes(app.app))
        # surfaced by service/rest.py stats and tools/t1_report.py: did
        # the selection tail (having/order/limit) compile to device?
        self.selection_route = None
        if self.cga.selection is not None:
            self.selection_route = {"backend": "device",
                                    "sig": self.cga.selection.key}
        if self.keyed:
            ex = key_executors.get(self.cga.stream_id)
            if ex is None:
                raise SiddhiAppCreationError(
                    f"device grouped-agg path: stream "
                    f"'{self.cga.stream_id}' has no partition key executor")
            self.key_executor = ex
        self.key_lanes: Dict[Any, int] = KeyLanes()
        self.qr = qr
        self._dtype_for = dtype_for

        attrs = [Attribute(name,
                           self.cga.output_attr_type(kind, attr))
                 for (name, kind, attr) in self.cga.outputs]
        target = getattr(q.output_stream, "target_id", "") or qr.name
        out_def = StreamDefinition(target, attrs)
        self.head = qr._finish_device_chain(out_def, factory)

        recv = ProcessStreamReceiver(
            _DeviceIngress(self, 0, self.cga.stream_id), qr.lock,
            app.latency_tracker_for(qr.name), qr.name, app.app_ctx)
        app.junction_of(self.cga.stream_id, sis.is_inner,
                        sis.is_fault).subscribe(recv)
        qr.receivers[self.cga.stream_id] = recv
        self._init_pipeline(app, [self.cga.stream_id])
        self.cga.flush_hook = self.flush
        from .pipeline import egress_fuser_for
        self.app_name = app.name
        # the compiler owns dispatch/decode, so it registers its own
        # output buffers on the app slab
        self.cga.egress_fuser = egress_fuser_for(app)
        self.shards: Optional[List[Any]] = None
        if self._shard_want >= 2:
            # per-device engines can't share the one-device egress slab;
            # clones share the template's jitted planes but own fresh
            # group dictionaries (clone_for_shard), so group ids stay
            # shard-local.  Every shard's group growth funnels through
            # the shared flush (pre-carries of in-flight works go stale)
            self.cga.egress_fuser = None
            self.shards = build_shards(self.cga, self._shard_want)
            for sh in self.shards:
                sh.key_lanes = KeyLanes()
                sh.engine.flush_hook = self.flush

    # ------------------------------------------------------------ ingest

    def _grow_lanes(self, cap: int) -> None:
        # lane growth re-shapes the [P, ...] planes: retire in-flight
        # work first so replay never mixes widths
        self.flush()
        self.cga.grow_lanes(cap)

    def ingest(self, stream_code: int, stream_id: str, chunk) -> None:
        from ..core.event import CURRENT
        data = chunk.only(CURRENT)
        if data.is_empty:
            return
        marks = shape_registry().marks()
        if self.group_lanes is not None:
            self._ingest_group_lanes(data)
            _record_block(self, marks, stream_id, len(data))
            return
        if self.keyed:
            data, keys = _factored_keys(self.key_executor, data,
                                        self.app_name)
            if data.is_empty:
                return
            if self.shards is not None:
                self._ingest_sharded(data, keys.keys())
                _record_block(self, marks, stream_id,
                              len(data))
                return
            lanes = map_keys_to_lanes(self.key_lanes, keys,
                                      self.cga.n_lanes,
                                      self._grow_lanes)
        else:
            lanes = np.zeros(len(data), np.int64)
        with _ledger().span("device"):
            work = self.cga.dispatch(lanes, data)
        if work is None:
            return
        _ledger().note_gagg(self.app_name, len(data), 0, work["cells"])
        self._submit(work)
        _record_block(self, marks, stream_id, len(data))

    def _ingest_group_lanes(self, data) -> None:
        """One lane per group tuple: the filters and value lanes first,
        then the accepted events' group tuples factored once for the
        block and gathered to lanes, then one step."""
        led = _ledger()
        cga, gl = self.cga, self.group_lanes
        with led.span("device", "encode"):
            ok, vals_f, vals_i = cga.encode(data)
            if not ok.all():
                data, vals_f, vals_i = data.mask(ok), vals_f[ok], vals_i[ok]
                ok = ok[ok]
        n = len(data)
        if n == 0:
            return
        with led.span("dispatch", "keys"):
            factored = gl.factor(data.columns)
            led.note_key_factor(self.app_name, False)
            led.note_key_intern(self.app_name, n, factored[0].hits)
        with led.span("dispatch", "lanes"):
            lanes = gl.lanes_of(factored)
            if gl.n > cga.n_lanes:
                cap = cga.n_lanes
                while cap < gl.n:
                    cap *= 2
                self._grow_lanes(cap)
        with led.span("device"):
            work = cga.dispatch(lanes, data, (ok, vals_f, vals_i))
        led.note_gagg(self.app_name, n, n, work["cells"])
        self._submit(work)

    def _ingest_sharded(self, data, keys_arr: np.ndarray) -> None:
        """Hash-route the chunk; each shard's sub-block dispatches on its
        own engine.  Works carry a "shard" tag so the retire path decodes
        (and, on overflow, rewinds/replays) against the right engine
        while sibling shards' in-flight works stay queued untouched."""
        for sid, rows in split_rows(keys_arr, len(self.shards)):
            sh = self.shards[sid]
            m = np.zeros(len(data), bool)
            m[rows] = True
            sub = data.mask(m)

            def grow(cap, sh=sh):
                self.flush()
                sh.engine.grow_lanes(cap)
                sh.grows += 1

            lanes = map_keys_to_lanes(sh.key_lanes, keys_arr[rows],
                                      sh.engine.n_lanes, grow)
            with _ledger().span("device"):
                work = sh.engine.dispatch(lanes, sub)
            sh.events += len(rows)
            if work is None:
                continue
            _ledger().note_gagg(self.app_name, len(rows), 0, work["cells"])
            sh.dispatches += 1
            work["shard"] = sh
            self._submit(work)

    def shard_stats(self) -> Optional[List[dict]]:
        if self.shards is None:
            return None
        return [sh.stats_row() for sh in self.shards]

    def _take_same_shard(self, sh) -> list:
        """Pull the failing engine's LATER in-flight works out of the
        shared queue for replay; other shards' works keep their queue
        positions (their pre-carries reference different engines and
        stay valid).  Unsharded: takes everything — the original
        behavior."""
        if sh is None:
            rest = list(self._inflight)
            self._inflight.clear()
            return rest
        mine = [w for w in self._inflight if w.get("shard") is sh]
        keep = [w for w in self._inflight if w.get("shard") is not sh]
        self._inflight.clear()
        self._inflight.extend(keep)
        return mine

    def _retire(self, work) -> None:
        from .gagg_compiler import GaggOverflow
        sh = work.get("shard")
        eng = sh.engine if sh is not None else self.cga
        try:
            res = eng.decode(work)
        except GaggOverflow:
            # a still-in-window time-ring entry was evicted: rewind to
            # this chunk's pre-carry, grow the ring, replay it and every
            # later in-flight chunk OF THIS ENGINE (exact — no
            # undercounted windows); sibling shards are untouched
            pending = [work] + self._take_same_shard(sh)
            eng.carry = work["pre_carry"]
            eng.grow_time_window()
            if sh is not None:
                sh.grows += 1
            for w in pending:
                while True:
                    eng.redispatch(w)
                    try:
                        res = eng.decode(w)
                        break
                    except GaggOverflow:
                        eng.carry = w["pre_carry"]
                        eng.grow_time_window()
                        if sh is not None:
                            sh.grows += 1
                self._emit(w, res)
            return
        except SiddhiAppRuntimeException:
            # data error (exact-sum bound, running-agg configs only — a
            # time window never trips it, so the two handlers are
            # mutually exclusive by config): drop the chunk — rewind its
            # carry, replay the LATER chunks (they are independent), and
            # re-raise at the @OnError boundary.  A replayed chunk that
            # trips the bound AGAIN (the rewind moved it closer to the
            # limit) is un-applied and dropped the same way, never left
            # half-applied
            rest = self._take_same_shard(sh)
            eng.carry = work["pre_carry"]
            for w in rest:
                eng.redispatch(w)
                try:
                    res = eng.decode(w)
                except SiddhiAppRuntimeException:
                    eng.carry = w["pre_carry"]
                    continue
                self._emit(w, res)
            raise
        self._emit(work, res)

    def _emit(self, work, res) -> None:
        from ..core.event import EventChunk
        data = work["data"]
        sel = res.pop("sel_rows", None)
        if sel is not None:
            # device selection already masked/ordered/limited the rows;
            # sel holds chunk-row indices in emission order
            if len(sel) == 0:
                return
            out_ts = np.asarray(data.timestamps)[sel]
        else:
            ok = res.pop("mask")
            out_ts = np.asarray(data.timestamps)[ok]
        names = [o[0] for o in self.cga.outputs]
        cols: Dict[str, np.ndarray] = {}
        for (name, kind, attr) in self.cga.outputs:
            dt = self._dtype_for(self.cga.output_attr_type(kind, attr))
            v = res[name]
            if dt is object:
                col = np.empty(len(v), object)
                col[:] = list(v)
                cols[name] = col
            else:
                cols[name] = np.asarray(v).astype(dt)
        self.head.process(EventChunk.from_columns(names, out_ts, cols))

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        pass

    def shutdown(self) -> None:
        self.flush()

    # ------------------------------------------------------------ snapshot

    def current_state(self) -> dict:
        with self.qr.lock:
            self.flush()
            if self.shards is not None:
                return {"shards": [{"cga": sh.engine.current_state(),
                                    "key_lanes": dict(sh.key_lanes)}
                                   for sh in self.shards]}
            state = {"cga": self.cga.current_state(),
                     "key_lanes": dict(self.key_lanes)}
            if self.group_lanes is not None:
                state["group_lanes"] = self.group_lanes.as_state()
            return state

    def restore_state(self, state: dict) -> None:
        with self.qr.lock:
            self.flush()
            snap_shards = state.get("shards")
            if snap_shards is not None or self.shards is not None:
                _check_shard_count(self.shards, snap_shards)
                for sh, s in zip(self.shards, snap_shards):
                    sh.engine.restore_state(s["cga"])
                    sh.engine.pin_to_device(sh.device)
                    sh.key_lanes = KeyLanes(s["key_lanes"])
                return
            self.cga.restore_state(state["cga"])
            self.key_lanes = KeyLanes(state["key_lanes"])
            if self.group_lanes is not None:
                self.group_lanes.load_state(state["group_lanes"])


@persistent_schema("device-filter", schema=None,
                   doc="stateless: the deferred mask read needs no "
                       "replay machinery at all")
class DeviceFilterRuntime(PipelinedDeviceIngest):
    """Stateless filter/project query as one jitted column program — the
    device replacement for the reference's per-event expression-tree DFS
    (FilterProcessor.java:55-67 + QuerySelector attribute processors).
    Ingest is pipelined (round 5, plan/pipeline.py): stateless, so the
    deferred mask read needs no replay machinery at all."""

    backend = "device"

    def __init__(self, query_runtime, sis, factory):
        import jax
        import jax.numpy as jnp
        from ..core.event import dtype_for
        from ..core.query_runtime import ProcessStreamReceiver
        from ..core.aggregator import is_aggregator
        from ..query_api import Filter
        from ..query_api.expression import AttributeFunction
        from .expr_compiler import EvalCtx, ExprCompiler, Scope

        qr = query_runtime
        app = qr.app_runtime
        q = qr.query
        sel = q.selector
        if sel.group_by or sel.having is not None or sel.order_by or \
                sel.limit is not None or sel.offset is not None:
            raise SiddhiAppCreationError(
                "device filter path: group-by/having/order-by/limit are "
                "host-only")
        if any(not isinstance(h, Filter) for h in sis.handlers):
            raise SiddhiAppCreationError(
                "device filter path: windows/stream functions are stateful")

        def is_agg(e):
            return is_aggregator(e.namespace, e.name, len(e.args))

        definition = app.definition_of(sis.stream_id, sis.is_inner,
                                       sis.is_fault)
        self.definition = definition
        numeric = {a.name for a in definition.attributes
                   if dtype_for(a.type) is not object}

        sel_attrs = sel.attributes
        if sel.select_all:            # `select *` → passthrough of all attrs
            from ..query_api.query import OutputAttribute
            from ..query_api.expression import Variable as _V
            sel_attrs = [OutputAttribute(a.name, _V(a.name))
                         for a in definition.attributes]

        # string predicates lower onto per-chunk order-preserving code
        # lanes (plan/str_lanes.py) — ==/!=/order/is-null over STRING
        # attrs evaluate ON DEVICE via integer ranks; constructs with no
        # lane form reject with the rewrite's reason
        from ..query_api.definition import AttrType as _AT
        from .str_lanes import StringLanes, StringRewriteError
        slanes = StringLanes({a.name for a in definition.attributes
                              if a.type == _AT.STRING})
        try:
            filter_exprs = [slanes.rewrite(h.expr) for h in sis.handlers]
        except StringRewriteError as se:
            raise SiddhiAppCreationError(
                f"device filter path: {se}") from se
        out_rewritten = {}
        for oa in sel_attrs:
            try:
                out_rewritten[id(oa)] = slanes.rewrite(oa.expr)
            except StringRewriteError:
                pass                  # host-expr fallback handles it
        self._slanes = slanes

        scope = Scope()
        ext_def = definition
        if slanes.any:
            from ..query_api.definition import Attribute as _A
            from ..query_api.definition import StreamDefinition as _SD
            ext_def = _SD(definition.id, list(definition.attributes) +
                          [_A(nm, _AT.FLOAT)
                           for nm in slanes.lane_names()])
        scope.add_primary(sis.stream_id, sis.stream_ref, ext_def)
        compiler = ExprCompiler(scope, jnp)
        filters = [compiler.compile(e) for e in filter_exprs]

        if any(_scan_fns(oa.expr, is_agg) for oa in sel_attrs):
            raise SiddhiAppCreationError(
                "device filter path: aggregates are stateful (host windows)")
        if any(_scan_fns(h.expr, _is_time_fn) for h in sis.handlers):
            # the device FILTER must be exact; output expressions with
            # time functions evaluate host-side below instead
            raise SiddhiAppCreationError(
                "device filter path: timestamp functions in filters need "
                "int64 host evaluation")

        # outputs: plain attribute passthroughs gather host-side by mask
        # (exact dtypes — INT/LONG would corrupt on float32 device lanes);
        # computed FLOAT/DOUBLE/BOOL outputs evaluate on device; computed
        # outputs the device cannot express exactly (STRING/OBJECT,
        # INT/LONG, timestamp functions) evaluate HOST-SIDE on the
        # device-masked rows — the hot per-event work (the filter) stays
        # on device, projection of the survivors is host gather work the
        # passthrough columns already do
        self.outputs = []      # (name, 'host_col'|'dev'|'host_expr', ref)
        dev_exprs = []
        host_exprs = []
        attrs = []
        from ..query_api.expression import Variable
        host_compiler = ExprCompiler(scope, np,
                                     app.app_ctx.script_functions,
                                     app.extension_registry)
        attr_types = {a.name: a.type for a in definition.attributes}
        for oa in sel_attrs:
            e = oa.expr
            if isinstance(e, Variable) and e.attribute in attr_types and \
                    e.stream_index is None:
                self.outputs.append((oa.rename, "host_col", e.attribute))
                attrs.append(Attribute(oa.rename, attr_types[e.attribute]))
                continue
            ce = None
            if not _scan_fns(e, _is_time_fn):
                try:
                    ce = compiler.compile(out_rewritten.get(id(oa), e))
                except Exception:       # noqa: BLE001 — host expr instead
                    ce = None
            if ce is None or dtype_for(ce.type) is object or \
                    ce.type in (AttrType.INT, AttrType.LONG):
                che = host_compiler.compile(e)
                self.outputs.append((oa.rename, "host_expr",
                                     len(host_exprs)))
                host_exprs.append(che)
                attrs.append(Attribute(oa.rename, che.type))
            else:
                self.outputs.append((oa.rename, "dev", len(dev_exprs)))
                dev_exprs.append(ce)
                attrs.append(Attribute(oa.rename, ce.type))
        if host_exprs and not filters:
            raise SiddhiAppCreationError(
                "device filter path: no filters and host-only computed "
                "outputs — nothing to run on the device")
        self._host_exprs = host_exprs
        target = getattr(q.output_stream, "target_id", "") or qr.name
        out_def = StreamDefinition(target, attrs)
        self.head = qr._finish_device_chain(out_def, factory)
        self.qr = qr
        self._dtype_for = dtype_for
        self._dev_dtypes = [dtype_for(ce.type) for ce in dev_exprs]
        self.numeric = sorted(numeric)

        def program(cols, ts, valid):
            n = ts.shape[0]
            ctx = EvalCtx(cols, ts, n)
            ok = valid
            for f in filters:
                m = jnp.asarray(f.fn(ctx), bool)
                ok = ok & jnp.broadcast_to(m, ok.shape)
            outs = [jnp.broadcast_to(jnp.asarray(ce.fn(ctx)), (n,))
                    for ce in dev_exprs]
            return ok, outs

        self._program = shape_registry().jit(
            "filter.program",
            {"filters": len(filters), "outs": len(dev_exprs),
             "lanes": len(self.numeric)},
            program)

        # trace now so incompatibilities reject at plan time
        try:
            warm_cols = {a: jnp.zeros((1,), jnp.float32)
                         for a in self.numeric}
            for nm in self._slanes.lane_names():
                warm_cols[nm] = jnp.zeros((1,), jnp.float32)
            self._program(warm_cols, jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1,), bool))
        except (SiddhiAppCreationError, JaxRuntimeError):
            raise
        except Exception as e:
            raise SiddhiAppCreationError(
                f"device filter path: program not traceable ({e})") from e

        recv = ProcessStreamReceiver(
            _DeviceIngress(self, 0, sis.stream_id), qr.lock,
            app.latency_tracker_for(qr.name), qr.name, app.app_ctx)
        if app.has_named_window(sis.stream_id):
            raise SiddhiAppCreationError(
                "device filter path: named-window input is host-only")
        app.junction_of(sis.stream_id, sis.is_inner,
                        sis.is_fault).subscribe(recv)
        qr.receivers[sis.stream_id] = recv
        self._init_pipeline(app, [sis.stream_id])
        from .pipeline import egress_fuser_for
        self.app_name = app.name
        self._fuser = egress_fuser_for(app)

    # ------------------------------------------------------------ ingest

    def ingest(self, stream_code: int, stream_id: str, chunk) -> None:
        import jax.numpy as jnp
        n = len(chunk)
        if n == 0:
            return
        marks = shape_registry().marks()
        n_pad = 1 << (n - 1).bit_length()
        cols = {}
        for a in self.numeric:
            col = chunk.columns.get(a)
            arr = np.zeros(n_pad, np.float32)
            if col is not None:
                arr[:n] = np.asarray(col, np.float32)
            cols[a] = jnp.asarray(arr)
        if self._slanes.any:
            for nm, lane in self._slanes.encode(chunk.columns, n,
                                                n_pad).items():
                cols[nm] = jnp.asarray(lane)
        # int32 ts offsets — absolute-timestamp functions are planner-
        # rejected on this path, nothing else reads ctx.timestamps
        ts = np.zeros(n_pad, np.int32)
        ts_arr = np.asarray(chunk.timestamps)
        ts[:n] = (ts_arr - ts_arr[0]).astype(np.int32)
        valid = np.zeros(n_pad, bool)
        valid[:n] = True
        with _ledger().span("device"):
            ok, outs = self._program(cols, jnp.asarray(ts),
                                     jnp.asarray(valid))
        token = None
        if self._fuser is not None:
            # mask + device columns ride the app's per-ingest-block slab
            token = self._fuser.register(self, [ok] + list(outs))
        else:
            for o in [ok] + list(outs):
                o.copy_to_host_async()
        self._submit({"ok": ok, "outs": outs, "fuse": token,
                      "chunk": chunk, "n": n})
        _record_block(self, marks, stream_id, n)

    def _retire(self, work) -> None:
        from ..core.event import TIMER, RESET, EventChunk
        chunk, n, outs = work["chunk"], work["n"], work["outs"]
        if work.get("fuse") is not None:
            fetched = work["fuse"].fetch()
            ok = fetched[0][:n]
            outs = fetched[1:]
        else:
            with _ledger().span("egress_d2h"):
                ok = np.asarray(work["ok"])[:n]
                outs = [np.asarray(o) for o in outs]
            self._program.entry.d2h_bytes += ok.nbytes + sum(
                o.nbytes for o in outs)
        # TIMER/RESET rows always pass (host FilterProcessor parity)
        ok = ok | (chunk.types == TIMER) | (chunk.types == RESET)
        if not ok.any():
            return
        hctx = None
        if self._host_exprs:
            from .expr_compiler import EvalCtx
            masked = chunk.mask(ok)
            hctx = EvalCtx(masked.columns, masked.timestamps, len(masked))
        out_cols: Dict[str, np.ndarray] = {}
        for (name, kind, ref) in self.outputs:
            if kind == "host_col":
                out_cols[name] = np.asarray(chunk.columns[ref])[ok]
            elif kind == "host_expr":
                v = np.asarray(self._host_exprs[ref].fn(hctx))
                if v.ndim == 0:
                    v = np.broadcast_to(v, (hctx.n,))
                out_cols[name] = v
            else:
                arr = np.asarray(outs[ref])[:n][ok]
                out_cols[name] = arr.astype(self._dev_dtypes[ref])
        out = EventChunk.from_columns(
            [o[0] for o in self.outputs],
            np.asarray(chunk.timestamps)[ok], out_cols,
            types=chunk.types[ok])
        with _ledger().span(None, "match.scatter"):
            self.head.process(out)

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        pass

    def shutdown(self) -> None:
        self.flush()

    def current_state(self):
        self.flush()
        return None

    def restore_state(self, state):
        pass


def _plan(query_runtime, build):
    """Shared try-compile: (runtime, reason) where exactly one side is None.
    'host' mode short-circuits; 'device' mode re-raises the incompatibility
    instead of falling back."""
    app = query_runtime.app_runtime
    mode = engine_mode(app.app)
    if mode == "host":
        return None, "engine mode 'host'"
    try:
        return build(), None
    except SiddhiAppCreationError as e:
        if mode == "device":
            raise
        return None, str(e)


def plan_state_runtime(query_runtime, sis: StateInputStream, factory):
    """Device pattern compile.  (The keyed partition path constructs
    DevicePatternRuntime directly — a host fallback at the query level
    would wire an unpartitioned runtime.)"""
    return _plan(query_runtime,
                 lambda: DevicePatternRuntime(query_runtime, sis, factory))


def plan_join_runtime(query_runtime, jis, factory):
    """The keyed device runtime for a join it takes, else the reason
    core/join.py keeps the query.  No engine mode raises here: the host
    join runtime's mask probe is a device path of its own, and 'device'
    mode is held to that one as before."""
    if engine_mode(query_runtime.app_runtime.app) == "host":
        return None, "device keyed join: engine mode 'host'"
    try:
        return DeviceKeyedJoinRuntime(query_runtime, jis, factory), None
    except SiddhiAppCreationError as e:
        return None, str(e)


def plan_single_runtime(query_runtime, sis, factory):
    """Device compile for a single-stream query: aggregation/window shapes
    go to the grouped-agg kernel, stateless filter/project to the jitted
    column program."""
    from ..core.aggregator import is_aggregator
    from ..query_api import WindowHandler

    def is_agg(e):
        return is_aggregator(e.namespace, e.name, len(e.args))

    q = query_runtime.query
    has_window = any(isinstance(h, WindowHandler) for h in sis.handlers)
    has_agg = any(_scan_fns(oa.expr, is_agg)
                  for oa in q.selector.attributes) or \
        (q.selector.having is not None and
         _scan_fns(q.selector.having, is_agg))
    if has_window and not has_agg and not q.selector.group_by:
        # plain projection over a window: the dwin hybrid (device window
        # state, host selector) owns this shape — routing it to the
        # grouped-agg kernel would reject ("no aggregates"), and under
        # engine('device') that rejection must not veto the dwin path
        return None, "window with plain projection → dwin hybrid path"
    if has_window or has_agg or q.selector.group_by:
        return _plan(query_runtime,
                     lambda: DeviceGroupedAggRuntime(query_runtime, sis,
                                                     factory))
    return _plan(query_runtime,
                 lambda: DeviceFilterRuntime(query_runtime, sis, factory))

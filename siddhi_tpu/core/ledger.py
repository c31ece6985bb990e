"""Per-chunk latency ledger, event-time lag watermarks and the SLO engine.

One end-to-end latency number carries no stage attribution.  This module
keeps an always-on, kill-switchable, stage-bucketed wall-clock ledger
over the whole ingest→publish path:

  ingress     input-handler admit (validate/encode, before junction.send)
  queue       @Async buffer wait (enqueue → worker dequeue; 0 when sync)
  dispatch    junction fan-out + host-side query processing not otherwise
              attributed (exclusive of the nested stages below)
  device      device step issue + blocking retire waits (NFA dispatch,
              retire_events, window/group process_block, filter program)
  egress_d2h  the fused egress slab's single device→host read
  decode      columnar slab decode back into EventChunks
  publish     terminal callback / sink delivery

Stages are recorded through nest-aware spans: a span's *exclusive* time
(elapsed minus enclosed child spans) goes to its stage, so the per-stage
sums reconcile against an independently measured end-to-end wall clock
without double counting.  The span is the program's ONE span source, on
two clocks at once: a span with a ``name`` also credits its exclusive
time to the sub-span accumulator ``"<stage>.<name>"``
(:data:`SPAN_NAMES`) and records it, one entry per execution, in a
per-app histogram under that key; and while a ``jax.profiler`` session
runs every span is a
``TraceAnnotation("siddhi/<stage>[.<name>]", block=<seq>)``, so that it
lies in the host plane of the same ``.xplane.pb`` as the device's ops, on
the thread that ran it.  ``block`` is the junction's dequeue sequence
number of the delivered chunk: it joins a block's spans across the send
that dispatched it and the later send that retired it.  A span with a
name and no stage credits no stage and only annotates
(:data:`ANNOTATIONS`; ``device.issue`` alone among them is declared, and
keeps its elapsed time under its key).  When ``tracing='true'`` the same
spans feed the operator's Chrome-trace exporter (core/tracing.py).
The waits of a block in flight (:data:`WAITS`) are histograms only: they
credit no stage.  A recorded span (a profiler session or the exporter is
on) of one of :data:`ONCPU_KEYS` also reads the thread's CPU clock just
outside its two wall stamps: its exclusive CPU time, by the same rules
as its wall time, goes beside its exclusive wall time to a pair of
accumulators per key (:meth:`LatencyLedger.oncpu_seconds`).  Nobody
recording, no span reads that clock.  Per-block deltas are folded into
per-app/per-stage HDR histograms (PR 1 machinery) and a ``ledger``
waterfall row on each flight ring record — same global-accumulator-delta
convention as the ring's existing rim/kernel ms split.

On top of the ledger:

  * event-time lag watermarks: per-(app, stream) gauges of admitted-event
    timestamps vs the wall/playback clock
    (``siddhi_event_time_lag_ms`` / ``siddhi_processing_lag_ms``);
  * an SLO engine: ``@app:slo(latency.p99.ms=..., lag.ms=...)`` targets,
    per-app burn-rate gauges, ``/health`` degradation on sustained breach
    and an ``SLO001`` incident bundle through the flight bus carrying the
    breaching window's waterfall.

Always-on with a ``SIDDHI_TPU_LEDGER=0`` kill switch; the env is re-read
per call, so it can be toggled per block.  It is not gated on
``@app:statistics``.
"""
from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

from collections import deque
from operator import itemgetter, sub as _sub
from typing import Any, Dict, List, Optional

from .hotpath import hot_path
from .statistics import Histogram
from .tracing import tracer as _chrome_sink

LEDGER_ENV = "SIDDHI_TPU_LEDGER"

#: stage keys in pipeline order (waterfall rows and /stats render in this
#: order; see module docstring for the boundary definitions)
STAGES = ("ingress", "queue", "dispatch", "device", "egress_d2h",
          "decode", "publish")

_stage_values = itemgetter(*STAGES)     # the seven accumulators at once

#: the named sub-spans the program declares, ``"<stage>.<name>"``: each
#: has an accumulator beside the seven stages in :meth:`stage_ns` and a
#: per-app histogram beside them in ``snapshot()["apps"][app]
#: ["stages_ms"]``.  A span under another name raises KeyError, so a
#: renamed span fails a test (benchmark/tests/test_setup_reader.py holds
#: every metric file to this list), not a run.  What each one wraps:
#:
#:   dispatch.keys    the partition-key executor's ids of the chunk's
#:                    keys (core/keyfactor.py): one probe per event of
#:                    the partition's interner, a block's new keys
#:                    appended to it; made by the first query of a
#:                    partition that meets the chunk, found on it by the
#:                    others
#:   dispatch.lanes   key -> lane map (``map_keys_to_lanes``): a gather
#:                    from the runtime's table by key id, and lanes for
#:                    the keys the runtime has not met
#:   dispatch.cols    kernel input columns (``_event_cols``)
#:   dispatch.pack    the windowed-agg runtime's ``pack_blocks`` (it sits
#:                    under ``dispatch`` there, and stage membership is
#:                    not this list's to change)
#:   device.encode    string dictionary encoding (the key's own column:
#:                    a gather from the automaton's table by key id;
#:                    another string column: per distinct value, then a
#:                    gather) / derived lanes
#:   device.pack      the NFA's dense ``[P, T]`` scatter (``pack_blocks``)
#:   device.sync      a gang bucket's flush, up to and after the gang call
#:   device.issue     one registry-jitted call (``RegisteredJit.__call__``,
#:                    annotated ``siddhi/device.issue/<registry kind>``).
#:                    Opened without a stage, wherever the call is made
#:                    from: its time stays with the stage that called it
#:   device.retire    a pattern retire's part under ``device``: what
#:                    ``retire_events`` does besides the gang flush and the
#:                    D2H read (re-pack on overflow, compact-row decode)
#:   decode.fetch     the windowed-agg runtime's fetch of its step's
#:                    outputs from the fused slab, under ``decode`` there
#:   device.timer     a host TIMER of the device pattern runtime: the
#:                    flush of its in-flight queue and the TIMER block's
#:                    step, for time that the runtime's own events did
#:                    not bring (the wall clock, playback's idle
#:                    heartbeat, a send on a stream the pattern does not
#:                    read; never a send on one it reads)
SPAN_NAMES = ("dispatch.keys", "dispatch.lanes", "dispatch.cols",
              "dispatch.pack", "device.encode", "device.pack", "device.sync",
              "device.issue", "device.retire", "decode.fetch",
              "device.timer")

#: spans without a stage that only annotate (no accumulator; nothing at
#: all unless a profiler session or the operator's exporter records):
#: ``queue.idle`` the junction worker's blocking ``q.get``, ``deliver``
#: one dequeued block's whole delivery, ``ingest.chunk`` a send,
#: ``egress_d2h.seal`` the fused slab's eager concatenate/bitcasts,
#: ``match.scatter`` a retire's emit, and app creation's ``parse``,
#: ``analyze``, ``plan``, ``plan.verify``, ``schema``, ``numeric``
ANNOTATIONS = ("queue.idle", "deliver", "ingest.chunk", "egress_d2h.seal",
               "match.scatter", "parse", "analyze", "plan", "plan.verify",
               "schema", "numeric")

#: waits of one block in flight, per-app histograms only (no stage):
#: ``wait.defer`` submit -> its step actually launched (0 for a step
#: issued at once; a gang tenant waits for its bucket's flush);
#: ``wait.inflight`` submit -> the start of its retire
WAITS = ("wait.defer", "wait.inflight")

#: the declared keys whose recorded spans also read the thread's CPU
#: clock, and keep their exclusive wall and CPU ns in a pair of
#: accumulators (:meth:`LatencyLedger.oncpu_seconds`): a block's packing
#: on the host and its launch.  The stage and keyed spans inside them
#: read it too, to keep the pairs exclusive; no other span does unless
#: the operator's exporter is on.  A read is a system call, which a
#: sandboxed kernel can make cost microseconds: hence so few keys
ONCPU_KEYS = ("dispatch.keys", "dispatch.lanes", "dispatch.cols",
              "device.encode", "device.pack", "device.sync", "device.issue")

#: the per-app retire counters, in the order of a ``_retires`` row.  The
#: first two: was the result there when the retire began.  The last
#: three: what made the block leave its queue (plan/pipeline.py) — a
#: check that does not wait found its result ready (a submit's, or the
#: junction worker's idle hook); a submit found the queue over its cap
#: and blocked on the oldest; a blocking ``flush()``.  A cause is its
#: counter's place in the row.
RETIRE_COUNTERS = ("retire_ready_total", "retire_blocked_total",
                   "retire_on_ready_total", "retire_on_depth_total",
                   "retire_on_flush_total")
ON_READY, ON_DEPTH, ON_FLUSH = 2, 3, 4

#: the per-app counters of `not … for t` deadlines on the device path, in
#: the order of an ``_absent`` row: slots that entered an absent unit;
#: deadlines that fired; of those, the ones fired by an event block's own
#: clock; slots killed by an arrival on the `not` stream (the first four
#: are ops/nfa.ABSENT_CTR, counted on the device and read off the
#: egress tail); TIMER rows stepped by host TIMERs
ABSENT_COUNTERS = ("absent_armed_total", "absent_fired_total",
                   "absent_fired_inblock_total", "absent_killed_total",
                   "absent_timer_rows_total")

#: the per-app counters of the keyed device runtimes' ingests, in the
#: order of a ``_keyfac`` row: ingests that asked their partition
#: executor for a block's factored keys (core/keyfactor.py); of those,
#: the ones that found it made by an earlier query of the partition
KEY_FACTOR_COUNTERS = ("key_factor_total", "key_factor_reused_total")

#: the per-app counters of the partitions' key interners
#: (core/keyfactor.py), in the order of a ``_keyint`` row: events of the
#: blocks whose keys were interned for a keyed device ingest (once per
#: block, not once per query); of those, the events whose id the
#: per-event dict probe gave: neither a key new to the partition nor a
#: block that went the per-distinct way (typed integers, floats, mixed
#: objects)
KEY_INTERN_COUNTERS = ("key_intern_events_total", "key_intern_hits_total")

#: the per-app counters of kleene `<m:n>` units on the device path, in the
#: order of a ``_count`` row (ops/nfa.COUNT_CTR, counted on the device and
#: read off the egress tail as the absent unit's are): chains started;
#: events appended to a chain (once per chain that takes the event);
#: chains that reached `m` and opened the next unit; chains that reached
#: `n` and stopped absorbing
COUNT_COUNTERS = ("count_armed_total", "count_appended_total",
                  "count_forwarded_total", "count_frozen_total")

#: the per-app counters of the keyed device runtimes' dense blocks
#: (``ops/nfa.pack_blocks``), in the order of a ``_pack`` row: events
#: placed, and the P x T cells of the blocks they were placed in (lanes
#: times the block's depth, the fullest key's events rounded up to a
#: power of two): their ratio is how full the blocks are
PACK_COUNTERS = ("pack_events_total", "pack_cells_total")

#: the per-app counters of those blocks' planes, in the order of a
#: ``_planes`` row: ``[P, T]`` planes placed in blocks handed to a step,
#: per query and chunk; of those, the planes that another query of the
#: partition had already made of the chunk (``ops/nfa.SharedPlanes``:
#: scattered once, and uploaded once by the gang, plan/xtenant.py)
PLANE_COUNTERS = ("pack_planes_total", "pack_planes_shared_total")

#: the per-app counters of joins, in the order of a ``_join`` row.  The
#: first five are ops/keyed_join.JOIN_CTR, counted on the device by the
#: keyed join step and read off the egress tail as the count unit's
#: are: probing events that met a windowed side; those of them that
#: found a row; rows; events that entered a window ring; entries
#: expired (at the next event of their key).  Then: ring doublings (a
#: lane's ring was full of live entries: the block is replayed, nothing
#: is dropped); events that reached a join query, on either path; of
#: those, the ones that reached a keyed device join runtime (which
#: places in its blocks the ones that pass a side's filter); last, the
#: build side's events uploaded as compact rows, once per group of int
#: planes and block, and the rows of those uploads, padding included
#: (their ratio is how full the uploads are)
JOIN_COUNTERS = ("join_probes_total", "join_probe_hits_total",
                 "join_rows_total", "join_inserted_total",
                 "join_expired_total", "join_ring_grown_total",
                 "join_events_total", "join_device_events_total",
                 "join_build_rows_total", "join_build_slots_total")

#: the per-app counters of the grouped-aggregation device runtime, in the
#: order of a ``_gagg`` row: events stepped (placed in a block); of
#: those, the events stepped on group lanes (one lane per group tuple,
#: plan/gagg_compiler.py ``group_lanes``); the ``[P, T]`` cells of the
#: blocks stepped (lanes times the block's depth): events over cells is
#: how full the blocks are
GAGG_COUNTERS = ("gagg_events_total", "gagg_lane_events_total",
                 "gagg_cells_total")


# os.environ.get pays ~0.9 us per call (key encode + value decode);
# the ledger asks "am I on?" ~10x per ingest block, so that alone would
# eat a fifth of the < 5% overhead budget.  os._Environ keeps the live
# mapping in ``_data`` (mutated in place by os.environ[...] = ..., so
# per-block toggling still works); reading it directly is a plain dict
# get.  Fall back to the public API if the internals ever move.
_ENV_DATA = getattr(os.environ, "_data", None)
_LEDGER_KEY = (os.environ.encodekey(LEDGER_ENV)
               if _ENV_DATA is not None and hasattr(os.environ, "encodekey")
               else LEDGER_ENV)
if _ENV_DATA is not None and _LEDGER_KEY not in _ENV_DATA and \
        LEDGER_ENV in os.environ:
    _ENV_DATA = None        # key codec mismatch: use the public API

_PARSED: Dict[Any, bool] = {}       # raw env value -> parsed verdict


def ledger_enabled() -> bool:
    """Kill switch, re-read per call (same contract as flight_enabled):
    ``SIDDHI_TPU_LEDGER=0`` disables every stamp mid-process."""
    if _ENV_DATA is not None:
        raw = _ENV_DATA.get(_LEDGER_KEY)
    else:
        raw = os.environ.get(LEDGER_ENV)
    if raw is None:
        return True
    v = _PARSED.get(raw)
    if v is None:
        s = os.fsdecode(raw) if isinstance(raw, bytes) else raw
        v = s.strip().lower() not in ("0", "false", "off", "no")
        _PARSED[raw] = v
    return v


# --------------------------------------------------------------- SLO config


class SloConfig:
    """Targets from ``@app:slo(...)``, parsed tolerantly like the @Async
    overload options (bad values clamp to defaults with a log warning;
    the analyzer's SA07x diagnostics are where the author learns why)."""

    __slots__ = ("latency_p99_ms", "lag_ms", "window_blocks",
                 "breach_blocks")

    def __init__(self, latency_p99_ms: Optional[float] = None,
                 lag_ms: Optional[float] = None,
                 window_blocks: int = 128, breach_blocks: int = 3):
        if latency_p99_ms is not None and latency_p99_ms <= 0:
            latency_p99_ms = None
        if lag_ms is not None and lag_ms <= 0:
            lag_ms = None
        self.latency_p99_ms = latency_p99_ms
        self.lag_ms = lag_ms
        self.window_blocks = max(4, int(window_blocks))
        self.breach_blocks = max(1, int(breach_blocks))

    @staticmethod
    def from_annotation(ann) -> "SloConfig":
        def num(key, default):
            raw = ann.get(key, None)
            if raw is None:
                return default
            try:
                return float(raw)
            except (TypeError, ValueError):
                return default      # malformed: analyzer diagnostic SA070
        wb = num("window.blocks", 128.0)
        bb = num("breach.blocks", 3.0)
        return SloConfig(
            latency_p99_ms=num("latency.p99.ms", None),
            lag_ms=num("lag.ms", None),
            window_blocks=int(wb) if wb and wb > 0 else 128,
            breach_blocks=int(bb) if bb and bb > 0 else 3)

    def as_dict(self) -> Dict[str, Any]:
        return {"latency.p99.ms": self.latency_p99_ms,
                "lag.ms": self.lag_ms,
                "window.blocks": self.window_blocks,
                "breach.blocks": self.breach_blocks}


class _SloState:
    """Rolling evaluation state for one app's SLO.  A breach needs
    ``breach_blocks`` CONSECUTIVE over-target evaluations — one slow
    block is tail, a run of them is an incident (same philosophy as the
    dispatch-storm watchdog's sustained-window trip)."""

    __slots__ = ("config", "window", "consecutive", "breached",
                 "breach_total", "burn_latency", "burn_lag",
                 "observed_p99_ms", "blocks")

    def __init__(self, config: SloConfig):
        self.config = config
        self.window: "deque" = deque(maxlen=config.window_blocks)
        self.consecutive = 0
        self.breached = False
        self.breach_total = 0
        self.burn_latency = 0.0
        self.burn_lag = 0.0
        self.observed_p99_ms = 0.0
        self.blocks = 0

    def observe(self, total_ms: Optional[float],
                lag_ms: Optional[float]) -> bool:
        """One evaluation; returns True exactly on the transition into
        breach (the caller emits the SLO001 bundle then, once)."""
        cfg = self.config
        if total_ms is not None:
            self.window.append(total_ms)
            self.blocks += 1
        if cfg.latency_p99_ms and len(self.window) >= 4:
            ordered = sorted(self.window)
            self.observed_p99_ms = ordered[
                min(len(ordered) - 1, int(0.99 * len(ordered)))]
            self.burn_latency = self.observed_p99_ms / cfg.latency_p99_ms
        if cfg.lag_ms and lag_ms is not None:
            self.burn_lag = max(0.0, lag_ms) / cfg.lag_ms
        burn = max(self.burn_latency, self.burn_lag)
        if burn > 1.0:
            self.consecutive += 1
        else:
            self.consecutive = 0
            self.breached = False       # sustained recovery clears it
        if self.consecutive >= cfg.breach_blocks and not self.breached:
            self.breached = True
            self.breach_total += 1
            return True
        return False

    def as_dict(self) -> Dict[str, Any]:
        return {"config": self.config.as_dict(),
                "burn_rate": {"latency_p99": round(self.burn_latency, 4),
                              "lag": round(self.burn_lag, 4)},
                "observed_p99_ms": round(self.observed_p99_ms, 3),
                "window_blocks_observed": len(self.window),
                "consecutive_over_target": self.consecutive,
                "breached": self.breached,
                "breach_total": self.breach_total}


# ------------------------------------------------------------------ spans


_pcns = time.perf_counter_ns
# the thread's CPU clock: read only by a recorded span
_tns = time.thread_time_ns

# jax.profiler.TraceAnnotation, bound on the first span after jax was
# imported: this module stays importable (and the analyzer's CLI stays
# runnable) without jax, and with no jax in the process there is no
# profiler session a span could lie in
_TA = None


def _ta_session() -> bool:
    """Is a profiler session recording host events?  This body runs only
    until jax is imported: it then rebinds its own name to TraceMe's
    test."""
    global _TA, _ta_session
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _TA = TraceAnnotation
    _ta_session = TraceAnnotation.is_enabled
    return _ta_session()


_SINK = _chrome_sink()


def _names_of(stage: Optional[str], name: Optional[str]) -> tuple:
    """-> (accumulator key or None, annotation name).  ``name`` may carry
    a ``/<detail>`` that only the annotation shows (``device.issue/
    <registry kind>``): the key is what stands before it."""
    if name is None:
        return None, f"siddhi/{stage}"
    base = name.split("/", 1)[0]
    if stage is not None:
        key = f"{stage}.{base}"
        if key not in SPAN_NAMES:
            raise KeyError(
                f"ledger span {key!r} is not declared in SPAN_NAMES")
        return key, f"siddhi/{stage}.{name}"
    # without a stage only a declared name keeps an accumulator
    if base in SPAN_NAMES:
        return base, f"siddhi/{name}"
    if base not in ANNOTATIONS:
        raise KeyError(f"ledger span {base!r} is not in ANNOTATIONS")
    return None, f"siddhi/{name}"


def _sink_event(name: str, cat: str, t0: int, dur: Optional[int],
                args: Optional[Dict[str, Any]]) -> None:
    """One Chrome trace event into the operator's exporter, stamped with
    this module's clock (``dur`` None: an instant)."""
    ev = {"name": name, "cat": cat, "ts": t0 / 1e3, "pid": 0,
          "tid": threading.get_ident()}
    if dur is None:
        ev.update(ph="i", s="t")
    else:
        ev.update(ph="X", dur=dur / 1e3)
    if args:
        ev["args"] = args
    _SINK.add(ev)


# what span() hands out for an annotation nobody is recording
_NO_SPAN = contextlib.nullcontext()

class _Span:
    """Nest-aware span.  With a stage: on exit its EXCLUSIVE time
    (elapsed minus enclosed stage spans on this thread) is credited to
    its stage, and its full elapsed time is charged to the parent's
    child accumulator, so ``sum(stage_ns)`` over the seven stages of a
    fully-spanned path equals the wall clock once, not once per nesting
    level.  With a name as well, that time less the stage-less named
    spans inside goes to ``"<stage>.<name>"`` and the app's histogram of
    that key: sub-spans are exclusive of one another too.

    Without a stage it touches no stage's books: what the spans inside
    it took it hands up to the span around it, and its own time stays
    with the stage that called it, whichever that is.  A declared name
    keeps its whole elapsed time under its key (``device.issue``: a leaf).

    The hot path runs cold-cache right next to device dispatches, where
    every attribute chase costs real time — a span's state is one plain
    list ``[child_ns, named_ns, block, app, rec, t0, annotation,
    child_cpu, named_cpu, c0]`` on a thread-local stack, so the span
    object itself holds nothing of one execution: the ledger hands out
    one object per (stage, name) over and over, and makes a new one only
    where it is given a ``block``.  ``block`` and ``app`` default to the
    enclosing frame's.  Only the outermost span asks the kill switch and
    ``rec`` (is a profiler session or the operator's exporter
    recording?); those inside take its answer.

    A recorded span of :data:`ONCPU_KEYS` reads the thread's CPU clock
    (``c0``) and sets ``rec`` to 2 for the spans inside it, whose stage
    and keyed spans then read it too; with the exporter on every span
    reads it.  The last three slots keep the CPU time exclusive by the
    rules of the first two.  The reads lie just outside the wall stamps,
    and what they and the span's books take goes to the parent's child
    slot: no stage or key is charged for the second clock."""

    __slots__ = ("ledger", "stage", "key", "annot", "block", "app",
                 "clock")

    def __init__(self, ledger: "LatencyLedger", stage: Optional[str],
                 key: Optional[str], annot: str,
                 block: Optional[int] = None, app: Optional[str] = None):
        self.ledger = ledger
        self.stage = stage
        self.key = key
        self.annot = annot
        self.block = block
        self.app = app
        # 2: a key with a pair; 1: a stage or a key, which reads the CPU
        # clock inside a span that does; 0: an annotation
        self.clock = 2 if key in ONCPU_KEYS else \
            int(stage is not None or key is not None)

    def __enter__(self):
        tls = self.ledger._tls
        st = getattr(tls, "stack", None)
        block = self.block
        if st:
            top = st[-1]
            rec = top[4]
            frame = [0, 0, top[2], top[3], rec, 0, None, 0, 0, None] \
                if block is None else \
                [0, 0, block, self.app, rec, 0, None, 0, 0, None]
        else:
            if not ledger_enabled():
                return self
            if st is None:
                st = tls.stack = []
            rec = _SINK.enabled or _ta_session()
            frame = [0, 0, block, self.app, rec, 0, None, 0, 0, None]
        st.append(frame)
        if rec:
            if _ta_session():
                block = frame[2]
                ta = frame[6] = _TA(self.annot) if block is None else \
                    _TA(self.annot, block=block)
                ta.__enter__()
            clock = self.clock
            if clock == 2 or clock and rec == 2 or _SINK.enabled:
                w = _pcns()
                frame[4] = 2
                frame[9] = _tns()
                t0 = frame[5] = _pcns()
                if len(st) > 1:
                    st[-2][0] += t0 - w
                return self
        frame[5] = _pcns()
        return self

    def __exit__(self, *exc):
        st = getattr(self.ledger._tls, "stack", None)
        if not st:
            return False            # the ledger was off at the enter
        frame = st.pop()
        t1 = _pcns()
        elapsed = t1 - frame[5]
        c0 = frame[9]
        if c0 is not None:
            cpu = _tns() - c0
        led = self.ledger
        stage = self.stage
        key = self.key
        if stage is not None:
            if st:
                st[-1][0] += elapsed
            ns = elapsed - frame[0]
            if ns > 0:
                led._ns[stage] += ns
            led._spans[stage] += 1
            if key is not None:
                ns -= frame[1]
                if ns > 0:
                    led._ns[key] += ns
                app = frame[3]
                if app is not None:
                    named = led._named
                    named.append((app, key, ns))
                    if len(named) >= led._FOLD_NAMED_EVERY:
                        led._fold_named()
            if c0 is not None:
                if st:
                    st[-1][7] += cpu
                if self.clock == 2:
                    led._rec_ns[key] += ns
                    led._cpu_ns[key] += cpu - frame[7] - frame[8]
        else:
            # what the stage spans inside it took is their own stages'
            ns = elapsed - frame[0]
            if st:
                top = st[-1]
                top[0] += frame[0]
                top[1] += frame[1] if key is None else ns
                if frame[4] == 2:
                    top[7] += frame[7]
                    top[8] += frame[8] if key is None else cpu - frame[7]
            if key is not None and ns > 0:
                led._ns[key] += ns
            if self.clock == 2 and c0 is not None:
                led._rec_ns[key] += ns
                led._cpu_ns[key] += cpu - frame[7]
        if frame[4]:
            if c0 is not None and st:
                st[-1][0] += _pcns() - t1
            if frame[6] is not None:
                frame[6].__exit__(None, None, None)
            if _SINK.enabled:
                args = {} if c0 is None else {"cpu_us": cpu / 1e3}
                if frame[2] is not None:
                    args["block"] = frame[2]
                _sink_event(self.annot[7:], stage or "engine", frame[5],
                            elapsed, args or None)
        return False


# ------------------------------------------------------------------ ledger


class LatencyLedger:
    """Process-global stage accumulators + per-app histograms + lag
    watermarks + SLO state.

    Hot-path writes are plain int adds under the GIL (the RimStats
    contract: exact single-threaded, monotone everywhere); dict creation
    for new (app, stage) keys is the only locked path."""

    #: per-app block deltas buffered before the histogram fold — the
    #: fold (6-8 locked Histogram.records) costs ~10x its isolated time
    #: right after a device block (cold caches), so the hot path only
    #: appends the integer deltas and the fold runs once per
    #: _FOLD_EVERY blocks / lazily on any read surface
    _FOLD_EVERY = 64

    #: named-span / wait entries buffered before the same lazy fold
    #: (~0.9 us an entry: half a millisecond of the worker at a time)
    _FOLD_NAMED_EVERY = 512

    def __init__(self):
        # the seven stages and, beside them, the declared sub-spans
        self._ns: Dict[str, int] = {s: 0 for s in STAGES + SPAN_NAMES}
        self._spans: Dict[str, int] = {s: 0 for s in STAGES}
        # the exclusive wall ns and CPU ns of ONCPU_KEYS' recorded spans
        # alone, so that their ratio compares like with like
        self._rec_ns: Dict[str, int] = dict.fromkeys(ONCPU_KEYS, 0)
        self._cpu_ns: Dict[str, int] = dict.fromkeys(ONCPU_KEYS, 0)
        self._lock = threading.Lock()
        self._tls = threading.local()
        # stage or (stage, name) -> the span object of every execution of
        # that span
        self._span_of: Dict[Any, _Span] = {}
        # (app, stage) -> Histogram of per-block stage ns; stage "total"
        # is the per-block all-stage sum (the e2e estimator SLOs burn on)
        self._hist: Dict[tuple, Histogram] = {}
        # app -> buffered per-block delta lists awaiting the fold
        self._pending: Dict[str, list] = {}
        # (app, sub-span or wait key, ns): one entry per execution,
        # awaiting the same fold
        self._named: list = []
        # app -> [retires whose result was ready, retires that blocked]
        self._retires: Dict[str, list] = {}
        # app -> ABSENT_COUNTERS row.  Kept past drop_app, as the stage
        # accumulators are: a run reads it after its app shut down
        self._absent: Dict[str, list] = {}
        # app -> KEY_FACTOR_COUNTERS row and app -> KEY_INTERN_COUNTERS
        # row, kept as ``_absent`` is
        self._keyfac: Dict[str, list] = {}
        self._keyint: Dict[str, list] = {}
        # app -> COUNT_COUNTERS row and app -> PACK_COUNTERS row, likewise
        self._count: Dict[str, list] = {}
        self._pack: Dict[str, list] = {}
        # app -> PLANE_COUNTERS row, likewise
        self._planes: Dict[str, list] = {}
        # app -> JOIN_COUNTERS row, likewise
        self._join: Dict[str, list] = {}
        # app -> GAGG_COUNTERS row, likewise
        self._gagg: Dict[str, list] = {}
        # app -> [device launches, ingest blocks]: the runtimes hand the
        # launch delta of every ingest block to ``note_block``.  Kept as
        # ``_absent`` is
        self._blocks: Dict[str, list] = {}
        # app -> the most recent block's stage deltas (waterfall row)
        self._last_deltas: Dict[str, list] = {}
        # (app, stream) -> lag watermark state
        self._lag: Dict[tuple, Dict[str, float]] = {}
        self._slo: Dict[str, _SloState] = {}

    # -------------------------------------------------------- hot path

    @property
    def enabled(self) -> bool:
        return ledger_enabled()

    def _tls_stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, stage: Optional[str], name: Optional[str] = None,
             block: Optional[int] = None, app: Optional[str] = None):
        """``with ledger().span("device", "pack"): ...`` — see the module
        docstring.  ``block`` and ``app`` are given together, where a
        block changes hands (a delivery, a retire); the spans inside
        take them from there."""
        sp = self._span_of.get(stage if name is None else (stage, name))
        if sp is None:
            sp = self._span_of[stage if name is None else (stage, name)] = \
                _Span(self, stage, *_names_of(stage, name))
        if block is not None:
            return _Span(self, stage, sp.key, sp.annot, block, app)
        if stage is None and sp.key is None:
            # only an annotation: is anybody recording it?
            st = getattr(self._tls, "stack", None)
            if not (st[-1][4] if st else _SINK.enabled or _ta_session()):
                return _NO_SPAN
        return sp

    def stamp(self) -> Optional[tuple]:
        """``(now ns, the current block)`` for a handle that goes in
        flight; None with the ledger off."""
        st = getattr(self._tls, "stack", None)
        if st:
            return _pcns(), st[-1][2]
        return (_pcns(), None) if ledger_enabled() else None

    def current_block(self) -> Optional[int]:
        """The ``block`` of the innermost open stage span on this thread
        (a chunk emitted inside a delivery stays with that block)."""
        st = getattr(self._tls, "stack", None)
        return st[-1][2] if st else None

    def instant(self, name: str, cat: str = "engine", **args) -> None:
        """A point event for the operator's Chrome exporter (nothing on
        the profiler's clock: a TraceMe has a duration)."""
        if _SINK.enabled:
            _sink_event(name, cat, _pcns(), None, args)

    def record(self, stage: str, ns: int) -> None:
        """Credit ``ns`` of exclusive wall time to ``stage``."""
        if ns < 0:
            ns = 0
        self._ns[stage] += ns
        self._spans[stage] += 1

    def note_ingress(self, app: str, stream: str, event_ts_ms: int,
                     now_ms: float, dur_ns: int) -> None:
        """Per-chunk admit stamp: ingress stage time + the event-time lag
        watermark (max admitted event timestamp vs the wall clock — or
        the playback clock when the app replays history)."""
        if dur_ns > 0:
            self._ns["ingress"] += dur_ns
        self._spans["ingress"] += 1
        ent = self._lag.get((app, stream))
        if ent is None:
            ent = self._lag[(app, stream)] = {}
        ent["admit_wall_ms"] = time.time() * 1000.0
        ent["lag_ms"] = float(now_ms - event_ts_ms)

    def note_retire(self, app: str, t_submit: Optional[int],
                    t_issue: int, t_retire: int, ready: bool,
                    cause: int) -> None:
        """One in-flight block starts its retire: bank its waits (ns
        stamps of this module's clock; a block with no submit stamp was
        dispatched with the ledger off), count whether its result was
        already there, and what caused the retire (``ON_READY``,
        ``ON_DEPTH`` or ``ON_FLUSH``)."""
        named = self._named
        if t_submit is not None:
            named.append((app, "wait.inflight", t_retire - t_submit))
            named.append((app, "wait.defer", t_issue - t_submit))
        row = self._retires.get(app)
        if row is None:
            with self._lock:
                row = self._retires.setdefault(
                    app, [0] * len(RETIRE_COUNTERS))
        row[0 if ready else 1] += 1
        row[cause] += 1

    def _add(self, rows: Dict[str, list], app: str, deltas) -> None:
        # under the lock: two queries of an app may ingest on two sender
        # threads at once, and `row[i] += d` alone loses an update
        with self._lock:
            row = rows.get(app)
            if row is None:
                row = rows[app] = [0] * len(deltas)
            for i, d in enumerate(deltas):
                row[i] += int(d)

    def note_absent(self, app: str, deltas) -> None:
        """Add to an app's ABSENT_COUNTERS (a device pattern runtime, as
        it retires a block or steps a TIMER)."""
        self._add(self._absent, app, deltas)

    def note_count(self, app: str, deltas) -> None:
        """Add to an app's COUNT_COUNTERS (a device pattern runtime, as
        it retires a block)."""
        self._add(self._count, app, deltas)

    def note_pack(self, app: str, events: int, cells: int) -> None:
        """One dense block packed: its events and its P x T cells."""
        self._add(self._pack, app, (events, cells))

    def note_planes(self, app: str, planes: int, shared: int) -> None:
        """One dense block's planes, and those of them that were made
        already."""
        self._add(self._planes, app, (planes, shared))

    def note_join(self, app: str, deltas, grown: int = 0) -> None:
        """Add a retired block's JOIN_CTR deltas and ring doublings to
        an app's JOIN_COUNTERS (the keyed device join runtime)."""
        self._add(self._join, app, (*deltas, grown, 0, 0, 0, 0))

    def note_join_events(self, app: str, events: int, device: int) -> None:
        """``events`` reached a join query; ``device`` of them a keyed
        device join runtime's."""
        self._add(self._join, app, (0,) * 6 + (events, device, 0, 0))

    def note_join_build(self, app: str, rows: int, slots: int) -> None:
        """One group of a block's build side uploaded as compact rows:
        the events it holds, and its rows, padding included."""
        self._add(self._join, app, (0,) * 8 + (rows, slots))

    def note_gagg(self, app: str, events: int, lane_events: int,
                  cells: int) -> None:
        """One block stepped by a grouped-aggregation device runtime: its
        events, those of them on group lanes, its cells."""
        self._add(self._gagg, app, (events, lane_events, cells))

    def note_key_factor(self, app: str, reused: bool) -> None:
        """One keyed device ingest asked for its block's factored keys."""
        self._add(self._keyfac, app, (1, reused))

    def note_key_intern(self, app: str, events: int, hits: int) -> None:
        """One block's keys interned: its events, and those of them
        whose id the per-event probe gave."""
        self._add(self._keyint, app, (events, hits))

    def _counter_rows(self):
        """(counter names, app -> row) of every per-app counter family."""
        return ((RETIRE_COUNTERS, self._retires),
                (ABSENT_COUNTERS, self._absent),
                (KEY_FACTOR_COUNTERS, self._keyfac),
                (KEY_INTERN_COUNTERS, self._keyint),
                (COUNT_COUNTERS, self._count),
                (PACK_COUNTERS, self._pack),
                (PLANE_COUNTERS, self._planes),
                (JOIN_COUNTERS, self._join),
                (GAGG_COUNTERS, self._gagg))

    # ------------------------------------------------------ block fold

    def stage_ns(self) -> Dict[str, int]:
        return dict(self._ns)

    def oncpu_seconds(self) -> Dict[str, Dict[str, float]]:
        """Key of :data:`ONCPU_KEYS` -> ``{"wall": s, "cpu": s}``: the
        exclusive wall and CPU time of its recorded spans (only while a
        profiler session or the exporter records; 0 and 0 otherwise)."""
        return {k: {"wall": self._rec_ns[k] / 1e9,
                    "cpu": self._cpu_ns[k] / 1e9} for k in self._rec_ns}

    def _hist_for(self, app: str, stage: str) -> Histogram:
        h = self._hist.get((app, stage))
        if h is None:
            with self._lock:
                h = self._hist.setdefault((app, stage), Histogram())
        return h

    @hot_path("per-block stage-delta banking + SLO evaluation")
    def note_block(self, app: str, owner, runtime=None,
                   want_row: bool = True,
                   dispatches: int = 0) -> Optional[Dict[str, float]]:
        """Bank one ingest block: the device launches it cost (kill
        switch or not: they are counted, not timed) and its stage deltas
        (global accumulators vs ``owner``'s last snapshot), evaluate the
        app's SLO, and return the waterfall row for the flight record
        (only built when ``want_row``; the histogram fold is deferred —
        see ``_FOLD_EVERY``)."""
        tot = self._blocks.get(app)
        if tot is None:
            with self._lock:
                tot = self._blocks.setdefault(app, [0, 0])
        tot[0] += dispatches
        tot[1] += 1
        if not ledger_enabled():
            return None
        cur = _stage_values(self._ns)
        prev = getattr(owner, "_ledger_ns0", None)
        owner._ledger_ns0 = cur
        if prev is None:
            return None
        deltas = tuple(map(_sub, cur, prev))
        if min(deltas) < 0:         # the accumulators were reset() since
            deltas = tuple(d if d > 0 else 0 for d in deltas)
        total_ns = sum(deltas)
        self._last_deltas[app] = deltas
        pend = self._pending.get(app)
        if pend is None:
            with self._lock:
                pend = self._pending.setdefault(app, [])
        pend.append(deltas)
        if len(pend) >= self._FOLD_EVERY:
            self._fold_pending(app)
        st = self._slo.get(app)
        if st is not None and st.observe(
                total_ns / 1e6 if total_ns > 0 else None,
                self._app_lag_ms(app)):
            self._emit_breach(app, st, runtime)
        if not want_row or total_ns <= 0:
            return None
        return self._row_ms(deltas)

    @staticmethod
    def _row_ms(deltas) -> Dict[str, float]:
        # ms to four places (d // 100 / 1e4: no call per stage)
        return {s: d // 100 / 1e4
                for s, d in zip(STAGES, deltas) if d > 0}

    def _fold_pending(self, app: Optional[str] = None) -> None:
        """Drain buffered block deltas into the per-app histograms
        (cold path: every read surface calls this first)."""
        apps = [app] if app is not None else list(self._pending)
        for a in apps:
            pend = self._pending.get(a)
            if not pend:
                continue
            drained = pend[:]
            del pend[:len(drained)]     # GIL-safe vs concurrent appends
            # a stage's deltas of all drained blocks at once (no delta
            # is negative: note_block clamps them)
            rows = list(zip(*drained)) + [[sum(d) for d in drained]]
            for s, row in zip(STAGES + ("total",), rows):
                values = [d for d in row if d > 0]
                if values:
                    self._hist_for(a, s).record_many(values)
        self._fold_named()

    def _fold_named(self) -> None:
        named = self._named
        drained = named[:]
        del named[:len(drained)]        # GIL-safe vs concurrent appends
        groups: Dict[tuple, list] = {}
        for app, key, ns in drained:
            groups.setdefault((app, key), []).append(ns)
        for (app, key), values in groups.items():
            self._hist_for(app, key).record_many(values)

    def _app_lag_ms(self, app: str) -> Optional[float]:
        lags = [v["lag_ms"] for (a, _s), v in list(self._lag.items())
                if a == app]
        return max(lags) if lags else None

    def _emit_breach(self, app: str, st: _SloState, runtime) -> None:
        """SLO001 through the flight bus: the breach ships its own
        waterfall evidence (last block row + the per-stage histogram
        summaries of the breaching window)."""
        from .flight import flight
        try:
            flight().emit("slo_breach", app=app, detail={
                "code": "SLO001",
                "slo": st.config.as_dict(),
                "observed": st.as_dict(),
                "waterfall": self._row_ms(
                    self._last_deltas.get(app, [])),
                "stage_summary_ms": self._stage_summary(app),
            }, runtime=runtime)
        except Exception:   # noqa: BLE001 — SLO accounting must not raise
            pass

    # ----------------------------------------------------- SLO registry

    def register_slo(self, app: str, config: SloConfig) -> None:
        with self._lock:
            self._slo[app] = _SloState(config)

    def drop_app(self, app: str) -> None:
        """Forget one app's SLO + lag + histogram state (runtime
        shutdown; process-global stage counters are left alone)."""
        self._fold_named()          # so the app's buffered entries go too
        with self._lock:
            self._slo.pop(app, None)
            self._pending.pop(app, None)
            self._retires.pop(app, None)
            self._last_deltas.pop(app, None)
            for key in [k for k in self._lag if k[0] == app]:
                self._lag.pop(key, None)
            for key in [k for k in self._hist if k[0] == app]:
                self._hist.pop(key, None)

    def dispatches_per_block(self) -> Dict[str, float]:
        """app -> device launches per ingest block, a running average
        (``siddhi_app_dispatches_per_block``; a watchdog incident's
        evidence: the session-timer storm was this ratio exploding)."""
        return {app: d / n for app, (d, n) in sorted(self._blocks.items())
                if n}

    def slo_breached(self, app: str) -> bool:
        st = self._slo.get(app)
        return bool(st is not None and st.breached)

    # ------------------------------------------------------- snapshots

    def _stage_summary(self, app: str) -> Dict[str, Dict[str, float]]:
        self._fold_pending(app)
        out: Dict[str, Dict[str, float]] = {}
        for stage in STAGES + ("total",) + SPAN_NAMES + WAITS:
            h = self._hist.get((app, stage))
            if h is not None and h.count:
                out[stage] = h.summary(scale=1e-6)      # ns -> ms
        return out

    def snapshot(self, app: Optional[str] = None) -> Dict[str, Any]:
        self._fold_pending()
        doc: Dict[str, Any] = {
            "enabled": ledger_enabled(),
            "stage_seconds": {s: self._ns[s] / 1e9 for s in STAGES},
            "span_seconds": {s: self._ns[s] / 1e9 for s in SPAN_NAMES},
            "oncpu_seconds": self.oncpu_seconds(),
            "stage_spans": dict(self._spans),
        }
        apps = sorted({a for (a, _s) in self._hist}.union(
            self._absent, self._keyfac, self._keyint, self._count,
            self._pack, self._planes, self._join, self._gagg)) \
            if app is None else [app]
        per_app = {}
        for a in apps:
            entry: Dict[str, Any] = {"stages_ms": self._stage_summary(a)}
            lags = {s: {"lag_ms": round(v["lag_ms"], 3),
                        "processing_lag_ms": round(
                            time.time() * 1000.0 - v["admit_wall_ms"], 3)}
                    for (aa, s), v in list(self._lag.items()) if aa == a}
            if lags:
                entry["lag"] = lags
            st = self._slo.get(a)
            if st is not None:
                entry["slo"] = st.as_dict()
            last = self._last_deltas.get(a)
            if last:
                entry["last_block_ms"] = self._row_ms(last)
            for names, rows in self._counter_rows():
                row = rows.get(a)
                if row is not None:
                    entry.update(zip(names, row))
            per_app[a] = entry
        doc["apps"] = per_app
        return doc

    def prometheus_lines(self) -> List[str]:
        from .statistics import _fmt_labels
        self._fold_pending()
        lines: List[str] = []
        for stage in STAGES:
            lab = _fmt_labels({"stage": stage})
            lines.append(f"siddhi_ledger_stage_seconds_total{lab} "
                         f"{self._ns[stage] / 1e9:.9g}")
            lines.append(f"siddhi_ledger_stage_spans_total{lab} "
                         f"{self._spans[stage]}")
        for key in SPAN_NAMES:
            lab = _fmt_labels({"span": key})
            lines.append(f"siddhi_ledger_span_seconds_total{lab} "
                         f"{self._ns[key] / 1e9:.9g}")
        for names, rows in self._counter_rows():
            for app, row in sorted(rows.items()):
                lab = _fmt_labels({"app": app})
                for name, n in zip(names, row):
                    lines.append(f"siddhi_{name}{lab} {n}")
        for app, v in self.dispatches_per_block().items():
            lab = _fmt_labels({"app": app})
            lines.append(f"siddhi_app_dispatches_per_block{lab} {v:.9g}")
        for (app, stage), h in sorted(self._hist.items()):
            if not h.count:
                continue
            s = h.summary(scale=1e-6)
            for q in ("p50", "p99"):
                lab = _fmt_labels({"app": app, "stage": stage, "q": q})
                lines.append(
                    f"siddhi_ledger_stage_latency_ms{lab} {s[q]:.6g}")
        now_ms = time.time() * 1000.0
        for (app, stream), v in sorted(self._lag.items()):
            lab = _fmt_labels({"app": app, "stream": stream})
            lines.append(f"siddhi_event_time_lag_ms{lab} "
                         f"{v['lag_ms']:.6g}")
            lines.append(f"siddhi_processing_lag_ms{lab} "
                         f"{now_ms - v['admit_wall_ms']:.6g}")
        for app, st in sorted(self._slo.items()):
            for slo_kind, burn in (("latency_p99", st.burn_latency),
                                   ("lag", st.burn_lag)):
                lab = _fmt_labels({"app": app, "slo": slo_kind})
                lines.append(f"siddhi_slo_burn_rate{lab} {burn:.6g}")
            lab = _fmt_labels({"app": app})
            lines.append(f"siddhi_slo_breach_active{lab} "
                         f"{1 if st.breached else 0}")
            lines.append(f"siddhi_slo_breach_total{lab} {st.breach_total}")
        return lines

    def reset(self) -> None:
        """Test/bench isolation (mirrors flight().reset())."""
        with self._lock:
            for s in self._ns:
                self._ns[s] = 0
            for s in ONCPU_KEYS:
                self._rec_ns[s] = self._cpu_ns[s] = 0
            for s in STAGES:
                self._spans[s] = 0
            self._hist.clear()
            self._pending.clear()
            del self._named[:]
            for _names, rows in self._counter_rows():
                rows.clear()
            self._blocks.clear()
            self._last_deltas.clear()
            self._lag.clear()
            self._slo.clear()


_GLOBAL = LatencyLedger()


def ledger() -> LatencyLedger:
    return _GLOBAL

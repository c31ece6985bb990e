"""Batched NFA step kernel — the TPU pattern-matching hot path.

This replaces the reference's per-event, per-partial-match Java loop
(query/input/stream/state/StreamPreStateProcessor.java:292-337 — a linked
list of partial matches stepped one event at a time under a ReentrantLock)
with a dense tensor program:

    state:    slot_state [P, K] int32   — unit each partial slot waits on
              slot_start [P, K] int32   — first-capture timestamp (within)
              captures   [P, K, R, C]   — capture rows (one per unit side)
    events:   [P, T] time-major blocks, one independent lane per partition

    step = lax.scan over T  ∘  vmap over P  ∘  (condition gate + advance)

All K partial slots of all P partitions evaluate their pending condition
against the incoming event in one vectorised pass.  Partition lanes are
fully independent, so the P axis shards over an ICI mesh with jax.sharding
(see parallel/mesh.py) with zero collectives on the hot path.

The pattern algebra is a chain of *units* compiled by plan/nfa_compiler.py
(reference util/parser/StateInputStreamParser.java:76-404):

  - simple   one condition; advance on match
             (Stream Pre/PostStateProcessor)
  - count    kleene <m:n>: per-slot counter accumulates matches, forwards
             at min, keeps live-appending into the last-capture bank while
             the next unit is pending, freezes at max
             (CountPreStateProcessor.java:53-105, CountPostStateProcessor)
  - logical  and/or partner pair: two (stream, condition, capture-row)
             sides + a per-slot side bitmask
             (LogicalPreStateProcessor.java:57-92)
  - absent   `not X for t`: per-slot deadline lane; an arriving match
             kills the partial, deadline expiry (driven by real events or
             host-injected TIMER rows) confirms the absence and advances
             (AbsentStreamPreStateProcessor.java:63-96)

Both PATTERN (non-strict) and SEQUENCE (strict contiguity: a partial must
advance or append on every event or die — the reference's per-event
resetState/updateState barriers, StreamPreStateProcessor.java:263-290)
semantics are supported.  Conformance vs the host oracle (core/pattern.py)
is asserted in tests/test_tpu_nfa.py and tests/test_planner.py.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NO_SLOT = jnp.int32(-1)
COUNT_INF = 0x7FFFFFFF

#: block leaf ``[P, 1]`` int32: the block's clock, its latest event time
#: (offset from the engine's base like ``__ts``).  Present only in blocks
#: of an automaton with a `not … for t` unit (build_block_step).
CLOCK_KEY = "__clock"

#: what the ``absent_ctr`` carry leaf counts, per lane and cumulatively:
#: slots that entered a `not … for t` unit with a deadline; deadlines that
#: fired (the slot left the unit by time, row or not); of those, the ones
#: fired by an event block's own clock (a lane's event at or past the
#: deadline, or the block's closing pass) rather than by a host TIMER row;
#: slots killed by an arrival on the `not` stream
ABSENT_CTR = ("armed", "fired", "fired_inblock", "killed")

#: what the ``count_ctr`` carry leaf counts, per lane and cumulatively,
#: over the automaton's kleene `<m:n>` units: chains started (the append
#: that made a chain one event long); events appended (an event counts
#: once per chain that takes it); chains that reached `m` and opened the
#: next unit (a min-0 chain, open from the start, at its first event);
#: chains that reached `n` and stopped absorbing
COUNT_CTR = ("armed", "appended", "forwarded", "frozen")

#: B-event micro-batching of the scan chain (round 6).  The env value is
#: B itself: unset/empty → DEFAULT_BATCH_B; ``=1`` is the kill switch
#: (legacy one-event ticks, no hoisting).
BATCH_ENV = "SIDDHI_TPU_NFA_BATCH"
DEFAULT_BATCH_B = 4


def resolve_batch_b(batch_b: Optional[int] = None) -> int:
    """Effective events-per-tick B: explicit argument wins, else the
    BATCH_ENV value, else DEFAULT_BATCH_B.  Anything < 1 (or
    unparseable) clamps to the legacy/default respectively."""
    if batch_b is None:
        raw = os.environ.get(BATCH_ENV, "").strip().lower()
        if raw in ("", "on", "true", "default"):
            return DEFAULT_BATCH_B
        try:
            return max(1, int(raw))
        except ValueError:
            return DEFAULT_BATCH_B
    return max(1, int(batch_b))


#: Chunk stacking (round 7): a bank of C homogeneous-shape pattern
#: chunks runs as ONE jitted super-dispatch (vmap over the chunk axis)
#: instead of C sequential device calls.  ``=0``/``off`` restores the
#: legacy sequential chunk loop.
STACK_ENV = "SIDDHI_TPU_NFA_STACK"


def resolve_stack(stack: Optional[bool] = None) -> bool:
    """Effective chunk-stacking switch: explicit argument wins, else the
    STACK_ENV value (default on; 0/false/off disables)."""
    if stack is None:
        raw = os.environ.get(STACK_ENV, "").strip().lower()
        return raw not in ("0", "false", "off", "no")
    return bool(stack)


class UnitSpec(NamedTuple):
    """One chain position (≙ one Pre/PostStateProcessor pair)."""
    kind: str                 # 'simple' | 'count' | 'logical' | 'absent'
    stream_a: int             # stream code of side A
    cond_a: int               # index into NfaSpec.cond_fns
    row_a: int                # capture row (-1: no captures, absent units)
    stream_b: int = -1        # logical pairs only
    cond_b: int = -1
    row_b: int = -1
    is_and: bool = False      # logical: and vs or
    min_count: int = 1        # count units
    max_count: int = 1
    waiting_ms: int = 0       # absent units


class NfaSpec(NamedTuple):
    """Compiled NFA structure (built by plan/nfa_compiler.py)."""
    units: Tuple[UnitSpec, ...]
    n_rows: int                       # capture rows
    n_caps: int                       # lanes per row (C)
    n_slots: int                      # K: max concurrent partials
    within_ms: Optional[int]
    # cond_fns[i](event_cols: {attr: scalar}, captures: [K, R, C]) -> [K]
    cond_fns: Tuple[Callable, ...]
    cap_cols: Tuple[Tuple[str, ...], ...]   # per row: first bank ++ last bank
    n_first: Tuple[int, ...]          # per row: #lanes in the first bank
    n_lane: Tuple[int, ...]           # per row: __n counter lane (-1: none)
    matched_lane: Tuple[int, ...]     # per row: __matched lane (-1: none)
    attr_names: Tuple[str, ...]       # event column order
    is_every: bool
    is_sequence: bool = False
    arm_once: bool = False            # single-shot arming
    every_group_end: int = 0          # last unit of the `every` re-arm group
    tail_every_start: int = -1        # first unit of a trailing `every`
    #                                   group: a completing partial re-arms
    #                                   there (captures intact) instead of
    #                                   dying — `A -> every B` semantics
    #                                   (StateInputStreamParser.java:272-273)
    mid_every: Tuple[Tuple[int, int], ...] = ()
    #                                   mid-chain `every` groups (g0, g1):
    #                                   a partial advancing OUT of g1 forks
    #                                   a clone that re-arms at g0 with its
    #                                   pre-group captures while the
    #                                   original advances (the reference's
    #                                   addEveryState clone,
    #                                   StreamPostStateProcessor.java:66-68)
    eps_start: bool = False           # leading min-0 kleene: unit 1 is an
    #                                   alternate start state (empty-kleene
    #                                   path), see _one_partition_step
    n_last: Tuple[int, ...] = ()      # per row: #lanes in the last bank
    idx_banks: Tuple = ()             # per row: ((k, start, len), ...) —
    #                                   e[k] banks, written when the kleene
    #                                   chain reaches k+1 elements
    lastk_banks: Tuple = ()           # per row: ((j, start), ...) — e[last-j]
    #                                   banks, shift chain behind the last
    #                                   bank on every append
    m_src: Tuple = ()                 # per row: last-bank source lanes for
    #                                   the shift chain (lane-aligned)
    lead_absent: bool = False         # `not A for t -> ...`: the start
    #                                   state is an absent unit — a partial
    #                                   with a deadline is kept armed at
    #                                   unit 0 (ensure-arm; arrivals kill +
    #                                   re-arm with a fresh deadline), the
    #                                   reference's AbsentStreamPreState
    #                                   Processor start/init/re-init loop
    dead_start: bool = False          # SEQUENCE leading kleene min >= 2:
    #                                   the per-event barrier clears every
    #                                   pending list and CountPost only
    #                                   re-adds at cnt >= min, so a sub-min
    #                                   accumulator never survives — the
    #                                   shape produces ZERO matches (oracle
    #                                   verified); arming is suppressed
    cond_free: Tuple[bool, ...] = ()  # per cond_fn: True when the program
    #                                   reads ONLY the current event (no
    #                                   captures, no __cnt lanes, no
    #                                   nullable-row gates) — eligible for
    #                                   block-wide hoisting out of the scan
    batch_b: int = 0                  # events consumed per scan tick (the
    #                                   compiler pins resolve_batch_b();
    #                                   0 → resolve from env at build time,
    #                                   1 → legacy one-event ticks)
    telemetry: bool = False           # @app:statistics(telemetry='true'):
    #                                   accumulate an int32 telemetry leaf
    #                                   (per-state occupancy, gate
    #                                   pass/fail, within-expiry drops) in
    #                                   the carry — read out through the
    #                                   fused egress slab; MUST leave match
    #                                   outputs bit-identical

    @property
    def n_states(self) -> int:
        return len(self.units)


def _has(spec: NfaSpec, kind: str) -> bool:
    return any(u.kind == kind for u in spec.units)


def _land_static(spec: NfaSpec, j_from: int):
    """Where a slot advancing out of unit j_from ends up.

    Returns (target, live0, completed): `live0` marks an epsilon-skipped
    min-0 count unit at target-1 that keeps live-appending
    (CountPreStateProcessor.addState min==0 branch); `completed` means the
    chain is done and the advance emits a match."""
    S = len(spec.units)
    t = j_from + 1
    live0 = False
    if t < S and spec.units[t].kind == "count" and \
            spec.units[t].min_count == 0:
        live0 = True
        t += 1
    return t, live0, t >= S


def make_carry(spec: NfaSpec, n_partitions: int) -> Dict[str, jnp.ndarray]:
    # NOTE: the static cost model (analysis/cost_model.nfa_state_bytes)
    # mirrors these shapes closed-form and is asserted BYTE-EXACT against
    # the arrays allocated here (tests/test_plan_verify.py) — adding or
    # resizing a carry array must update both, or that test fails.
    P, K = n_partitions, spec.n_slots
    R, C = max(spec.n_rows, 1), max(spec.n_caps, 1)
    carry = {
        "slot_state": jnp.full((P, K), -1, jnp.int32),
        "slot_start": jnp.zeros((P, K), jnp.int32),
        # ts the slot entered its current unit + per-partition arm sequence
        # — together they reproduce the oracle's pending-list insertion
        # order for same-event completions
        "slot_enter": jnp.zeros((P, K), jnp.int32),
        "slot_seq": jnp.zeros((P, K), jnp.int32),
        "arm_seq": jnp.zeros((P,), jnp.int32),
        "captures": jnp.zeros((P, K, R, C), jnp.float32),
        "dropped": jnp.zeros((P,), jnp.int32),   # slot-overflow counter
    }
    if _has(spec, "count"):
        carry["cnt_cur"] = jnp.zeros((P, K), jnp.int32)
        carry["cnt_prev"] = jnp.full((P, K), -1, jnp.int32)
        # cumulative per lane, in COUNT_CTR order; read as absent_ctr is
        carry["count_ctr"] = jnp.zeros((P, len(COUNT_CTR)), jnp.int32)
    if spec.eps_start and spec.is_sequence:
        # 1 when the leading kleene froze at max on the previous event:
        # the oracle's fresh virgin then finds the next unit's new-list
        # still holding the frozen partial and is closer-blocked for its
        # creation event (CountPre addState SEQUENCE empty-list guard)
        carry["seq_froze"] = jnp.zeros((P,), jnp.int32)
    if _has(spec, "logical"):
        carry["lmask"] = jnp.zeros((P, K), jnp.int32)
    if _has(spec, "absent"):
        carry["deadline"] = jnp.zeros((P, K), jnp.int32)
        # cumulative per lane, in ABSENT_CTR order; read off the
        # egress tail, never by a device read of its own
        carry["absent_ctr"] = jnp.zeros((P, len(ABSENT_CTR)),
                                        jnp.int32)
    if spec.arm_once:
        carry["armed_total"] = jnp.zeros((P,), jnp.int32)
    if spec.telemetry:
        # [occ[S] (gauge) ‖ gate_pass[S] ‖ gate_fail[S] ‖ within_drops]
        carry["telem"] = jnp.zeros((P, 3 * len(spec.units) + 1), jnp.int32)
    return carry


def _event_rows(spec: NfaSpec, event) -> jnp.ndarray:
    """[R, C] matrix of the lanes this event would write into each row
    (__matched lanes read 1.0; __n lanes are patched per-slot later)."""
    R, C = max(spec.n_rows, 1), max(spec.n_caps, 1)
    rows = []
    for r in range(R):
        cols = spec.cap_cols[r] if r < len(spec.cap_cols) else ()
        lanes = [event[a].astype(jnp.float32) if a in event
                 else jnp.float32(1.0)          # __matched / __n defaults
                 for a in cols]
        lanes += [jnp.float32(0)] * (C - len(lanes))
        rows.append(jnp.stack(lanes) if lanes
                    else jnp.zeros((C,), jnp.float32))
    return jnp.stack(rows)


def _gate_key(i: int) -> str:
    """Event-dict column carrying cond i's hoisted block-wide gate."""
    return f"__gate_{i}"


def _eval_conds(spec: NfaSpec, event, caps) -> List[jnp.ndarray]:
    """Per-cond [K] booleans for one event.

    Hoisted conditions (capture-free, precomputed for the whole block by
    ``_hoist_cond_gates``) read their scalar gate straight from the event
    dict — the scan body then carries only the truly sequential masked
    state update; everything else evaluates its program against the
    current captures exactly as before."""
    K = caps.shape[0]
    conds = []
    for i, fn in enumerate(spec.cond_fns):
        key = _gate_key(i)
        if key in event:
            conds.append(jnp.broadcast_to(event[key], (K,)))
        else:
            conds.append(fn(event, caps))
    return conds


def _cond_on(spec: NfaSpec, event, cond_id: int, caps) -> jnp.ndarray:
    """One condition against an explicit capture context (the virgin
    zero-caps re-arm/seed sites).  A hoisted gate IS fn(event, zeros) by
    construction, so it substitutes exactly."""
    key = _gate_key(cond_id)
    if key in event:
        return event[key]
    return spec.cond_fns[cond_id](event, caps)[0]


def _hoist_cond_gates(spec: NfaSpec, events_p: Dict[str, jnp.ndarray],
                      extra: Optional[Dict[str, jnp.ndarray]] = None
                      ) -> Dict[str, jnp.ndarray]:
    """Evaluate every capture-free condition for a whole [T] event lane in
    ONE vectorized pass outside the scan → {__gate_i: [T] bool} columns.

    Capture-free programs never read the slot captures (spec.cond_free,
    proven statically by plan/nfa_compiler), so evaluating them against a
    zero capture context is exact and uniform over K.  `extra` carries
    per-pattern parameter scalars in bank mode."""
    free = [i for i, f in enumerate(spec.cond_free) if f]
    if not free:
        return {}
    R, C = max(spec.n_rows, 1), max(spec.n_caps, 1)
    zero_caps = jnp.zeros((1, R, C), jnp.float32)

    def one(ev):
        if extra:
            ev = {**ev, **extra}
        return jnp.stack([jnp.asarray(spec.cond_fns[i](ev, zero_caps)[0],
                                      bool) for i in free])
    g = jax.vmap(one)(events_p)                  # [T, n_free]
    return {_gate_key(i): g[:, j] for j, i in enumerate(free)}


def _pad_block_t(events_p: Dict[str, jnp.ndarray], batch_b: int):
    """Pad the time axis up to a batch_b multiple.  Padding rows are
    invalid (__valid False — every transition/arm is gated on it) and
    repeat the LAST event's timestamp, so the only unconditional per-tick
    pass (within expiry) re-runs at a time it already ran at and kills
    nothing new: the carry stays bit-identical to the unpadded scan."""
    T = int(events_p["__ts"].shape[0])
    ticks = -(-T // batch_b) if T else 0
    pad = ticks * batch_b - T
    if not pad:
        return events_p, T, ticks

    def pad_leaf(name, v):
        if name == "__ts":
            fill = jnp.broadcast_to(v[T - 1], (pad,))
        else:
            fill = jnp.zeros((pad,) + v.shape[1:], v.dtype)
        return jnp.concatenate([v, fill], axis=0)
    return ({k: pad_leaf(k, v) for k, v in events_p.items()}, T, ticks)


class _StepState:
    """Mutable per-event slot arrays threaded through the unit loop."""

    def __init__(self, spec: NfaSpec, carry: Dict, K: int):
        self.spec = spec
        self.st = carry["slot_state"]
        self.start = carry["slot_start"]
        self.enter = carry["slot_enter"]
        self.seq = carry["slot_seq"]
        self.arm_seq = carry["arm_seq"]
        self.caps = carry["captures"]
        self.dropped = carry["dropped"]
        self.cnt_cur = carry.get("cnt_cur")
        self.cnt_prev = carry.get("cnt_prev")
        self.seq_froze = carry.get("seq_froze")
        self.lmask = carry.get("lmask")
        self.deadline = carry.get("deadline")
        # this step's additions to the lane's ABSENT_CTR (plain
        # zeros until an absent unit's code adds a traced count)
        self.actr = carry.get("absent_ctr")
        self.n_armed = self.n_fired = self.n_inblock = self.n_killed = 0
        # and to its COUNT_CTR
        self.cctr = carry.get("count_ctr")
        self.c_armed = self.c_appended = self.c_forwarded = \
            self.c_frozen = 0
        self.armed_total = carry.get("armed_total")
        self.m_mask = jnp.zeros((K,), bool)
        self.m_ts = jnp.zeros((K,), jnp.int32)
        self.m_enter = jnp.zeros((K,), jnp.int32)
        self.m_seq = jnp.zeros((K,), jnp.int32)
        # captures snapshotted AT COMPLETION — a trailing-every re-arm may
        # clear group rows in the live slot after the match is recorded
        R, C = self.caps.shape[1], self.caps.shape[2]
        self.m_caps = jnp.zeros((K, R, C), jnp.float32)
        # mid-chain `every` clone requests collected during land():
        # group start → (source mask, source rank by pre-land (enter, seq))
        self.spawn: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]] = {}

    def _pending_rank(self, pred):
        """Rank `pred` slots by their pending-list order (enter, seq) —
        the oracle's append order for re-arm clones and fork clones."""
        e, sq = self.enter, self.seq
        less = (e[None, :] < e[:, None]) | \
            ((e[None, :] == e[:, None]) & (sq[None, :] < sq[:, None]))
        return jnp.sum(pred[None, :] & less, axis=1)

    def _clear_group_logical_rows(self, caps, sel_or_range, g0, g1):
        """Zero the logical-side capture rows of units[g0..g1] — the
        oracle's re-arm/fork clone clears LOGICAL sides (addEveryState);
        simple rows are overwritten on the next match and stay.
        sel_or_range: [K] bool (applied per-slot) or None (whole array)."""
        spec = self.spec
        log_rows = [r for u in spec.units[g0:g1 + 1]
                    for r in (u.row_a, u.row_b)
                    if u.kind == "logical" and r >= 0]
        if not log_rows:
            return caps
        R = caps.shape[-2]
        rm = np.zeros((R,), bool)
        rm[log_rows] = True
        mask = jnp.asarray(rm)[None, :, None]
        if sel_or_range is not None:
            mask = sel_or_range[:, None, None] & mask
        return jnp.where(mask, jnp.float32(0), caps)

    def land(self, pred, j_from: int, base_ts, fwd_cnt=None, fwd_dead=None):
        """Advance `pred` slots out of unit j_from at time base_ts.

        fwd_cnt: forwarded count for count-unit exits (stays live unless
        fwd_dead).  base_ts may be scalar (event ts) or [K] (deadlines)."""
        spec = self.spec
        t, live0, completed = _land_static(spec, j_from)
        for g0, g1 in spec.mid_every:
            if j_from == g1:
                # fork request: rank sources by pre-land pending order so
                # the clones append in oracle order (see alloc_clones)
                rank = self._pending_rank(pred)
                old_m, old_r = self.spawn.get(g0, (None, None))
                if old_m is not None:       # a second land on the same g1
                    rank = rank + jnp.sum(old_m.astype(jnp.int32))
                    pred_all = old_m | pred
                    rank = jnp.where(pred, rank, old_r)
                    self.spawn[g0] = (pred_all, rank)
                else:
                    self.spawn[g0] = (pred, rank)
        if completed:
            self.m_mask = self.m_mask | pred
            self.m_ts = jnp.where(pred, base_ts, self.m_ts)
            self.m_caps = jnp.where(pred[:, None, None], self.caps,
                                    self.m_caps)
            # oracle emission order for same-event completions follows the
            # last unit's pending-list insertion order
            self.m_enter = jnp.where(pred, self.enter, self.m_enter)
            self.m_seq = jnp.where(pred, self.seq, self.m_seq)
            if spec.tail_every_start >= 0:
                # trailing `every`: the match is emitted AND the partial
                # re-arms at the group start, keeping its pre-group
                # captures (the reference's nextEveryStatePreProcessor
                # loop, StreamPostStateProcessor.java:66-68); group-side
                # captures are overwritten by the next firing
                te = spec.tail_every_start
                self.st = jnp.where(pred, te, self.st)
                # the oracle APPENDS re-armed clones to the pending list in
                # emission order, so future same-ts ties must rank them
                # after older entries and in their prior pending order:
                # fresh seq = counter + rank by prior (enter, seq)
                rank = self._pending_rank(pred)
                self.seq = jnp.where(pred, self.arm_seq + rank, self.seq)
                self.arm_seq = self.arm_seq + \
                    jnp.sum(pred.astype(jnp.int32))
                self.enter = jnp.where(pred, base_ts, self.enter)
                if self.lmask is not None:
                    self.lmask = jnp.where(pred, 0, self.lmask)
                self.caps = self._clear_group_logical_rows(
                    self.caps, pred, te, len(spec.units) - 1)
                # count units are compile-rejected alongside trailing
                # every; pre-group absent deadlines are never revisited
            else:
                self.st = jnp.where(pred, -1, self.st)
            if live0 and self.cnt_prev is not None:
                # trailing min-0 count: match emitted on arrival, slot dies
                pass
            return
        self.st = jnp.where(pred, t, self.st)
        self.enter = jnp.where(pred, base_ts, self.enter)
        if self.lmask is not None:
            self.lmask = jnp.where(pred, 0, self.lmask)
        if self.cnt_prev is not None:
            if fwd_cnt is not None:
                dead = fwd_dead if fwd_dead is not None else \
                    jnp.zeros_like(pred)
                self.cnt_prev = jnp.where(
                    pred, jnp.where(dead, -1, fwd_cnt), self.cnt_prev)
            elif live0:
                self.cnt_prev = jnp.where(pred, 0, self.cnt_prev)
            else:
                self.cnt_prev = jnp.where(pred, -1, self.cnt_prev)
            self.cnt_cur = jnp.where(pred, 0, self.cnt_cur)
        if spec.units[t].kind == "absent":
            self.deadline = jnp.where(
                pred, base_ts + spec.units[t].waiting_ms, self.deadline)
            self.count_armed(pred)

    def count_armed(self, pred):
        self.n_armed = self.n_armed + jnp.sum(pred.astype(jnp.int32))

    def fire_deadlines(self, now, gate, by_timer=None):
        """Every `not … for t` deadline due at or before `now` fires, for
        the slots `gate` admits: the slot lands AT ITS DEADLINE (match
        timestamp, next unit's entry time, a cascaded deadline's base),
        and `within` is judged at the deadline, not at `now` — a slot
        whose deadline lies past `within` of its start dies instead.
        Ascending unit order cascades an absence chain in one pass.
        by_timer: the pass is a host TIMER row's (else a block's own
        clock: counted as fired in-block)."""
        spec = self.spec
        for j, u in enumerate(spec.units):
            if u.kind != "absent":
                continue
            due = gate & (self.st == j) & (self.deadline <= now)
            if spec.within_ms is not None and j >= 1:
                late = due & (self.deadline - self.start > spec.within_ms)
                self.st = jnp.where(late, -1, self.st)
                due = due & ~late
            self.land(due, j, self.deadline)
            n = jnp.sum(due.astype(jnp.int32))
            self.n_fired = self.n_fired + n
            self.n_inblock = self.n_inblock + (
                n if by_timer is None else jnp.where(by_timer, 0, n))

    def to_carry(self) -> Dict[str, jnp.ndarray]:
        out = {"slot_state": self.st, "slot_start": self.start,
               "slot_enter": self.enter, "slot_seq": self.seq,
               "arm_seq": self.arm_seq, "captures": self.caps,
               "dropped": self.dropped}
        if self.cnt_cur is not None:
            out["cnt_cur"] = self.cnt_cur
            out["cnt_prev"] = self.cnt_prev
        if self.seq_froze is not None:
            out["seq_froze"] = self.seq_froze
        if self.lmask is not None:
            out["lmask"] = self.lmask
        if self.deadline is not None:
            out["deadline"] = self.deadline
        if self.actr is not None:
            out["absent_ctr"] = self.actr + jnp.stack(
                [jnp.asarray(n, jnp.int32) for n in
                 (self.n_armed, self.n_fired, self.n_inblock,
                  self.n_killed)])
        if self.cctr is not None:
            out["count_ctr"] = self.cctr + jnp.stack(
                [jnp.asarray(n, jnp.int32) for n in
                 (self.c_armed, self.c_appended, self.c_forwarded,
                  self.c_frozen)])
        if self.armed_total is not None:
            out["armed_total"] = self.armed_total
        return out

    def count_chains(self, forwarded=None, frozen=None):
        """Chains that reached their unit's min / its max this step."""
        if forwarded is not None:
            self.c_forwarded = self.c_forwarded + \
                jnp.sum(forwarded.astype(jnp.int32))
        if frozen is not None:
            self.c_frozen = self.c_frozen + jnp.sum(frozen.astype(jnp.int32))

    def write_all(self, pred, row: int, ev_rows):
        """Write every lane of `row` for `pred` slots."""
        if row < 0:
            return
        R = self.caps.shape[1]
        sel = pred[:, None, None] & \
            (jnp.arange(R)[None, :, None] == row)
        self.caps = jnp.where(sel, ev_rows[row][None, None, :], self.caps)

    def write_count(self, pred_first, pred_last, row: int, ev_rows, new_n):
        """Count-row append: first bank on the first append, last bank +
        __n lane on every append; e[last-j] banks shift behind the last
        bank (deepest first, BEFORE the new value lands) and e[k] banks
        capture the append that brings the chain to k+1 elements."""
        self.c_armed = self.c_armed + jnp.sum(pred_first.astype(jnp.int32))
        self.c_appended = self.c_appended + \
            jnp.sum(pred_last.astype(jnp.int32))
        if row < 0:
            return
        spec = self.spec
        R, C = self.caps.shape[1], self.caps.shape[2]
        lane = jnp.arange(C)
        nf = spec.n_first[row]
        first_lanes = lane < nf
        nl = spec.n_lane[row]
        n_l = spec.n_last[row] if spec.n_last else 0
        last_lanes = (lane >= nf) & (lane < nf + n_l) & \
            ((lane != nl) if nl >= 0 else True)
        row_sel = (jnp.arange(R)[None, :, None] == row)
        ev = ev_rows[row][None, None, :]
        mb = spec.lastk_banks[row] if spec.lastk_banks else ()
        src = spec.m_src[row] if spec.m_src else ()
        if mb and src:
            L = len(src)
            starts = {j: st for (j, st) in mb}
            for j, start in sorted(mb, reverse=True):
                src_lanes = np.asarray(
                    src if j == 1
                    else range(starts[j - 1], starts[j - 1] + L),
                    np.int32)
                dst_lanes = np.asarray(range(start, start + L), np.int32)
                vals = self.caps[:, row, src_lanes]
                cur = self.caps[:, row, dst_lanes]
                self.caps = self.caps.at[:, row, dst_lanes].set(
                    jnp.where(pred_last[:, None], vals, cur))
        self.caps = jnp.where(
            pred_first[:, None, None] & row_sel & first_lanes[None, None, :],
            ev, self.caps)
        self.caps = jnp.where(
            pred_last[:, None, None] & row_sel & last_lanes[None, None, :],
            ev, self.caps)
        for (k, start, ln) in (spec.idx_banks[row]
                               if spec.idx_banks else ()):
            predk = pred_last & (new_n == k + 1)
            sel = (lane >= start) & (lane < start + ln)
            self.caps = jnp.where(
                predk[:, None, None] & row_sel & sel[None, None, :],
                ev, self.caps)
        if nl >= 0:
            nsel = pred_last[:, None, None] & row_sel & \
                (lane == nl)[None, None, :]
            self.caps = jnp.where(
                nsel, new_n.astype(jnp.float32)[:, None, None], self.caps)

    def clear_slot(self, pred):
        self.caps = jnp.where(pred[:, None, None],
                              jnp.float32(0), self.caps)

    def alloc_clones(self, g0: int, spawn, rank, ts):
        """Fork mid-chain `every` clones: for each source slot in `spawn`,
        place a new partial at unit g0 carrying the source's captures
        (group-side logical rows cleared — the oracle's addEveryState
        clone) and chain-start timestamp (within runs from the original
        first event).  Sources ranked by pre-land pending order fill free
        slots in that order; unplaceable clones count as drops (the
        engine's grow-and-replay reruns the chunk on a bigger ring)."""
        spec = self.spec
        K = spawn.shape[0]
        n_spawn = jnp.sum(spawn.astype(jnp.int32))
        free = (self.st < 0) & ~self.m_mask
        free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        by_rank = jnp.zeros((K,), jnp.int32).at[
            jnp.where(spawn, rank, K)].set(jnp.arange(K, dtype=jnp.int32),
                                           mode="drop")
        src = by_rank[jnp.clip(free_rank, 0, K - 1)]
        fill = free & (free_rank < n_spawn)
        self.st = jnp.where(fill, g0, self.st)
        self.start = jnp.where(fill, self.start[src], self.start)
        caps_src = self.caps[src]
        g1 = next(g1 for (s0, g1) in spec.mid_every if s0 == g0)
        caps_src = self._clear_group_logical_rows(caps_src, None, g0, g1)
        self.caps = jnp.where(fill[:, None, None], caps_src, self.caps)
        self.enter = jnp.where(fill, ts, self.enter)
        self.seq = jnp.where(fill, self.arm_seq + free_rank, self.seq)
        self.arm_seq = self.arm_seq + n_spawn
        self.dropped = self.dropped + \
            jnp.maximum(n_spawn - jnp.sum(free.astype(jnp.int32)), 0)
        if self.lmask is not None:
            self.lmask = jnp.where(fill, 0, self.lmask)
        if self.cnt_cur is not None:
            self.cnt_cur = jnp.where(fill, 0, self.cnt_cur)
            self.cnt_prev = jnp.where(fill, -1, self.cnt_prev)


def _one_partition_step(spec: NfaSpec, carry: Dict, event):
    """Step one partition's slot ring over one event.

    event: cols dict of scalars + __ts/__stream/__valid
    returns (new_carry, (match_mask [K], match_caps [K, R, C],
    match_ts [K]))"""
    units = spec.units
    S = len(units)
    K = spec.n_slots
    ts = event["__ts"]
    valid = event["__valid"]
    stream = event["__stream"]

    s = _StepState(spec, carry, K)

    # telemetry leaf rides the carry untouched by the match math: every
    # contribution below is a NEW reduction over masks the transition
    # logic already computes, so match outputs stay bit-identical
    tel = carry.get("telem") if spec.telemetry else None
    tel_exp = jnp.int32(0)

    # ---- absent deadline pass: the clock reaches an event's timestamp,
    # and every timer due by then fires, BEFORE the event is routed
    # (upstream's playback order: InputHandler.send sets the clock, the
    # Scheduler sends its TIMER events, then the event goes in).  So an
    # event meets a deadline at or before its own timestamp as already
    # fired, by event time alone, whatever other lanes saw: an arrival on
    # the `not` stream kills only with ts < deadline, and a slot the
    # deadline moved on may consume THIS event at its new unit.  Lanes
    # with no event at or past a deadline are served by the block's
    # closing pass (build_block_step).  Before the within expiry below,
    # which is judged at the event's time.
    # (A SEQUENCE keeps the order it had, below: its partials at an
    # absent unit live and die by the per-event stabilize barrier.)
    have0 = jnp.any(s.st == 0) if spec.lead_absent else None
    by_timer = stream == -2 if s.deadline is not None else None
    if s.deadline is not None and not spec.is_sequence:
        s.fire_deadlines(ts, valid, by_timer)

    # ---- within expiry (reference isExpired :104-113 — start-state
    # partials are exempt: a half-filled leading pair or accumulating
    # kleene start never expires, only later units enforce `within`)
    if spec.within_ms is not None:
        expired = (s.st >= 1) & (ts - s.start > spec.within_ms)
        if spec.eps_start:
            # the empty-kleene start partial (leading min-0) sits at unit
            # 1 but IS a start-state partial — exempt
            expired = expired & ~((s.st == 1) & (s.cnt_prev == 0))
        if tel is not None:
            tel_exp = jnp.sum(expired.astype(jnp.int32))
        s.st = jnp.where(expired, -1, s.st)

    # ---- leading absent ensure-arm: the oracle re-initializes the start
    # absent partial whenever its pending list is empty (absent_tick
    # initialize + init_start), so exactly one partial waits at unit 0
    # with a live deadline; arrivals below kill + re-arm it in place
    if spec.lead_absent:
        # REAL events only: the oracle's ticks stop after a successful
        # confirmation until an arrival (or fresh scheduling) restarts
        # them — re-arming on an injected TIMER row would chain
        # confirmations the reference never produces.  `have0` is the
        # lane BEFORE this event's deadline pass: a start partial that
        # just confirmed is replaced at the next real event, not this one
        want0 = valid & (stream != -2) & ~have0
        free0 = (s.st < 0) & ~s.m_mask
        armed0 = (want0 & jnp.any(free0)) & \
            (jnp.arange(K) == jnp.argmax(free0))
        s.clear_slot(armed0)
        s.st = jnp.where(armed0, 0, s.st)
        s.deadline = jnp.where(armed0, ts + spec.units[0].waiting_ms,
                               s.deadline)
        s.count_armed(armed0)
        s.start = jnp.where(armed0, ts, s.start)
        s.enter = jnp.where(armed0, ts, s.enter)
        s.seq = jnp.where(armed0, s.arm_seq, s.seq)
        s.arm_seq = s.arm_seq + jnp.where(jnp.any(armed0), 1, 0)
        if s.lmask is not None:
            s.lmask = jnp.where(armed0, 0, s.lmask)
        if s.cnt_cur is not None:
            s.cnt_cur = jnp.where(armed0, 0, s.cnt_cur)
            s.cnt_prev = jnp.where(armed0, -1, s.cnt_prev)
        s.dropped = s.dropped + jnp.where(want0 & ~jnp.any(free0), 1, 0)

    # ---- SEQUENCE early deadline pass: a deadline that coincides with
    # (or precedes) an event's timestamp fires BEFORE that event
    # stabilizes the sequence — a due `not … for t` confirms the absence
    # even though the arriving event would clear the pending list (see
    # the stabilize barrier below); fired slots advance and may consume
    # THIS event at their new unit
    if spec.is_sequence and s.deadline is not None:
        s.fire_deadlines(ts, valid, by_timer)

    # ---- SEQUENCE stabilize barrier for absent units: the oracle clears
    # every unit's pending list BEFORE each real event (stabilizeStates →
    # resetState), so a partial waiting at a `not … for t` unit survives
    # only an event-free gap — any arriving event (even a non-matching
    # one) breaks the sequence before the deadline could fire; a deadline
    # at or before the event's timestamp has fired above.  Timer rows
    # (stream -2) do not stabilize.
    if spec.is_sequence and _has(spec, "absent"):
        absent_u = np.asarray([u.kind == "absent" for u in spec.units] +
                              [False], bool)
        at_absent = jnp.asarray(absent_u)[jnp.clip(s.st, 0, S)]
        kill0 = valid & (stream != -2) & (s.st >= 0) & at_absent
        s.st = jnp.where(kill0, -1, s.st)

    # ---- leading min-0 kleene: the start partial lives at unit 1 with an
    # empty, live-appending kleene chain (the reference parks the shared
    # StateEvent in BOTH the count's and the successor's pending lists —
    # epsilon closure at arm time).  Ensure exactly one such virgin
    # (cnt_prev == 0) exists; re-created here after the previous one
    # advanced (every mode) — eligible from this event on
    if spec.eps_start:
        # exactly one start chain: unit 1 is only ever occupied by the
        # shared start StateEvent (virgin, accumulating, or frozen at
        # max) — the reference start partial sits in BOTH the count's and
        # the successor's pending lists, never duplicated; re-init only
        # after it advances out
        if spec.is_sequence:
            # the oracle re-inits whenever the start's new-list is empty:
            # a LIVE chain (appending, cnt_prev >= 0) occupies it, a
            # frozen-at-max chain (cnt_prev == -1) does not — the frozen
            # partial keeps waiting at unit 1 while a fresh virgin arms
            have = jnp.any((s.st == 1) & (s.cnt_prev >= 0))
        else:
            have = jnp.any(s.st == 1)
        want = valid & ~have
        if spec.arm_once:
            want = want & (s.armed_total == 0)
        freev = (s.st < 0) & ~s.m_mask
        armed_v = (want & jnp.any(freev)) & \
            (jnp.arange(K) == jnp.argmax(freev))
        s.clear_slot(armed_v)
        s.st = jnp.where(armed_v, 1, s.st)
        s.cnt_cur = jnp.where(armed_v, 0, s.cnt_cur)
        s.cnt_prev = jnp.where(armed_v, 0, s.cnt_prev)
        s.start = jnp.where(armed_v, ts, s.start)
        s.enter = jnp.where(armed_v, ts, s.enter)
        s.seq = jnp.where(armed_v, s.arm_seq, s.seq)
        s.arm_seq = s.arm_seq + jnp.where(jnp.any(armed_v), 1, 0)
        if s.lmask is not None:
            s.lmask = jnp.where(armed_v, 0, s.lmask)
        if spec.arm_once:
            s.armed_total = s.armed_total + \
                jnp.where(want & jnp.any(freev), 1, 0)
        s.dropped = s.dropped + jnp.where(want & ~jnp.any(freev), 1, 0)

    st_pre = s.st
    # pre-event live-append state: the arm occupancy gate must see the
    # chain as the ORACLE's barrier did (a freeze during this event's
    # live-append frees the start only at the NEXT event's re-init)
    cnt_prev_pre = s.cnt_prev

    # ---- condition programs over the current capture state (hoisted
    # capture-free gates ride the event dict — see _eval_conds)
    conds = _eval_conds(spec, event, s.caps)
    ev_rows = _event_rows(spec, event)

    advanced = jnp.zeros((K,), bool)
    appended = jnp.zeros((K,), bool)
    # every-min-0 SEQUENCE: set when the empty-chain virgin closes this
    # event — the re-init pair's every-clone (oracle _min_count_reached →
    # addEveryState) then appends the SAME event, seeding the next chain
    seed_req = None
    # SEQUENCE single-admission: a unit's new-list admits ONE partial per
    # event (StreamPreStateProcessor.addState empty-list guard) and units
    # process in REVERSE order, so a chain re-adding itself into the
    # count unit's list (CountPost, cnt >= min and cnt != max) blocks the
    # every-arm forwarded there the same event
    seq_block_arm = jnp.zeros((), bool)

    # ---- main transitions, one unit at a time (statically unrolled)
    for j, u in enumerate(units):
        at = valid & (st_pre == j)
        if u.kind == "simple":
            ok = at & (stream == u.stream_a) & conds[u.cond_a]
            if spec.eps_start and j == 1:
                if spec.is_sequence and s.seq_froze is not None:
                    # a virgin created right after a freeze is closer-
                    # blocked for its creation event (see make_carry)
                    ok = ok & ~((s.cnt_prev == 0) & (s.seq_froze > 0))
                if spec.is_sequence and spec.is_every:
                    seed_req = jnp.any(ok & (s.cnt_prev == 0))
                # empty-kleene start partial advancing directly: its
                # chain-start timestamp is THIS event (a normal arm would
                # have set start = ts)
                s.start = jnp.where(ok & (s.cnt_prev == 0), ts, s.start)
            s.write_all(ok, u.row_a, ev_rows)
            s.land(ok, j, ts)
            advanced = advanced | ok
        elif u.kind == "logical":
            bitA = (s.lmask & 1) > 0
            bitB = (s.lmask & 2) > 0
            # a side already satisfied ignores further matches (the
            # reference removes the partial from that side's pending list)
            newA = at & (stream == u.stream_a) & conds[u.cond_a] & ~bitA
            newB = at & (stream == u.stream_b) & conds[u.cond_b] & ~bitB
            if not u.is_and:
                # or: when ONE event satisfies both sides, the left side
                # captures and completes first — the right side's partner
                # is already gone (oracle: left pre-processor runs first,
                # LogicalPreStateProcessor partner removal)
                newB = newB & ~newA
            s.write_all(newA, u.row_a, ev_rows)
            s.write_all(newB, u.row_b, ev_rows)
            haveA, haveB = bitA | newA, bitB | newB
            done = at & ((haveA & haveB) if u.is_and else (newA | newB))
            s.lmask = jnp.where(newA, s.lmask | 1, s.lmask)
            s.lmask = jnp.where(newB, s.lmask | 2, s.lmask)
            s.land(done, j, ts)
            advanced = advanced | done
            appended = appended | ((newA | newB) & ~done)
        elif u.kind == "count":
            # accumulating phase: slot sits at j while cnt < min
            ok = at & (stream == u.stream_a) & conds[u.cond_a]
            c2 = s.cnt_cur + 1
            s.write_count(ok & (s.cnt_cur == 0), ok, u.row_a, ev_rows, c2)
            s.cnt_cur = jnp.where(ok, c2, s.cnt_cur)
            reach = ok & (c2 == u.min_count)
            dead = reach & (c2 == u.max_count)
            s.land(reach, j, ts, fwd_cnt=c2, fwd_dead=dead)
            s.count_chains(reach, dead)
            advanced = advanced | reach
            if spec.is_sequence and j == 1 and \
                    units[0].kind == "simple":
                seq_block_arm = seq_block_arm | \
                    jnp.any(ok & (c2 >= u.min_count) & (c2 != u.max_count))
            if spec.is_sequence:
                appended = appended | (ok & (c2 >= u.min_count))
            else:
                appended = appended | ok
        elif u.kind == "absent":
            # an actual arrival on the `not` stream kills the partial
            # (AbsentStreamPostStateProcessor: never advances)
            kill = at & (stream == u.stream_a) & conds[u.cond_a]
            s.n_killed = s.n_killed + jnp.sum(kill.astype(jnp.int32))
            if j == 0 and spec.lead_absent:
                # leading absent: the kill re-arms in place with a fresh
                # deadline (oracle add_every_state on arrival — the wait
                # restarts from the arrival)
                s.deadline = jnp.where(kill, ts + u.waiting_ms,
                                       s.deadline)
                s.count_armed(kill)
                s.start = jnp.where(kill, ts, s.start)
                s.enter = jnp.where(kill, ts, s.enter)
            else:
                s.st = jnp.where(kill, -1, s.st)

    # ---- live-append phase: a forwarded count keeps growing its last
    # bank while the next unit is pending (the reference shares one
    # StateEvent between the kleene chain and the next pending list,
    # CountPreStateProcessor.removeIfNextStateProcessed)
    if s.cnt_prev is not None:
        for j, u in enumerate(units):
            if u.kind != "count":
                continue
            t, _live0, completed = _land_static(spec, j)
            if completed:
                continue        # trailing count: match already emitted
            live = valid & (st_pre == t) & (s.cnt_prev >= 0) & ~advanced
            ok = live & (stream == u.stream_a) & conds[u.cond_a] & \
                (s.cnt_prev < u.max_count)
            if spec.eps_start and j == 0:
                # first append into the leading kleene: the chain starts
                # here (within runs from the first captured event)
                s.start = jnp.where(ok & (s.cnt_prev == 0), ts, s.start)
            c2 = s.cnt_prev + 1
            s.write_count(ok & (s.cnt_prev == 0), ok, u.row_a, ev_rows, c2)
            # max reached → the reference marks stateChanged and stops
            froze = ok & (c2 == u.max_count)
            s.count_chains(ok & (s.cnt_prev == 0) if u.min_count == 0
                           else None, froze)
            s.cnt_prev = jnp.where(ok, c2, s.cnt_prev)
            s.cnt_prev = jnp.where(froze, -1, s.cnt_prev)
            appended = appended | ok
            if j == 0 and spec.eps_start and spec.is_sequence and \
                    s.seq_froze is not None:
                s.seq_froze = jnp.where(
                    valid, jnp.any(froze).astype(jnp.int32),
                    s.seq_froze)
            if spec.is_sequence and j == 1 and \
                    units[0].kind == "simple":
                # CountPost re-adds while cnt != max — that re-add owns
                # the count's new-list slot for this event
                seq_block_arm = seq_block_arm | jnp.any(ok & ~froze)

    # ---- SEQUENCE strict contiguity: partials at simple/count/logical
    # units must advance or append on every event or die (per-event
    # resetState barriers, StreamPreStateProcessor.java:263-279); an `and`
    # partial with one side already satisfied waits for its partner, and
    # absent partials survive (processAndReturn keeps them)
    if spec.is_sequence:
        # injected TIMER rows (stream -2) are not events: the oracle's
        # absent_tick never runs the per-event reset barrier
        is_real = valid & (stream != -2)
        # logical units are strict too: a sequence partial whose or/and
        # unit matched NEITHER side on this event dies — EXCEPT an and-
        # partial that already satisfied one side (the oracle's logical
        # pending entry survives while waiting for its partner)
        strict = np.asarray([u.kind in ("simple", "count", "logical")
                             for u in units] + [False], bool)
        logical_u = np.asarray([u.kind == "logical" for u in units] +
                               [False], bool)
        at_strict = jnp.asarray(strict)[jnp.clip(st_pre, 0, S)]
        at_logical = jnp.asarray(logical_u)[jnp.clip(st_pre, 0, S)]
        half_done = at_logical & (s.lmask != 0)
        kill = is_real & (st_pre >= 0) & (s.st >= 0) & at_strict & \
            ~(advanced | appended) & ~half_done
        s.st = jnp.where(kill, -1, s.st)

    # ---- arming a fresh partial at unit 0 (reference `every` re-arm /
    # start-state init)
    u0 = units[0]
    # conditions at unit 0 never read captures → uniform over K: lane 0
    occ_gate = ~jnp.any((st_pre >= 0) & (st_pre <= spec.every_group_end)) \
        if (spec.is_every and spec.every_group_end > 0) or \
        u0.kind in ("count", "logical") else jnp.bool_(True)
    if spec.is_sequence and u0.kind == "count" and not spec.eps_start \
            and not spec.dead_start:
        # SEQUENCE leading min-1 kleene: the shared StateEvent re-occupies
        # the start's new-list on every successful append, so the oracle
        # re-inits only once the chain freezes at max, closes, or dies —
        # and only at the NEXT event's barrier, hence the PRE-event
        # cnt_prev (a freeze during this event frees nothing yet)
        t0, _l0, _c0 = _land_static(spec, 0)
        occ = (st_pre >= 0) & (st_pre <= spec.every_group_end)
        if not _c0:
            occ = occ | ((st_pre == t0) & (cnt_prev_pre >= 0))
        occ_gate = ~jnp.any(occ)
    if spec.arm_once:
        occ_gate = occ_gate & (s.armed_total == 0)

    arm = jnp.zeros((), bool)
    arm_state = jnp.int32(0)
    arm_lmask = jnp.int32(0)
    arm_cnt_cur = jnp.int32(0)
    arm_cnt_prev = jnp.int32(-1)
    arm_match = jnp.zeros((), bool)
    arm_row_writes: List[int] = []      # rows the arming event captures
    arm_n1_rows: List[int] = []         # count rows written with __n = 1

    if u0.kind == "simple":
        c0 = valid & (stream == u0.stream_a) & conds[u0.cond_a][0]
        t, _live0, completed = _land_static(spec, 0)
        arm = c0
        arm_row_writes.append(u0.row_a)
        if completed:
            arm_match = c0
        else:
            arm_state = jnp.int32(t)
            arm_cnt_prev = jnp.int32(0 if _live0 else -1)
    elif u0.kind == "count" and spec.eps_start:
        pass        # leading min-0: arming is the ensure-virgin block above
    elif u0.kind == "count" and spec.dead_start:
        pass        # SEQUENCE min>=2: dead shape, never arms (see NfaSpec)
    elif u0.kind == "count":
        if spec.is_sequence:
            # a SEQUENCE re-arm is a FRESH empty chain: self e[last] refs
            # in the kleene's own condition must see a virgin context
            # (empty last bank, __cnt == 0), not slot 0's stale captures
            zero_caps = jnp.zeros((1,) + s.caps.shape[1:], s.caps.dtype)
            cond0 = _cond_on(spec, event, u0.cond_a, zero_caps)
        else:
            cond0 = conds[u0.cond_a][0]
        c0 = valid & (stream == u0.stream_a) & cond0
        arm = c0
        arm_row_writes.append(u0.row_a)
        arm_n1_rows.append(u0.row_a)
        if u0.min_count <= 1:
            t, _live0, completed = _land_static(spec, 0)
            if completed:
                arm_match = c0
            else:
                arm_state = jnp.int32(t)
                arm_cnt_prev = jnp.where(
                    jnp.bool_(u0.max_count == 1), jnp.int32(-1),
                    jnp.int32(1))
        else:
            arm_state = jnp.int32(0)
            arm_cnt_cur = jnp.int32(1)
    elif u0.kind == "logical":
        cA = valid & (stream == u0.stream_a) & conds[u0.cond_a][0]
        cB = valid & (stream == u0.stream_b) & conds[u0.cond_b][0]
        if not u0.is_and:
            cB = cB & ~cA       # or: same-event double match, left wins
        arm = cA | cB
        both = (cA & cB) if u0.is_and else (cA | cB)
        t, _live0, completed = _land_static(spec, 0)
        arm_match = both if completed else jnp.zeros((), bool)
        arm_state = jnp.where(both, jnp.int32(-2 if completed else t),
                              jnp.int32(0))
        # a completed leading unit advances with a CLEAN mask — stale side
        # bits would leak into a later logical unit (land() zeroes lmask
        # on advance; the arm path must match)
        arm_lmask = jnp.where(both, 0,
                              jnp.where(cA, 1, 0) | jnp.where(cB, 2, 0))
        arm_cnt_prev = jnp.int32(0 if _live0 else -1)
        # capture whichever side(s) matched
        arm_row_writes = []     # handled below with per-side predicates
    else:                       # absent at start: planner rejects
        arm = jnp.zeros((), bool)

    do_arm = arm & occ_gate & ~seq_block_arm
    free = (s.st < 0) & ~s.m_mask
    first_free = jnp.argmax(free)
    any_free = jnp.any(free)
    armed_here = (do_arm & any_free) & (jnp.arange(K) == first_free)
    s.dropped = s.dropped + jnp.where(do_arm & ~any_free, 1, 0)
    if spec.arm_once:
        s.armed_total = s.armed_total + jnp.where(do_arm & any_free, 1, 0)
        if spec.is_sequence:
            # a non-every sequence is single-shot: its one initial partial
            # dies forever on the first real event it cannot advance on
            # (StreamPreStateProcessor.init runs once; SEQUENCE barriers
            # clear the pending list every event; TIMER rows don't count)
            virgin_dies = valid & (stream != -2) & (s.armed_total == 0)
            s.armed_total = jnp.where(virgin_dies, 2, s.armed_total)

    s.clear_slot(armed_here)
    if u0.kind == "logical":
        cA = valid & (stream == u0.stream_a) & conds[u0.cond_a][0]
        cB = valid & (stream == u0.stream_b) & conds[u0.cond_b][0]
        if not u0.is_and:
            cB = cB & ~cA       # or: left side captures on a double match
        s.write_all(armed_here & cA, u0.row_a, ev_rows)
        s.write_all(armed_here & cB, u0.row_b, ev_rows)
    else:
        for r in arm_row_writes:
            if r in arm_n1_rows:
                s.write_count(armed_here, armed_here, r, ev_rows,
                              jnp.full((K,), 1, jnp.int32))
                s.count_chains(armed_here if u0.min_count <= 1 else None,
                               armed_here if u0.max_count == 1 else None)
            else:
                s.write_all(armed_here, r, ev_rows)
    emit_arm = armed_here & arm_match
    s.m_mask = s.m_mask | emit_arm
    s.m_ts = jnp.where(emit_arm, ts, s.m_ts)
    s.m_caps = jnp.where(emit_arm[:, None, None], s.caps, s.m_caps)
    s.m_enter = jnp.where(emit_arm, ts, s.m_enter)
    s.m_seq = jnp.where(emit_arm, s.arm_seq, s.m_seq)
    live_arm = armed_here & ~arm_match
    s.st = jnp.where(live_arm, arm_state, s.st)
    s.start = jnp.where(live_arm | emit_arm, ts, s.start)
    s.enter = jnp.where(live_arm, ts, s.enter)
    s.seq = jnp.where(live_arm, s.arm_seq, s.seq)
    s.arm_seq = s.arm_seq + jnp.where(jnp.any(armed_here), 1, 0)
    if s.lmask is not None:
        s.lmask = jnp.where(live_arm, arm_lmask, s.lmask)
    if s.cnt_cur is not None:
        s.cnt_cur = jnp.where(live_arm, arm_cnt_cur, s.cnt_cur)
        s.cnt_prev = jnp.where(live_arm, arm_cnt_prev, s.cnt_prev)
    if s.deadline is not None and len(units) > 1:
        t0, _l0, _c0 = _land_static(spec, 0)
        if t0 < S and units[t0].kind == "absent":
            s.deadline = jnp.where(live_arm & (s.st == t0),
                                   ts + units[t0].waiting_ms, s.deadline)
            s.count_armed(live_arm & (s.st == t0))

    # ---- every-min-0 SEQUENCE seed: the virgin closed this event while
    # the event also passes the kleene condition — the oracle's re-init
    # every-clone appends it, so the NEXT chain starts with THIS event
    if seed_req is not None:
        # the seed clone starts an EMPTY chain — virgin condition context
        # (self e[last] refs read nothing), like the count re-arm above
        zero_caps = jnp.zeros((1,) + s.caps.shape[1:], s.caps.dtype)
        c0 = valid & (stream == u0.stream_a) & \
            _cond_on(spec, event, u0.cond_a, zero_caps)
        want_seed = seed_req & c0
        free_s = (s.st < 0) & ~s.m_mask
        seeded = (want_seed & jnp.any(free_s)) & \
            (jnp.arange(K) == jnp.argmax(free_s))
        s.clear_slot(seeded)
        s.st = jnp.where(seeded, 1, s.st)
        s.write_count(seeded, seeded, u0.row_a, ev_rows,
                      jnp.full((K,), 1, jnp.int32))
        mx1 = u0.max_count == 1
        s.count_chains(seeded, seeded if mx1 else None)
        s.cnt_prev = jnp.where(seeded, jnp.int32(-1 if mx1 else 1),
                               s.cnt_prev)
        s.cnt_cur = jnp.where(seeded, 0, s.cnt_cur)
        s.start = jnp.where(seeded, ts, s.start)
        s.enter = jnp.where(seeded, ts, s.enter)
        s.seq = jnp.where(seeded, s.arm_seq, s.seq)
        s.arm_seq = s.arm_seq + jnp.where(jnp.any(seeded), 1, 0)
        s.dropped = s.dropped + jnp.where(want_seed & ~jnp.any(free_s),
                                          1, 0)
        if mx1 and s.seq_froze is not None:
            # a max-1 seed freezes immediately: its forward blocks the
            # next virgin's closer-eligibility (see make_carry)
            s.seq_froze = jnp.where(jnp.any(seeded), 1, s.seq_froze)

    # ---- mid-chain `every` clone allocation (requests collected by
    # land() during the unit loop; placed after arming so pending-list
    # append order matches the oracle: armed partial first, clones after)
    for g0 in sorted(s.spawn):
        spm, rk = s.spawn[g0]
        s.alloc_clones(g0, spm, rk, ts)

    # ---- SEQUENCE: a slot that reached an absent unit during this event
    # with a deadline already due
    if spec.is_sequence and s.deadline is not None:
        s.fire_deadlines(ts, valid, by_timer)

    match_caps = s.m_caps

    out = s.to_carry()
    if tel is not None:
        # gate pass/fail per unit: reuse the conds/st_pre/stream values
        # the transitions consumed — an "eligible" slot sat at unit j on
        # the matching stream; "pass" means its condition program fired
        tel_pass, tel_fail = [], []
        for j, u in enumerate(units):
            at = valid & (st_pre == j)
            if u.cond_a >= 0:
                elig = at & (stream == u.stream_a)
                hit = elig & conds[u.cond_a]
            else:
                elig = jnp.zeros((K,), bool)
                hit = elig
            if u.cond_b >= 0:
                elig_b = at & (stream == u.stream_b)
                hit = hit | (elig_b & conds[u.cond_b])
                elig = elig | elig_b
            tel_pass.append(jnp.sum(hit.astype(jnp.int32)))
            tel_fail.append(jnp.sum((elig & ~hit).astype(jnp.int32)))
        occ = jnp.sum((s.st[None, :] == jnp.arange(S)[:, None])
                      .astype(jnp.int32), axis=1)
        out["telem"] = jnp.concatenate([
            occ,                                    # live occupancy gauge
            tel[S:2 * S] + jnp.stack(tel_pass),
            tel[2 * S:3 * S] + jnp.stack(tel_fail),
            (tel[3 * S] + tel_exp)[None],           # within-expiry drops
        ])
    return out, (s.m_mask, match_caps, s.m_ts, s.m_enter, s.m_seq)


def build_block_step(spec: NfaSpec, batch_b: Optional[int] = None,
                     unroll: int = 1):
    """Returns jittable fn(carry, block) → (carry, matches).

    block: dict of [P, T] arrays — per-partition event lanes, time-major
    scan; `__valid` masks padding.  matches: (mask [P, T, K],
    caps [P, T, K, R, C], ts [P, T, K], enter [P, T, K], seq [P, T, K]).

    Round 6 — fatter scan ticks.  The legacy scan ran T ticks, each a
    chain of ~10² small fused ops whose issue LATENCY (not throughput)
    set the pace (docs/perf_notes.md §roofline accounting).  Two
    composable restructurings, both gated by ``SIDDHI_TPU_NFA_BATCH``
    (default B=4; ``=1`` is the kill switch → this exact legacy path):

      1. **Condition hoisting** — capture-free condition programs
         (spec.cond_free, the common case) are evaluated for the WHOLE
         block in one vectorized [T] pass outside the scan; the scan body
         reads precomputed boolean gates and shrinks to the truly
         sequential masked state update.
      2. **B-event micro-batching** — each scan tick consumes
         ``batch_b`` events (a static unroll of the per-event transition
         over the precomputed gates), cutting tick count T→⌈T/B⌉ so the
         fixed per-tick issue cost amortizes and XLA can overlap the
         independent per-lane work of the B sub-steps.

    Sub-steps are the SAME per-event function, so match semantics are
    bit-identical by construction (randomized parity across B × pattern
    shapes is asserted in tests/test_nfa_batch.py)."""
    B = resolve_batch_b(spec.batch_b or None) if batch_b is None \
        else resolve_batch_b(batch_b)

    def scan_events(carry_p, events_p):
        def step(c, ev):
            return _one_partition_step(spec, c, ev)
        if B == 1:
            return jax.lax.scan(step, carry_p, events_p, unroll=unroll)
        events_p = {**events_p, **_hoist_cond_gates(spec, events_p)}
        events_p, T, ticks = _pad_block_t(events_p, B)
        chunks = {k: v.reshape((ticks, B) + v.shape[1:])
                  for k, v in events_p.items()}

        def tick(c, evs):
            # inner scan fully unrolled (length B == unroll B): the step
            # body traces ONCE and XLA inlines B copies into the outer
            # tick — the outer sequential chain genuinely shrinks to
            # ⌈T/B⌉ ticks (asserted at the jaxpr level in tests)
            return jax.lax.scan(step, c, evs, unroll=B)
        carry2, ys = jax.lax.scan(tick, carry_p, chunks, unroll=unroll)
        ys = tuple(y.reshape((ticks * B,) + y.shape[2:])[:T] for y in ys)
        return carry2, ys

    def per_partition(carry_p, events_p):
        if CLOCK_KEY not in events_p:
            return scan_events(carry_p, events_p)
        # the block carries its own clock (its latest event time, over all
        # lanes): after the lane's last event every deadline the clock has
        # reached fires, in this same step and for lanes with no event
        # too, so no host TIMER row has to be stepped for it.  The rows
        # ride the lane's last time row (a slot cannot complete twice at
        # one row: one that matched there stays for the next block), so
        # the block is no deeper than its events made it.
        events_p = dict(events_p)
        clock = events_p.pop(CLOCK_KEY)[0]
        carry2, (mask, caps, ts, enter, seq) = scan_events(carry_p,
                                                           events_p)
        s = _StepState(spec, carry2, spec.n_slots)
        s.fire_deadlines(clock, ~mask[-1])
        for g0 in sorted(s.spawn):
            s.alloc_clones(g0, *s.spawn[g0], clock)
        m = s.m_mask
        ys = (mask.at[-1].set(mask[-1] | m),
              caps.at[-1].set(jnp.where(m[:, None, None], s.m_caps,
                                        caps[-1])),
              ts.at[-1].set(jnp.where(m, s.m_ts, ts[-1])),
              enter.at[-1].set(jnp.where(m, s.m_enter, enter[-1])),
              seq.at[-1].set(jnp.where(m, s.m_seq, seq[-1])))
        out = s.to_carry()
        if "telem" in carry2:
            out["telem"] = carry2["telem"]
        return out, ys

    def block_step(carry, block):
        return jax.vmap(per_partition)(carry, block)

    return block_step


def build_bank_step(spec: NfaSpec, ring: int = 0,
                    batch_b: Optional[int] = None):
    """N structurally-identical patterns (constants differ) × P partitions.

    Returns jittable fn(carry, block, params):
      carry:  NFA carry with a leading pattern axis [N, P, ...]
      block:  one [P, T] event block, shared by every pattern
      params: {param_name: [N]} per-pattern constant lanes

    ring == 0 → (carry, match_counts [N]): counts only; summing inside the
    scan keeps the [N, P, T, K] mask from materialising in HBM.

    ring > 0 → (carry, (match_counts [N], ring_cnt [N, ring],
    ring_pid [N, ring], ring_caps [N, ring, R, C], ring_ts [N, ring],
    ring_ok [N, ring])): a bounded per-pattern match-payload buffer — for
    up to `ring` matched partitions per block (those with the most
    matches), the capture rows + timestamp of a match from that
    partition's last matching event.  Counts stay exact; payloads beyond
    the ring are counted but not decoded.  This is the production alert
    payload the fleet path owes (reference matches carry the full
    StateEvent chain, query/output/callback/QueryCallback.java).

    Zero-copy design: touching the per-step match captures inside the scan
    forces XLA to double-buffer the whole captures carry every step (~20x
    throughput loss measured on v5e).  Instead the scan records only the
    last match's (ts, slot) scalars; captures are gathered from the FINAL
    carry after the scan — a completed match's capture rows stay in their
    slot until the slot is re-armed (clear_slot runs only on arming).
    `ring_ok` is False when the slot WAS re-armed after the match
    (slot_start moved past the match ts), i.e. the payload was overwritten
    and is dropped (still counted); with monotonically increasing block
    timestamps the check is exact, under repeated equal timestamps a
    same-ts re-arm can slip through as a stale payload.
    """

    B = resolve_batch_b(spec.batch_b or None) if batch_b is None \
        else resolve_batch_b(batch_b)

    def per_partition(carry_p, events_p, prm):
        def sub_step(c, ev):
            inner, acc, lmt, lmk = c
            inner2, (mm, *_rest) = _one_partition_step(
                spec, inner, {**ev, **prm})
            # accumulate in-carry: avoids a [N, P, T] stacked ys buffer
            acc2 = acc + jnp.sum(mm.astype(jnp.int32))
            if ring:
                # the EVENT's ts, not the per-slot match ts (m_ts): reading
                # m_ts forces the per-unit emission-bookkeeping chains XLA
                # otherwise dead-code-eliminates — 5.5x slower measured.
                # They only differ for absent-deadline completions, whose
                # payload ts then reads as the triggering event's time.
                hit = jnp.any(mm)
                lmt = jnp.where(hit, ev["__ts"], lmt)
                lmk = jnp.where(hit, jnp.argmax(mm).astype(jnp.int32), lmk)
            return (inner2, acc2, lmt, lmk)
        init = (carry_p, jnp.int32(0), jnp.int32(0), jnp.int32(0))
        if B == 1:
            def step(c, ev):
                return sub_step(c, ev), None
            (c2, acc, lmt, lmk), _ = jax.lax.scan(step, init, events_p)
            return c2, acc, lmt, lmk
        # fatter ticks (see build_block_step): hoist capture-free gates
        # for the whole lane, then consume B events per scan tick
        events_p = {**events_p,
                    **_hoist_cond_gates(spec, events_p, extra=prm)}
        events_p, _T, ticks = _pad_block_t(events_p, B)
        chunks = {k: v.reshape((ticks, B) + v.shape[1:])
                  for k, v in events_p.items()}

        def tick(c, evs):
            def inner(c2, ev):
                return sub_step(c2, ev), None
            c2, _ = jax.lax.scan(inner, c, evs, unroll=B)
            return c2, None
        (c2, acc, lmt, lmk), _ = jax.lax.scan(tick, init, chunks)
        return c2, acc, lmt, lmk

    def pattern_step(carry_n, prm, block):
        new_carry, counts, lmt, lmk = jax.vmap(
            per_partition, in_axes=(0, 0, None))(carry_n, block, prm)
        total = jnp.sum(counts)
        if not ring:
            return new_carry, total
        ring_cnt, ring_pid = jax.lax.top_k(counts, ring)
        sel_k = lmk[ring_pid]                              # [ring]
        ring_caps = new_carry["captures"][ring_pid, sel_k]
        ring_ts = lmt[ring_pid]
        # slot re-armed after the match → captures overwritten → drop
        ring_ok = new_carry["slot_start"][ring_pid, sel_k] <= ring_ts
        return new_carry, (total, ring_cnt, ring_pid, ring_caps, ring_ts,
                           ring_ok)

    def bank_step(carry, block, params):
        return jax.vmap(pattern_step, in_axes=(0, 0, None))(carry, params,
                                                            block)

    return bank_step


def build_super_bank_step(spec: NfaSpec, ring: int = 0,
                          batch_b: Optional[int] = None):
    """C homogeneous pattern chunks stepped as ONE dispatch.

    Returns jittable fn(carry, block, params):
      carry:  stacked bank carry [C, N, P, ...] (one array per leaf)
      block:  one [P, T] event block, shared by every chunk
      params: {param_name: [C, N]} stacked per-pattern constant lanes

    Semantically identical to running ``build_bank_step`` C times on the
    per-chunk slices (patterns never interact), but XLA sees a single
    executable and the runtime pays one launch per ingest block instead
    of C — the dispatch-side half of "fewer, fatter steps"."""
    bank = build_bank_step(spec, ring=ring, batch_b=batch_b)

    def super_step(carry, block, params):
        return jax.vmap(bank, in_axes=(0, None, 0))(carry, block, params)

    return super_step


def make_bank_carry(spec: NfaSpec, n_patterns: int,
                    n_partitions: int) -> Dict[str, jnp.ndarray]:
    c = make_carry(spec, n_partitions)
    return {k: jnp.broadcast_to(v[None], (n_patterns,) + v.shape)
            for k, v in c.items()}


def _same(a: np.ndarray, b: np.ndarray) -> bool:
    """``b`` is the array ``a``, or holds what ``a`` holds."""
    return a is b or (a.dtype == b.dtype and a.shape == b.shape
                      and np.array_equal(a, b))


class _Layout:
    """Where each event of a flat batch lies in its dense ``[P, T]``
    block, and, where they are kept, the planes scattered there so far:
    ``planes[name]`` lists (what the plane was scattered from, the
    plane)."""

    __slots__ = ("lanes", "pad_t_pow2", "shape", "row", "flat", "planes")

    def __init__(self, partition_ids: np.ndarray, n_partitions: int,
                 pad_t_pow2: bool, keep: bool):
        from ..native_ext import assign_rows
        self.lanes = partition_ids
        self.pad_t_pow2 = pad_t_pow2
        pids = np.ascontiguousarray(partition_ids, np.int32)
        self.row, _counts, T = assign_rows(pids, n_partitions)
        if pad_t_pow2:
            T = 1 << (T - 1).bit_length()
        self.shape = (n_partitions, T)
        # each event's cell of the block laid flat: one index array for
        # every plane, half the cost of indexing lane and row apart
        self.flat = pids.astype(np.intp) * T + self.row
        self.planes: Optional[Dict] = {} if keep else None

    def plane(self, name, source: np.ndarray, dtype, values: Callable):
        """The plane ``name`` of ``values(source)``, one per event -> (the
        plane, whether it was there: made from ``source`` or from an
        array equal to it).  A kept plane is read-only: the next caller
        with an equal source gets the same object."""
        made = () if self.planes is None else \
            self.planes.setdefault(name, [])
        for src, plane in made:
            if _same(src, source):
                return plane, True
        plane = np.zeros(self.shape, dtype)
        plane.reshape(-1)[self.flat] = values(source)
        if self.planes is not None:
            plane.flags.writeable = False
            made.append((source, plane))
        return plane, False


def _f32(col: np.ndarray) -> np.ndarray:
    return np.asarray(col, np.float32)


def _ts_offsets(base_ts: int, timestamps: np.ndarray) -> np.ndarray:
    return (np.asarray(timestamps, np.int64) - base_ts).astype(np.int32)


def _present(_lanes: np.ndarray) -> bool:
    return True


def _pack(lay: _Layout, columns: Dict[str, np.ndarray],
          timestamps: np.ndarray, stream_codes: np.ndarray,
          base_ts: int) -> Tuple[Dict[str, np.ndarray], int]:
    """The block of one flat batch in ``lay`` -> (block, how many of its
    planes were there already)."""
    planes = [(name, ("col", name), col, np.float32, _f32)
              for name, col in columns.items()]
    planes += [("__ts", ("__ts", base_ts), timestamps, np.int32,
                partial(_ts_offsets, base_ts)),
               ("__stream", "__stream", stream_codes, np.int32, np.asarray),
               ("__valid", "__valid", lay.lanes, bool, _present)]
    block: Dict[str, np.ndarray] = {}
    found = 0
    for key, name, source, dtype, values in planes:
        block[key], was = lay.plane(name, source, dtype, values)
        found += was
    return block, found


def pack_blocks(partition_ids: np.ndarray, columns: Dict[str, np.ndarray],
                timestamps: np.ndarray, stream_codes: np.ndarray,
                n_partitions: int, base_ts: int = 0,
                pad_t_pow2: bool = False, return_rows: bool = False):
    """Host-side: scatter a flat event batch into dense [P, T] lanes
    (T = max events of any partition in the batch; padding masked invalid;
    pad_t_pow2 rounds T up to a power of two so jit sees few distinct
    shapes).  return_rows additionally yields each input event's row index
    within its lane (for per-event output decode).

    The block is the caller's own: every plane made here, writeable.
    Several callers that pack one batch (the pattern queries of a
    partition) go through the batch's :class:`SharedPlanes` instead.

    This is the columnar replacement for the reference's per-key junction
    routing (partition/PartitionStreamReceiver.java:83-153)."""
    lay = _Layout(np.asarray(partition_ids), n_partitions, pad_t_pow2,
                  keep=False)
    block, _found = _pack(lay, columns, np.asarray(timestamps),
                          np.asarray(stream_codes), base_ts)
    if return_rows:
        return block, lay.row
    return block


class SharedPlanes:
    """The dense planes made of one flat event batch, kept with the
    batch (``EventChunk.factors``, core/keyfactor.py) for whoever packs
    it again: the pattern queries of a partition receive the same chunk,
    and their blocks are equal plane for plane as long as they saw the
    same chunks in the same order.  That is observed, never assumed.
    :meth:`pack` is ``pack_blocks`` with a memory: the row assignment and
    ``T`` are made once per lane array (and ``n_partitions``,
    ``pad_t_pow2``), a plane once per input, and a later caller whose
    input is the same object or compares equal (the lanes and dictionary
    codes of a query come from its own tables: a gather per query, equal
    until a restore, a growth or a later start makes them differ) gets
    the plane that is there; ``__ts`` is one per ``base_ts``.  A caller
    whose input differs makes its own plane, of that input only: a query
    that reads one more column adds that plane, lanes that differ give a
    block of the caller's own.  Kept planes are read-only, and every
    caller gets a dict of its own over them."""

    __slots__ = ("_layouts",)

    def __init__(self):
        self._layouts: List[_Layout] = []

    def pack(self, partition_ids: np.ndarray,
             columns: Dict[str, np.ndarray], timestamps: np.ndarray,
             stream_codes: np.ndarray, n_partitions: int,
             base_ts: int = 0, pad_t_pow2: bool = False
             ) -> Tuple[Dict[str, np.ndarray], int]:
        """-> (the block, how many of its planes an earlier caller had
        made)."""
        partition_ids = np.asarray(partition_ids)
        for lay in self._layouts:
            if lay.shape[0] == n_partitions and \
                    lay.pad_t_pow2 == pad_t_pow2 and \
                    _same(lay.lanes, partition_ids):
                break
        else:
            lay = _Layout(partition_ids, n_partitions, pad_t_pow2,
                          keep=True)
            self._layouts.append(lay)
        return _pack(lay, columns, np.asarray(timestamps),
                     np.asarray(stream_codes), base_ts)


def make_timer_block(n_partitions: int, ts_offset: int,
                     attr_names) -> Dict[str, np.ndarray]:
    """One virtual TIMER row per partition lane (stream code -2 matches no
    unit): drives absent-state deadlines and within expiry between real
    events (≙ the reference Scheduler's TIMER StreamEvents,
    util/Scheduler.java:180-211)."""
    block = {a: np.zeros((n_partitions, 1), np.float32) for a in attr_names}
    block["__ts"] = np.full((n_partitions, 1), ts_offset, np.int32)
    block["__stream"] = np.full((n_partitions, 1), -2, np.int32)
    block["__valid"] = np.ones((n_partitions, 1), bool)
    return block

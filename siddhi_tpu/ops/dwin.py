"""Device window kernels: sliding + tumbling window state as ring slabs.

The window buffer of record lives on device as left-aligned ring slabs
([P, W] payload banks + timestamps + fill), and each input chunk is one
jitted step that (a) computes every eviction / batch-flush VECTORIZED —
closed forms over the concatenated [carry ‖ chunk] stream (searchsorted /
cummax), no per-event host loop — and (b) emits the affected rows through
one compacted egress transfer (pack-with-cap, NFA-style).  The host
composes the reference's CURRENT/EXPIRED/RESET emission order from the
decoded refs (plan/dwin_compiler.py).

Eviction index math per kind (j = index in the concat stream of length
fill+T, FIFO order; t = chunk event index):

- length(n): entry j is displaced by arrival j+n → evict_t = j+n-fill
  (reference LengthWindowProcessor.java:68-90: displaced-by semantics).
- time(t): one cutoff per chunk (now = last ts): evicted iff carried and
  ts_j <= now - window (TimeWindowProcessor.java:118-142 collects expired
  once per batch before appending).
- externalTime(ts, t): evict_t[j] = first event index t with
  etime_t - window >= ts_j, clamped to arrivals after j
  (ExternalTimeWindowProcessor.java: per-event expiry on event time).
- timeLength(t, n): FIFO evictions; total evicted after event t is
  E(t) = max(timeE(t), fill+t+1-n) with timeE monotone — entry rank r is
  evicted at the first t with E(t) > r, by length iff the length bound is
  what crossed r (TimeLengthWindowProcessor.java).
- delay(t): emission (as CURRENT) at first t with now_t >= ts_j + delay
  (DelayWindowProcessor.java).
- lengthBatch(n): batches are consecutive n-blocks of the appended
  stream: batch_id = j // n (LengthBatchWindowProcessor.java).
- timeBatch(t) / externalTimeBatch(ts, t): flush boundaries are control
  state (host-scheduled); the kernel flushes the carried buffer at
  host-directed event positions (TimeBatchWindowProcessor.java).
- hopping(t, hop): ONE flush per step (the host dispatches a separate
  step per hop boundary — an entry can be CURRENT in many overlapping
  windows, which a single per-entry emit mask cannot express).  At a
  flush the window is the live entries with ts in (now - window, now];
  the exp plane carries the previous hop's window, whose entries with
  ts <= now - window emit EXPIRED (HopingWindowProcessor semantics).

Egress row schema (int32): [pool_idx, evict_t, cause, ts_off,
f-bank bitcast ×F, i-bank ×I]; tail row: [count, fill', exp_fill',
min_live_ts, overflow, pad...].  Causes: 1=time-expired, 2=length-
displaced, 3=batch-current, 4=carry-expired-batch, 5=delayed-current.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .compact import compact_indices

TS_NONE = np.int32(2 ** 31 - 1)      # "never" / empty sentinel
C_TIME, C_LEN, C_BATCH, C_EXPBATCH, C_DELAY = 1, 2, 3, 4, 5


class DwinSpec(NamedTuple):
    kind: str            # length|time|externalTime|timeLength|delay|
    #                      lengthBatch|timeBatch|externalTimeBatch|batch|
    #                      sort|session
    capacity: int        # ring capacity W (grow-and-replay on overflow)
    n_f: int             # f32 payload lanes
    n_i: int             # i32 payload lanes
    window_ms: int       # time span (0 for pure length kinds); session gap
    length: int          # count bound (0 for pure time kinds)
    sort_keys: tuple = ()  # sort kind: ((bank 0=f/1=i, lane, asc), ...) —
    #                        lex compare order; LONG attrs ride two (hi,
    #                        lo) entries whose lex order IS int64 order
    skey_lane: int = -1  # session kind: i32 lane holding the dict-encoded
    #                      session key (keyless apps encode one code)
    telemetry: bool = False  # @app:statistics(telemetry='true'): carry a
    #                      [P, 3] int32 telemetry leaf (fill gauge,
    #                      evictions total, overflow total) and append a
    #                      summary row to the egress buffer (before the
    #                      tail) — no extra D2H, emissions bit-identical
    hop_ms: int = 0      # hopping kind: emission period (window_ms is the
    #                      span); appended last to keep positional
    #                      construction stable


def make_dwin_carry(spec: DwinSpec, n_lanes: int) -> Dict[str, np.ndarray]:
    P, W = n_lanes, spec.capacity
    F, I = max(spec.n_f, 1), max(spec.n_i, 1)
    c = {"ring_f": np.zeros((P, W, F), np.float32),
         "ring_i": np.zeros((P, W, I), np.int32),
         "ring_ts": np.full((P, W), TS_NONE, np.int32),
         "fill": np.zeros((P,), np.int32)}
    if spec.kind in ("lengthBatch", "timeBatch", "externalTimeBatch",
                     "batch", "hopping"):
        c.update(exp_f=np.zeros((P, W, F), np.float32),
                 exp_i=np.zeros((P, W, I), np.int32),
                 exp_ts=np.full((P, W), TS_NONE, np.int32),
                 exp_fill=np.zeros((P,), np.int32))
    if spec.telemetry:
        # [fill gauge, evictions total, overflow total] per lane
        c["telem"] = np.zeros((P, 3), np.int32)
    return c


def _pool(carry, ev_f, ev_i, ev_ts, ev_valid, W):
    """Concat [carry-ring ‖ chunk] into the stream pool [P, M]."""
    pf = jnp.concatenate([carry["ring_f"], ev_f], axis=1)
    pi = jnp.concatenate([carry["ring_i"], ev_i], axis=1)
    pts = jnp.concatenate([carry["ring_ts"],
                           jnp.where(ev_valid, ev_ts, TS_NONE)], axis=1)
    P, M = pts.shape
    j = jnp.arange(M)[None, :]
    fill = carry["fill"][:, None]
    # concat slot j holds a live entry iff (carry slot < fill) or (chunk
    # slot valid); arrival rank = j for carry, fill + #valid-before for
    # chunk rows (chunk validity is a prefix per lane by construction)
    nv = jnp.sum(ev_valid.astype(jnp.int32), axis=1)[:, None]
    live = jnp.where(j < W, j < fill, j - W < nv)
    rank = jnp.where(j < W, j, fill + (j - W))
    return pf, pi, pts, live, rank, nv[:, 0]


def _rank_order(live, rank, M):
    """Gather order that left-aligns live entries by arrival rank."""
    key = jnp.where(live, rank, M + 1)
    return jnp.argsort(key, axis=1, stable=True)


def _gather(a, order):
    return jnp.take_along_axis(
        a, order.reshape(order.shape + (1,) * (a.ndim - 2)), axis=1) \
        if a.ndim > 2 else jnp.take_along_axis(a, order, axis=1)


def _new_ring(pf, pi, pts, keep, rank, W, F, I):
    """Left-align surviving entries into a fresh [P, W] ring."""
    P, M = pts.shape
    order = _rank_order(keep, rank, M)
    sf = _gather(pf, order)[:, :W]
    si = _gather(pi, order)[:, :W]
    sts = jnp.take_along_axis(jnp.where(keep, pts, TS_NONE), order,
                              axis=1)[:, :W]
    fill = jnp.sum(keep.astype(jnp.int32), axis=1)
    # entries beyond W were lost: overflow → host grows & replays
    overflow = fill > W
    sts = jnp.where(jnp.arange(W)[None, :] < fill[:, None], sts, TS_NONE)
    return sf, si, sts, jnp.minimum(fill, W), overflow


def _pack_egress(emit_mask, pool_idx, evict_t, cause, pts, pf, pi,
                 tail_vals, cap, telem_row=None):
    """[P, M] emission set → [cap+1, 4+F+I] compacted rows + tail.
    When `telem_row` (a [3] int32 summary) is given, one extra row is
    appended BEFORE the tail, so ``buf[-1]`` stays the tail row."""
    P, M = emit_mask.shape
    F = pf.shape[-1]
    I = pi.shape[-1]
    idx, count = compact_indices(emit_mask, cap)
    safe = jnp.maximum(idx, 0)

    def g(a):
        return a.reshape(-1)[safe][:, None].astype(jnp.int32)
    f_bits = jax.lax.bitcast_convert_type(
        pf.reshape(-1, F), jnp.int32)[safe]
    i_vals = pi.reshape(-1, I)[safe]
    rows = jnp.concatenate(
        [idx[:, None], g(evict_t), g(cause), g(pts), f_bits, i_vals],
        axis=1)
    tail = jnp.zeros((1, 4 + F + I), jnp.int32)
    tail = tail.at[0, 0].set(count)
    for k, v in enumerate(tail_vals):
        tail = tail.at[0, 1 + k].set(v)
    if telem_row is not None:
        trow = jnp.zeros((1, 4 + F + I), jnp.int32)
        trow = trow.at[0, :3].set(telem_row)
        return jnp.concatenate([rows, trow, tail], axis=0)
    return jnp.concatenate([rows, tail], axis=0)


def build_dwin_step(spec: DwinSpec):
    """→ step(carry, ev_f, ev_i, ev_ts, ev_valid, now, directive, cap)
    jittable; returns (new_carry, egress buffer).  `directive` is the
    kind-specific host control input (flush count / boundary ids)."""
    W = spec.capacity
    F, I = max(spec.n_f, 1), max(spec.n_i, 1)
    kind = spec.kind

    def step(carry, ev_f, ev_i, ev_ts, ev_valid, now, directive, cap):
        pf, pi, pts, live, rank, nv = _pool(carry, ev_f, ev_i, ev_ts,
                                            ev_valid, W)
        P, M = pts.shape
        fill = carry["fill"]
        j = jnp.arange(M)[None, :]
        is_carry = j < W
        new_carry = dict(carry)

        def telem(nfill, emit_mask, ovf_mask):
            """Accumulate the telemetry leaf; returns the [3] summary row
            for _pack_egress (None when telemetry is off).  Pure addition
            over masks the kernel already computed — emissions and ring
            contents are untouched."""
            tel = carry.get("telem")
            if tel is None:
                return None
            ev = jnp.sum(emit_mask.astype(jnp.int32), axis=1)
            nt = jnp.stack([nfill, tel[:, 1] + ev,
                            tel[:, 2] + ovf_mask.astype(jnp.int32)],
                           axis=1)
            new_carry["telem"] = nt
            return jnp.stack([jnp.max(nt[:, 0]), jnp.sum(nt[:, 1]),
                              jnp.sum(nt[:, 2])])

        if kind == "sort":
            # Keep the bottom-N by (sort key, arrival rank); each
            # overflowing arrival evicts the current lex-max (reference
            # SortWindowProcessor.java).  Greedy max-eviction telescopes:
            # the set after event t is bottom_N(pool through t), so entry
            # x is evicted at the FIRST t where >= N lex-smaller entries
            # have arrived — the N-th smallest arrival step among x's
            # lex-predecessors (an [M, M] order statistic; dwin rings are
            # single-lane and modest, the quadratic mask is cheap).
            n = spec.length
            less = jnp.zeros((P, M, M), bool)
            eq = jnp.ones((P, M, M), bool)
            for (bank, lane, asc) in spec.sort_keys:
                v = pf[:, :, lane] if bank == 0 else pi[:, :, lane]
                a = v[:, :, None]           # x
                b = v[:, None, :]           # y
                lt = (b < a) if asc else (b > a)
                less = less | (eq & lt)
                eq = eq & (b == a)
            # tie: equal keys keep buffer order — the NEWEST (largest
            # rank) is evicted first, so older counts as smaller
            less = less | (eq & (rank[:, None, :] < rank[:, :, None]))
            less = less & live[:, None, :]
            arr = jnp.where(is_carry, -1, rank - fill[:, None])  # [P, M]
            BIG = jnp.int32(2 ** 30)
            a_mask = jnp.where(less, arr[:, None, :], BIG)
            a_sorted = jnp.sort(a_mask, axis=2)
            idx = min(n - 1, M - 1)
            tN = a_sorted[:, :, idx]
            evict_t = jnp.maximum(tN, arr)
            evicted = live & (tN < BIG) & (evict_t < nv[:, None]) if \
                n - 1 < M else jnp.zeros((P, M), bool)
            cause = jnp.full((P, M), C_LEN, jnp.int32)
            keep = live & ~evicted
            sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                                W, F, I)
            new_carry.update(ring_f=sf, ring_i=si, ring_ts=sts,
                             fill=nfill)
            buf = _pack_egress(evicted, j, evict_t, cause, pts, pf, pi,
                               (jnp.max(nfill), jnp.int32(0), TS_NONE,
                                jnp.max(ovf.astype(jnp.int32))), cap,
                               telem_row=telem(nfill, evicted, ovf))
            return new_carry, buf

        if kind == "session":
            # Per-key gap sessions (reference SessionWindowProcessor):
            # the host expires due sessions BEFORE appending the chunk
            # (its _expire_sessions(now) runs first, so same-key chunk
            # events start a FRESH session).  A carried entry's session
            # is due when its key's last activity + gap <= now; evicted
            # rows carry (last + gap) in the evict_t column as the
            # EXPIRED emission timestamp offsets.
            key = pi[:, :, spec.skey_lane]
            carry_live = live & is_carry
            same = (key[:, None, :] == key[:, :, None]) & \
                carry_live[:, None, :]
            NEG = jnp.int32(-(2 ** 30))
            last = jnp.max(jnp.where(same, pts[:, None, :], NEG), axis=2)
            expired = carry_live & (last + spec.window_ms <= now[:, None])
            evict_ts = last + spec.window_ms
            cause = jnp.full((P, M), C_TIME, jnp.int32)
            keep = live & ~expired
            sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                                W, F, I)
            new_carry.update(ring_f=sf, ring_i=si, ring_ts=sts,
                             fill=nfill)
            # the host re-arms its gap timer at (reported min + gap), so
            # report the min over live entries of their KEY'S last
            # activity in the post-step ring — a session expires at
            # last+gap, not at its oldest event + gap.  Reporting the
            # min event ts re-armed the timer at an instant where
            # nothing can expire (oldest event's key stayed active),
            # which in playback degenerated to 1 ms timer crawl —
            # 50k+ dispatches on a 60-event stream.
            w_live = jnp.arange(W)[None, :] < nfill[:, None]
            k_new = si[:, :, spec.skey_lane]
            same_new = (k_new[:, None, :] == k_new[:, :, None]) & \
                w_live[:, None, :]
            last_new = jnp.max(jnp.where(same_new, sts[:, None, :], NEG),
                               axis=2)
            live_min = jnp.min(jnp.where(w_live, last_new, TS_NONE))
            buf = _pack_egress(expired, j, evict_ts, cause, pts, pf, pi,
                               (jnp.max(nfill), jnp.int32(0), live_min,
                                jnp.max(ovf.astype(jnp.int32))), cap,
                               telem_row=telem(nfill, expired, ovf))
            return new_carry, buf

        if kind in ("length", "time", "externalTime", "timeLength",
                    "delay"):
            if kind == "length":
                n = spec.length
                # displaced by arrival of rank+n → valid when that arrival
                # exists in this chunk
                evict_rank = rank + n
                evict_t = evict_rank - fill[:, None]     # chunk index
                evicted = live & (evict_t < nv[:, None]) & (evict_t >= 0)
                cause = jnp.full((P, M), C_LEN, jnp.int32)
            elif kind == "time":
                cutoff = now[:, None] - spec.window_ms
                evicted = live & is_carry & (pts <= cutoff)
                evict_t = jnp.zeros((P, M), jnp.int32)
                cause = jnp.full((P, M), C_TIME, jnp.int32)
            elif kind == "externalTime":
                # first chunk event whose etime - window >= entry ts, and
                # strictly after the entry's own arrival
                # int32 throughout: the host rebase guard keeps live offsets
                # below TS_NONE - window - 1, and dead (TS_NONE) entries
                # are masked by `live` before any wrapped value matters
                tgt = pts + spec.window_ms
                ets = jnp.where(ev_valid, ev_ts, TS_NONE)
                evict_t = jax.vmap(
                    lambda e, t: jnp.searchsorted(e, t, side="left"))(
                        ets, tgt).astype(jnp.int32)
                after_self = rank - fill[:, None] + 1   # chunk rows only
                evict_t = jnp.maximum(evict_t, jnp.maximum(after_self, 0))
                evicted = live & (evict_t < nv[:, None])
                cause = jnp.full((P, M), C_TIME, jnp.int32)
            elif kind == "timeLength":
                n = spec.length
                ets64 = jnp.where(ev_valid, ev_ts, TS_NONE)
                # timeE(t): #entries with ts <= now_t - window among those
                # arrived up to t.  Entries are FIFO by ts (arrival order);
                # carried entries sorted; chunk appended in order.
                # int32 throughout: the host rebase guard keeps live offsets
                # below TS_NONE - window - 1, and dead (TS_NONE) entries
                # are masked by `live` before any wrapped value matters
                tgt = pts + spec.window_ms
                t_evict = jax.vmap(
                    lambda e, t: jnp.searchsorted(e, t, side="left"))(
                        ets64, tgt).astype(jnp.int32)
                after_self = rank - fill[:, None] + 1
                t_evict = jnp.maximum(t_evict, jnp.maximum(after_self, 0))
                # length bound: E_len(t) = fill + t + 1 - n → rank r
                # crosses at t = r + n - fill
                l_evict = rank + n - fill[:, None]
                l_evict = jnp.maximum(l_evict,
                                      jnp.maximum(after_self, 0))
                evict_t = jnp.minimum(t_evict, l_evict)
                # timer steps (no events): time-expire against `now`
                by_now = (nv[:, None] == 0) & \
                    (pts + spec.window_ms <= now[:, None])
                evicted = live & ((evict_t < nv[:, None]) | by_now)
                cause = jnp.where(t_evict <= l_evict, C_TIME,
                                  C_LEN).astype(jnp.int32)
            else:                                        # delay
                # due = carried entries with ts <= now - delay, collected
                # once per step BEFORE appending (DelayWindowProcessor:
                # same shape as time, but re-emitted as CURRENT at their
                # original timestamps)
                cutoff = now[:, None] - spec.window_ms
                evicted = live & is_carry & (pts <= cutoff)
                evict_t = jnp.zeros((P, M), jnp.int32)
                cause = jnp.full((P, M), C_DELAY, jnp.int32)
            keep = live & ~evicted
            sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                                W, F, I)
            new_carry.update(ring_f=sf, ring_i=si, ring_ts=sts,
                             fill=nfill)
            live_min = jnp.min(jnp.where(
                jnp.arange(W)[None, :] < nfill[:, None], sts, TS_NONE))
            buf = _pack_egress(evicted, j, evict_t, cause, pts, pf, pi,
                               (jnp.max(nfill), jnp.int32(0), live_min,
                                jnp.max(ovf.astype(jnp.int32))), cap,
                               telem_row=telem(nfill, evicted, ovf))
            return new_carry, buf

        if kind == "hopping":
            # ONE hop boundary per step: the host dispatches a separate
            # kernel step per boundary (a row can be CURRENT in many
            # overlapping windows, so a single per-entry emit id cannot
            # express multi-flush membership).  `directive[:, 0] > 0`
            # marks a flush step at instant `now`; append-only steps
            # just pool the chunk.  At a flush the window is the live
            # entries with ts in (now - window, now]; the exp plane
            # holds the PREVIOUS hop's window, whose entries with
            # ts <= now - window emit EXPIRED (restamped at the
            # boundary by the host composer — HopingWindowProcessor).
            flushing = directive[:, 0] > 0
            cutoff = now[:, None] - spec.window_ms
            keep = live & (~flushing[:, None] | (pts > cutoff))
            sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                                W, F, I)
            cur_emit = keep & flushing[:, None]
            cause = jnp.full((P, M), C_BATCH, jnp.int32)
            eslot = jnp.arange(W)[None, :]
            exp_emit = (eslot < carry["exp_fill"][:, None]) & \
                flushing[:, None] & (carry["exp_ts"] <= cutoff)
            exp_cause = jnp.full((P, W), C_EXPBATCH, jnp.int32)
            post_exp_fill = jnp.where(flushing, nfill, carry["exp_fill"])
            new_carry.update(
                ring_f=sf, ring_i=si, ring_ts=sts, fill=nfill,
                exp_f=jnp.where(flushing[:, None, None], sf,
                                carry["exp_f"]),
                exp_i=jnp.where(flushing[:, None, None], si,
                                carry["exp_i"]),
                exp_ts=jnp.where(flushing[:, None], sts,
                                 carry["exp_ts"]),
                exp_fill=post_exp_fill)
            all_mask = jnp.concatenate([cur_emit, exp_emit], axis=1)
            all_idx = jnp.concatenate([j, M + eslot], axis=1)
            all_t = jnp.zeros((P, M + W), jnp.int32)
            all_cause = jnp.concatenate([cause, exp_cause], axis=1)
            all_ts = jnp.concatenate([pts, carry["exp_ts"]], axis=1)
            all_f = jnp.concatenate([pf, carry["exp_f"]], axis=1)
            all_i = jnp.concatenate([pi, carry["exp_i"]], axis=1)
            buf = _pack_egress(all_mask, all_idx, all_t, all_cause,
                               all_ts, all_f, all_i,
                               (jnp.max(nfill), jnp.max(post_exp_fill),
                                TS_NONE,
                                jnp.max(ovf.astype(jnp.int32))), cap,
                               telem_row=telem(nfill, all_mask, ovf))
            return new_carry, buf

        # ---------------- batch kinds ----------------
        # `directive` is [P, T] int32: the flush id each chunk row belongs
        # to (host-computed control state — next_emit / window_end);
        # `now` rides the per-lane count of flushes completed this step.
        if kind == "lengthBatch":
            n = spec.length
            batch_id = rank // n                        # tumbling blocks
            total = fill[:, None] + nv[:, None]
            n_done = (fill + nv) // n
            flushed = live & (batch_id < n_done[:, None])
            # exp state follows the LAST flushed batch (always non-empty)
            last_id = n_done - 1
        elif kind in ("timeBatch", "externalTimeBatch"):
            batch_id = jnp.concatenate(
                [jnp.zeros((P, W), jnp.int32), directive], axis=1)
            n_done = now.astype(jnp.int32)
            flushed = live & (batch_id < n_done[:, None])
            if kind == "timeBatch":
                # expired_batch = the last flush's batch even when empty
                # (TimeBatchWindowProcessor._flush assigns unconditionally)
                last_id = n_done - 1
            else:
                # expired_batch only replaced by a NON-EMPTY batch
                # (ExternalTimeBatchWindowProcessor._flush quirk)
                last_id = jnp.max(jnp.where(flushed, batch_id, -1),
                                  axis=1)
        else:                                           # batch()
            # whole chunk replaces the ring; the previous ring emits as
            # the expired batch (no separate exp plane needed)
            has_ev = (nv > 0)[:, None]
            emit = live & ((is_carry & has_ev) | ~is_carry)
            cause = jnp.where(is_carry, C_EXPBATCH,
                              C_BATCH).astype(jnp.int32)
            keep = live & (~is_carry | (is_carry & ~has_ev))
            sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                                W, F, I)
            new_carry.update(ring_f=sf, ring_i=si, ring_ts=sts,
                             fill=nfill)
            buf = _pack_egress(emit, j, jnp.zeros((P, M), jnp.int32),
                               cause, pts, pf, pi,
                               (jnp.max(nfill), jnp.int32(0), TS_NONE,
                                jnp.max(ovf.astype(jnp.int32))), cap,
                               telem_row=telem(nfill, emit, ovf))
            return new_carry, buf

        cause = jnp.full((P, M), C_BATCH, jnp.int32)
        keep = live & ~flushed
        in_last = flushed & (batch_id == last_id[:, None]) & \
            (last_id >= 0)[:, None]
        sf, si, sts, nfill, ovf = _new_ring(pf, pi, pts, keep, rank,
                                            W, F, I)
        ef, ei, ets_, efill, eovf = _new_ring(pf, pi, pts, in_last,
                                              rank, W, F, I)
        any_flush = n_done > 0
        post_exp_fill = jnp.where(any_flush, efill, carry["exp_fill"])
        new_carry.update(
            ring_f=sf, ring_i=si, ring_ts=sts, fill=nfill,
            exp_f=jnp.where(any_flush[:, None, None], ef,
                            carry["exp_f"]),
            exp_i=jnp.where(any_flush[:, None, None], ei,
                            carry["exp_i"]),
            exp_ts=jnp.where(any_flush[:, None], ets_, carry["exp_ts"]),
            exp_fill=post_exp_fill)
        # carried expired-batch rows ride the exp plane region: reuse the
        # pool layout by emitting them with pool_idx offset M (host maps
        # idx >= M to the exp plane)
        eslot = jnp.arange(W)[None, :]
        exp_emit = (eslot < carry["exp_fill"][:, None]) & \
            any_flush[:, None]
        exp_cause = jnp.full((P, W), C_EXPBATCH, jnp.int32)
        all_mask = jnp.concatenate([flushed, exp_emit], axis=1)
        all_idx = jnp.concatenate([j, M + eslot], axis=1)
        all_t = jnp.concatenate([batch_id, jnp.zeros((P, W), jnp.int32)],
                                axis=1)
        all_cause = jnp.concatenate([cause, exp_cause], axis=1)
        all_ts = jnp.concatenate([pts, carry["exp_ts"]], axis=1)
        all_f = jnp.concatenate([pf, carry["exp_f"]], axis=1)
        all_i = jnp.concatenate([pi, carry["exp_i"]], axis=1)
        buf = _pack_egress(all_mask, all_idx, all_t, all_cause, all_ts,
                           all_f, all_i,
                           (jnp.max(nfill), jnp.max(post_exp_fill), TS_NONE,
                            jnp.max((ovf | eovf).astype(jnp.int32))),
                           cap, telem_row=telem(nfill, all_mask,
                                                ovf | eovf))
        return new_carry, buf

    return step

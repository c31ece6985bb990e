"""Grouped window / running aggregation kernel — the device QuerySelector.

Replaces the reference's per-group HashMap of aggregator objects
(query/selector/QuerySelector.java:171+, GroupByKeyGenerator.java) with a
dense per-(lane, group) state slab:

    ring_f/ring_i [P, W, V]  — window contents per value expression
                               (length windows; W == 0 → no window)
    ring_gid [P, W]          — each slot's group id
    fsum/isum [P, G, V]      — per-group running sums (paired lanes)
    gcnt      [P, G]         — per-group live counts (shared: every
                               aggregate sees the same accepted events)
    *min/*max [P, G, V]      — add-only extrema (minForever/maxForever,
                               and plain min/max when there is no window)

    step = lax.scan over T  ∘  vmap over P

P is the partition-lane axis (1 for non-partitioned queries): groups of
different lanes are distinct aggregator states, exactly like the
reference's per-key QuerySelector clones.  V indexes the DISTINCT value
expressions of the select (sum(volume), avg(price), ... — each gets its
own lane; float-typed and int-typed expressions ride separate banks so
both stay exact).  An arriving event updates its group's state (evicting
the window's oldest entry from ITS group first) and emits that group's
aggregates — the reference's CURRENT/EXPIRED algebra netted per event.

Numeric exactness:
  - float bank: f32 values with TWO-FLOAT (TwoSum/Dekker) running sums —
    (hi, lo) pairs whose f64 sum tracks the true sum to ~2^-48 relative
    error, so egress agrees with the host oracle's float64 accumulation
    at float32 precision (plain Kahan is NOT enough: its runsum alone can
    sit one f32 ulp off, which the conformance corpus' f32-normalised
    equality catches).
  - int bank: i32 values with EXACT sums via a hi/lo split: every value
    v = (v >> 16) * 65536 + (v & 65535); both partial sums stay inside
    i32 exactly while a group holds < 32768 live entries (windows are
    plan-capped; the no-window running mode guards the live count at
    egress), and the host reassembles int64 = hi * 65536 + lo — this is
    what lets `sum(volume long)` run on device with exact integer
    equality (reference SumAttributeAggregatorExecutor long/int
    variants); |v| >= 2^31 is a rejected data error.

Windowed min/max need no decrement state: the ring materialises the
window, so extrema are masked reductions over the arriving group's slots
(same dissolution of the sliding-extremum problem as windowed_agg.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INT_EXACT_MAX = 1 << 31        # |int value| bound for i32 device lanes
INT_GROUP_MAX = 1 << 15        # live entries per group for exact int sums
_SPLIT = 65536                 # int hi/lo split base (16 bits)

I32_MAX = np.int32(np.iinfo(np.int32).max)
I32_MIN = np.int32(np.iinfo(np.int32).min)


class GroupedAggCarry(NamedTuple):
    ring_f: jnp.ndarray     # [P, W, VF] f32
    ring_i: jnp.ndarray     # [P, W, VI] i32
    ring_gid: jnp.ndarray   # [P, W] i32
    pos: jnp.ndarray        # [P] i32
    cnt: jnp.ndarray        # [P] i32
    fsum_hi: jnp.ndarray    # [P, G, VF] f32 two-float hi
    fsum_lo: jnp.ndarray    # [P, G, VF] f32 two-float lo
    isum_hi: jnp.ndarray    # [P, G, VI] i32 split hi
    isum_lo: jnp.ndarray    # [P, G, VI] i32 split lo
    gcnt: jnp.ndarray       # [P, G] i32
    fmin_f: jnp.ndarray     # [P, G, VF] f32 add-only min
    fmax_f: jnp.ndarray     # [P, G, VF] f32 add-only max
    fmin_i: jnp.ndarray     # [P, G, VI] i32 add-only min
    fmax_i: jnp.ndarray     # [P, G, VI] i32 add-only max


def make_grouped_carry(n_lanes: int, window: int, n_groups: int,
                       n_float: int, n_int: int) -> GroupedAggCarry:
    P, W, G, VF, VI = n_lanes, window, n_groups, n_float, n_int
    return GroupedAggCarry(
        ring_f=jnp.zeros((P, W, VF), jnp.float32),
        ring_i=jnp.zeros((P, W, VI), jnp.int32),
        ring_gid=jnp.full((P, W), -1, jnp.int32),
        pos=jnp.zeros((P,), jnp.int32),
        cnt=jnp.zeros((P,), jnp.int32),
        fsum_hi=jnp.zeros((P, G, VF), jnp.float32),
        fsum_lo=jnp.zeros((P, G, VF), jnp.float32),
        isum_hi=jnp.zeros((P, G, VI), jnp.int32),
        isum_lo=jnp.zeros((P, G, VI), jnp.int32),
        gcnt=jnp.zeros((P, G), jnp.int32),
        # ±inf sentinels (not ±F32_MAX): an infinite input value must
        # propagate to min/max output exactly as the host oracle's does
        fmin_f=jnp.full((P, G, VF), jnp.inf, jnp.float32),
        fmax_f=jnp.full((P, G, VF), -jnp.inf, jnp.float32),
        fmin_i=jnp.full((P, G, VI), I32_MAX, jnp.int32),
        fmax_i=jnp.full((P, G, VI), I32_MIN, jnp.int32))


def _two_sum(a, b):
    """Error-free transform: a + b = s + err exactly (Knuth TwoSum)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _pair_add(hi, lo, x, ok):
    """Add x ([V]) to the (hi, lo) two-float accumulators where ok."""
    s, e = _two_sum(hi, x)
    lo2 = lo + e
    hi2 = s + lo2                      # fast renormalisation keeps the
    lo3 = lo2 - (hi2 - s)              # pair non-overlapping
    return jnp.where(ok, hi2, hi), jnp.where(ok, lo3, lo)


def build_grouped_step(window: int, want_minmax: bool, want_forever: bool,
                       numguard: bool = False):
    """fn(carry, vals_f [P,T,VF], vals_i [P,T,VI], gids [P,T] i32,
    accepted [P,T]) → (carry, outs): per-event aggregates of the arriving
    event's group after the update — a 13-tuple of [P, T, ...] arrays
    (fsum hi/lo, isum hi/lo, cnt, windowed min/max per bank, forever
    min/max per bank).  Positions with accepted=False carry junk and are
    discarded host-side.

    window == 0 → running (no-window) mode: no eviction, and plain
    min/max equal the forever lanes.

    numguard=True (SIDDHI_TPU_NUMGUARD, core/numguard.py) appends a
    14th output: a [3] int32 sentinel plane [int-sums near the 2^31
    ceiling, count lanes near 2^31, non-finite float-sum lanes] folded
    from the post-step carry the kernel already holds — an extra
    OUTPUT, not a carry leaf, so the persistent schema, the cost model
    and every match output stay bit-identical with the guard off."""
    W = window

    def lane_step(carry, xs):
        (rf, ri, rgid, pos, cnt, fhi, flo, ihi, ilo, gc,
         mnf, mxf, mni, mxi) = carry
        xf, xi, g, ok = xs

        if W > 0:
            oh = jnp.arange(W) == pos
            evict = ok & (cnt == W)
            old_f = jnp.sum(jnp.where(oh[:, None], rf, 0), axis=0)   # [VF]
            old_i = jnp.sum(jnp.where(oh[:, None], ri, 0), axis=0)   # [VI]
            old_g = jnp.sum(jnp.where(oh, rgid, 0))
            h2, l2 = _pair_add(fhi[old_g], flo[old_g], -old_f, evict)
            fhi = fhi.at[old_g].set(h2)
            flo = flo.at[old_g].set(l2)
            ihi = ihi.at[old_g].add(
                jnp.where(evict, -(old_i >> 16), 0))
            ilo = ilo.at[old_g].add(
                jnp.where(evict, -(old_i & (_SPLIT - 1)), 0))
            gc = gc.at[old_g].add(jnp.where(evict, -1, 0))
            rf = jnp.where(ok & oh[:, None], xf[None, :], rf)
            ri = jnp.where(ok & oh[:, None], xi[None, :], ri)
            rgid = jnp.where(ok & oh, g, rgid)
            pos = jnp.where(ok, (pos + 1) % W, pos)
            cnt = jnp.where(ok, jnp.minimum(cnt + 1, W), cnt)

        h2, l2 = _pair_add(fhi[g], flo[g], xf, ok)
        fhi = fhi.at[g].set(h2)
        flo = flo.at[g].set(l2)
        ihi = ihi.at[g].add(jnp.where(ok, xi >> 16, 0))
        ilo = ilo.at[g].add(jnp.where(ok, xi & (_SPLIT - 1), 0))
        gc = gc.at[g].add(jnp.where(ok, 1, 0))
        if want_forever or (want_minmax and W == 0):
            mnf = mnf.at[g].min(jnp.where(ok, xf, mnf[g]))
            mxf = mxf.at[g].max(jnp.where(ok, xf, mxf[g]))
            mni = mni.at[g].min(jnp.where(ok, xi, mni[g]))
            mxi = mxi.at[g].max(jnp.where(ok, xi, mxi[g]))

        if want_minmax and W > 0:
            live = ((jnp.arange(W) < cnt) & (rgid == g))[:, None]
            w_mnf = jnp.min(jnp.where(live, rf, jnp.inf), axis=0)
            w_mxf = jnp.max(jnp.where(live, rf, -jnp.inf), axis=0)
            w_mni = jnp.min(jnp.where(live, ri, I32_MAX), axis=0)
            w_mxi = jnp.max(jnp.where(live, ri, I32_MIN), axis=0)
        else:
            w_mnf, w_mxf, w_mni, w_mxi = mnf[g], mxf[g], mni[g], mxi[g]
        out = (fhi[g], flo[g], ihi[g], ilo[g], gc[g],
               w_mnf, w_mxf, w_mni, w_mxi,
               mnf[g], mxf[g], mni[g], mxi[g])
        return (rf, ri, rgid, pos, cnt, fhi, flo, ihi, ilo, gc,
                mnf, mxf, mni, mxi), out

    def per_lane(carry_l, f_l, i_l, g_l, ok_l):
        return jax.lax.scan(lane_step, carry_l, (f_l, i_l, g_l, ok_l))

    def step(carry: GroupedAggCarry, vals_f, vals_i, gids, accepted):
        new_c, outs = jax.vmap(per_lane)(tuple(carry), vals_f, vals_i,
                                         gids, accepted)
        nc = GroupedAggCarry(*new_c)
        if numguard:
            outs = outs + (sentinel_plane(nc.fsum_hi, nc.isum_hi,
                                          nc.isum_lo, nc.gcnt),)
        return nc, outs

    return step


#: the step's per-event outputs, in the order of its 13-tuple: a row
#: gather names a plane by its place here
PLANES = ("fsum_hi", "fsum_lo", "isum_hi", "isum_lo", "cnt",
          "w_min_f", "w_max_f", "w_min_i", "w_max_i",
          "all_min_f", "all_max_f", "all_min_i", "all_max_i")
CNT = PLANES.index("cnt")


def _columns(x, cols, axis):
    """``x``'s value columns ``cols`` (static) along ``axis``; a count
    plane (``cols`` None) as it is."""
    if cols is None or tuple(cols) == tuple(range(x.shape[axis])):
        return x
    return jnp.stack([jnp.take(x, j, axis=axis) for j in cols], axis=axis)


def build_row_gather(step, gather):
    """The step and its row egress in one call: ``step``'s 13 planes
    ``[P, T(, V)]`` read at each accepted event's ``(lane, row)`` into
    ``[n]`` rows (``[n, k]`` for the ``k`` value columns a plane is read
    at), only the ``(plane, columns)`` of ``gather``.  Anything the step
    returns past its 13 planes (the NUMGUARD sentinel) follows the rows.
    -> fn(carry, *step inputs, lanes [n], rows [n])."""
    def gagg_rows_step(carry, *args):
        *inputs, lanes, rows = args
        carry, outs = step(carry, *inputs)
        got = tuple(_columns(outs[p][lanes, rows], cols, 1)
                    for p, cols in gather)
        return carry, got + tuple(outs[13:])

    return gagg_rows_step


class LaneGroupCarry(NamedTuple):
    """Running aggregates of one group per lane; the lanes are minor."""
    fsum_hi: jnp.ndarray    # [VF, P] f32 two-float hi
    fsum_lo: jnp.ndarray    # [VF, P] f32 two-float lo
    isum_hi: jnp.ndarray    # [VI, P] i32 split hi
    isum_lo: jnp.ndarray    # [VI, P] i32 split lo
    gcnt: jnp.ndarray       # [P] i32
    fmin_f: jnp.ndarray     # [VF, P] f32 add-only min
    fmax_f: jnp.ndarray     # [VF, P] f32 add-only max
    fmin_i: jnp.ndarray     # [VI, P] i32 add-only min
    fmax_i: jnp.ndarray     # [VI, P] i32 add-only max


def make_lane_group_carry(n_lanes: int, n_float: int,
                          n_int: int) -> LaneGroupCarry:
    P, VF, VI = n_lanes, n_float, n_int
    return LaneGroupCarry(
        fsum_hi=jnp.zeros((VF, P), jnp.float32),
        fsum_lo=jnp.zeros((VF, P), jnp.float32),
        isum_hi=jnp.zeros((VI, P), jnp.int32),
        isum_lo=jnp.zeros((VI, P), jnp.int32),
        gcnt=jnp.zeros((P,), jnp.int32),
        fmin_f=jnp.full((VF, P), jnp.inf, jnp.float32),
        fmax_f=jnp.full((VF, P), -jnp.inf, jnp.float32),
        fmin_i=jnp.full((VI, P), I32_MAX, jnp.int32),
        fmax_i=jnp.full((VI, P), I32_MIN, jnp.int32))


def build_lane_group_step(want_minmax: bool, gather,
                          numguard: bool = False):
    """Running (no-window) aggregates with one group per lane: an event
    updates its own lane alone, so the lanes step side by side and a
    block is ``T`` ticks of elementwise work over ``[V, P]`` slabs.

    fn(carry, vals_f [n, VF], vals_i [n, VI], at [n] i32, depth) ->
    (carry, rows): the block's accepted events come up as compact rows,
    ``at`` = ``row * P + lane`` their cell in the ``[depth, P]`` block
    (``depth * P`` or beyond: padding, dropped), and are scattered on the
    device; ``rows`` are the outputs of ``gather`` (``(plane, columns)``
    of the 13-tuple build_grouped_step documents) at each event's cell,
    ``[n]`` or ``[n, k]``, then the NUMGUARD sentinel where armed.
    ``depth`` is static."""
    def gagg_lane_step(carry: LaneGroupCarry, vals_f, vals_i, at, depth):
        P = carry.gcnt.shape[0]
        cells = depth * P

        def dense(rows):
            # [n, V] rows -> [depth, V, P] planes, zero where no event
            V = rows.shape[1]
            d = jnp.zeros((V, cells), rows.dtype).at[:, at].set(
                rows.T, mode="drop")
            return d.reshape(V, depth, P).transpose(1, 0, 2)

        ok = jnp.zeros((cells,), bool).at[at].set(
            True, mode="drop").reshape(depth, P)

        def tick(c: LaneGroupCarry, xs):
            xf, xi, m = xs
            fhi, flo = _pair_add(c.fsum_hi, c.fsum_lo, xf, m[None, :])
            ihi = c.isum_hi + jnp.where(m, xi >> 16, 0)
            ilo = c.isum_lo + jnp.where(m, xi & (_SPLIT - 1), 0)
            gc = c.gcnt + m.astype(jnp.int32)
            mnf, mxf, mni, mxi = c.fmin_f, c.fmax_f, c.fmin_i, c.fmax_i
            if want_minmax:
                mnf = jnp.where(m, jnp.minimum(mnf, xf), mnf)
                mxf = jnp.where(m, jnp.maximum(mxf, xf), mxf)
                mni = jnp.where(m, jnp.minimum(mni, xi), mni)
                mxi = jnp.where(m, jnp.maximum(mxi, xi), mxi)
            nc = LaneGroupCarry(fhi, flo, ihi, ilo, gc, mnf, mxf, mni, mxi)
            planes = (fhi, flo, ihi, ilo, gc, mnf, mxf, mni, mxi,
                      mnf, mxf, mni, mxi)
            return nc, tuple(_columns(planes[p], cols, 0)
                             for p, cols in gather)

        carry, outs = jax.lax.scan(
            tick, carry, (dense(vals_f), dense(vals_i), ok))
        cell = jnp.minimum(at, cells - 1)
        t, lane = cell // P, cell % P
        # [depth, k, P] read at (t, :, lane) -> [n, k]
        rows = tuple(o[t, lane] if o.ndim == 2 else o[t, :, lane]
                     for o in outs)
        if numguard:
            rows = rows + (sentinel_plane(carry.fsum_hi, carry.isum_hi,
                                          carry.isum_lo, carry.gcnt),)
        return carry, rows

    return gagg_lane_step


def sentinel_plane(fsum_hi, isum_hi, isum_lo, gcnt) -> jnp.ndarray:
    """[3] int32 NUMGUARD flags folded from accumulator planes a step
    already produced: [int sums past 90% of 2^31, count lanes past 90%
    of 2^31, non-finite float-sum lanes].  The int reassembly rides f32
    (x64 is off under jit) — exactness does not matter for a 0.9x
    threshold test, only magnitude."""
    near = jnp.float32(0.9 * INT_EXACT_MAX)
    isum = (isum_hi.astype(jnp.float32) * _SPLIT +
            isum_lo.astype(jnp.float32))
    n_int = jnp.sum(jnp.abs(isum) >= near).astype(jnp.int32)
    n_cnt = jnp.sum(gcnt.astype(jnp.float32) >= near).astype(jnp.int32)
    n_fin = jnp.sum(~jnp.isfinite(fsum_hi)).astype(jnp.int32)
    return jnp.stack([n_int, n_cnt, n_fin])


def reassemble_int_sums(sum_hi: np.ndarray, sum_lo: np.ndarray
                        ) -> np.ndarray:
    """hi/lo split partial sums → exact int64 totals (host egress side)."""
    return sum_hi.astype(np.int64) * _SPLIT + sum_lo.astype(np.int64)


# ------------------------------------------------------------ time windows

TS_EMPTY = np.iinfo(np.int32).min      # empty-slot timestamp marker


class GroupedTimeCarry(NamedTuple):
    ring_f: jnp.ndarray     # [P, W, VF] f32
    ring_i: jnp.ndarray     # [P, W, VI] i32
    ring_gid: jnp.ndarray   # [P, W] i32
    ring_ts: jnp.ndarray    # [P, W] i32 offsets (TS_EMPTY = empty)
    pos: jnp.ndarray        # [P] i32
    cnt: jnp.ndarray        # [P] i32
    overflow: jnp.ndarray   # [P] bool — sticky: a still-in-window entry
    #                         was evicted; caller grows capacity + replays
    fmin_f: jnp.ndarray     # [P, G, VF] add-only extrema (forever lanes)
    fmax_f: jnp.ndarray
    fmin_i: jnp.ndarray     # [P, G, VI]
    fmax_i: jnp.ndarray


def make_grouped_time_carry(n_lanes: int, capacity: int, n_groups: int,
                            n_float: int, n_int: int) -> GroupedTimeCarry:
    P, W, G, VF, VI = n_lanes, capacity, n_groups, n_float, n_int
    return GroupedTimeCarry(
        ring_f=jnp.zeros((P, W, VF), jnp.float32),
        ring_i=jnp.zeros((P, W, VI), jnp.int32),
        ring_gid=jnp.full((P, W), -1, jnp.int32),
        ring_ts=jnp.full((P, W), TS_EMPTY, jnp.int32),
        pos=jnp.zeros((P,), jnp.int32),
        cnt=jnp.zeros((P,), jnp.int32),
        overflow=jnp.zeros((P,), bool),
        fmin_f=jnp.full((P, G, VF), jnp.inf, jnp.float32),
        fmax_f=jnp.full((P, G, VF), -jnp.inf, jnp.float32),
        fmin_i=jnp.full((P, G, VI), I32_MAX, jnp.int32),
        fmax_i=jnp.full((P, G, VI), I32_MIN, jnp.int32))


def _pair_tree_sum(vals, live):
    """Masked two-float tree reduction over axis 0 (W must be pow2):
    returns (hi, lo) whose f64 sum tracks the true sum to ~2^-45 —
    a plain f32 tree reduce can sit an f32 ulp off the host's float64
    accumulation, which conformance equality catches."""
    hi = jnp.where(live, vals, 0.0)
    lo = jnp.zeros_like(hi)
    w = hi.shape[0]
    while w > 1:
        half = w // 2
        a_hi, a_lo = hi[:half], lo[:half]
        b_hi, b_lo = hi[half:w], lo[half:w]
        s, e = _two_sum(a_hi, b_hi)
        lo2 = a_lo + b_lo + e
        hi = s + lo2
        lo = lo2 - (hi - s)
        w = half
    return hi[0], lo[0]


def build_grouped_time_step(window_ms: int, capacity: int,
                            want_forever: bool):
    """Grouped sliding time(t)/externalTime aggregation: the ring
    materialises the window's (value, gid, ts) entries; each accepted
    event's outputs are exact masked reductions over entries of ITS group
    with `entry_ts > event_ts - window_ms` — the same expiry-in-the-mask
    treatment as ops/windowed_agg.build_time_wagg_step, with a group-id
    plane (per-group aggregator maps, QuerySelector.java:171).  Float
    sums reduce via the two-float pairwise tree (_pair_tree_sum — host
    float64 parity at f32 precision); INT sums reduce hi/lo split lanes
    and stay EXACT.  Same output contract as build_grouped_step
    (13-tuple)."""
    W = capacity
    iota = jnp.arange(W)

    def lane_step(carry, xs):
        (rf, ri, rgid, rts, pos, cnt, ovf, mnf, mxf, mni, mxi) = carry
        xf, xi, g, t, ok = xs
        oh = iota == pos
        old_ts = jnp.sum(jnp.where(oh, rts, 0))
        evicting_live = (cnt == W) & (old_ts > t - window_ms)
        ovf = ovf | (ok & evicting_live)
        rf = jnp.where((ok & oh)[:, None], xf[None, :], rf)
        ri = jnp.where((ok & oh)[:, None], xi[None, :], ri)
        rgid = jnp.where(ok & oh, g, rgid)
        rts = jnp.where(ok & oh, t, rts)
        pos = jnp.where(ok, (pos + 1) % W, pos)
        cnt = jnp.where(ok, jnp.minimum(cnt + 1, W), cnt)
        if want_forever:
            mnf = mnf.at[g].min(jnp.where(ok, xf, mnf[g]))
            mxf = mxf.at[g].max(jnp.where(ok, xf, mxf[g]))
            mni = mni.at[g].min(jnp.where(ok, xi, mni[g]))
            mxi = mxi.at[g].max(jnp.where(ok, xi, mxi[g]))
        live = ((iota < cnt) & (rts > t - window_ms) & (rgid == g))[:, None]
        s_f, s_f_lo = _pair_tree_sum(rf, live)
        s_ihi = jnp.sum(jnp.where(live, ri >> 16, 0), axis=0)
        s_ilo = jnp.sum(jnp.where(live, ri & (_SPLIT - 1), 0), axis=0)
        c = jnp.sum(live[:, 0].astype(jnp.int32))
        w_mnf = jnp.min(jnp.where(live, rf, jnp.inf), axis=0)
        w_mxf = jnp.max(jnp.where(live, rf, -jnp.inf), axis=0)
        w_mni = jnp.min(jnp.where(live, ri, I32_MAX), axis=0)
        w_mxi = jnp.max(jnp.where(live, ri, I32_MIN), axis=0)
        out = (s_f, s_f_lo, s_ihi, s_ilo, c,
               w_mnf, w_mxf, w_mni, w_mxi,
               mnf[g], mxf[g], mni[g], mxi[g])
        return (rf, ri, rgid, rts, pos, cnt, ovf, mnf, mxf, mni, mxi), out

    def per_lane(carry_l, f_l, i_l, g_l, ts_l, ok_l):
        return jax.lax.scan(lane_step, carry_l, (f_l, i_l, g_l, ts_l,
                                                 ok_l))

    def step(carry: GroupedTimeCarry, vals_f, vals_i, gids, ts, accepted):
        new_c, outs = jax.vmap(per_lane)(tuple(carry), vals_f, vals_i,
                                         gids, ts, accepted)
        return GroupedTimeCarry(*new_c), outs

    return step

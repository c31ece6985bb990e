"""Grouped / running aggregation query → ops/grouped_agg kernel.

The device QuerySelector path (VERDICT r2 next #4 + #8): lowers

    from S[filter](#window.length(W))?
    select <keys/passthroughs>, sum|count|avg|min|max|minForever|maxForever(x)
    (group by k1, k2, ...)?
    insert into Out;

onto ops/grouped_agg.build_grouped_step.  Covers what the sibling
CompiledWindowedAgg (plan/wagg_compiler.py) rejects:
  - group-by keys finer than / different from the partition key (each
    (lane, group-tuple) gets its own aggregate state — the reference's
    per-group aggregator maps, QuerySelector.java:171)
  - MULTIPLE distinct aggregate arguments (each distinct value expression
    gets its own V lane; float- and int-typed expressions ride separate
    exact banks)
  - no-window running aggregates (reference per-query cumulative
    aggregators), incl. minForever/maxForever anywhere
  - exact INT/LONG sums via the kernel's i32 hi/lo split

Filters, the value projections and group-key encoding run host-side with
the SAME expression IR (numpy backend) — one evaluation serves both the
device feed and emission masking; the stateful scan runs on device.

Reference: query/selector/QuerySelector.java:44-224,
GroupByKeyGenerator.java, selector/attribute/aggregator/*.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query_api import Filter, Query, SingleInputStream, WindowHandler
from ..core.stateschema import (CarryTuple, MapOf, Scalar, Struct,
                                persistent_schema)
from ..query_api.definition import AttrType
from ..query_api.expression import AttributeFunction, Constant, Variable
from ..utils.errors import (SiddhiAppCreationError,
                            SiddhiAppRuntimeException)
from ..ops.grouped_agg import (CNT, INT_EXACT_MAX, INT_GROUP_MAX, TS_EMPTY,
                               GroupedTimeCarry, build_grouped_step,
                               build_grouped_time_step,
                               build_lane_group_step, build_row_gather,
                               make_grouped_carry, make_grouped_time_carry,
                               make_lane_group_carry, reassemble_int_sums)
from ..core.ledger import ledger as _ledger
from .expr_compiler import EvalCtx, ExprCompiler, Scope

_AGGS = {"sum", "count", "avg", "min", "max", "minforever", "maxforever",
         "stddev"}
_INT_TYPES = (AttrType.INT, AttrType.LONG)
_NUM_TYPES = _INT_TYPES + (AttrType.FLOAT, AttrType.DOUBLE)
#: group attribute types whose distinct values a block factors exactly as
#: the host's tuple keys tell them apart (a float's -0.0 and NaN do not)
_LANE_KEY_TYPES = (AttrType.STRING, AttrType.INT, AttrType.LONG,
                   AttrType.BOOL)

G_START = 8          # initial per-lane group capacity (doubles on demand)
MAX_WINDOW = (1 << 15) - 1   # hi/lo int sums stay exact below this
TIME_CAPACITY_START = 64     # time-window ring start (grow-and-replay)
#: a block's rows go out as at least ``P * T / ROW_SHARE`` (the accepted
#: events rounded up to a power of two where more), so that the egress
#: buffer, like the block, takes its shape from the depth
ROW_SHARE = 8


def _const_choice(ast) -> bool:
    """``ifThenElse(<cond>, c1, c2)`` with two integer constants: a
    computed integer lane whose values are the two constants alone."""
    if not (isinstance(ast, AttributeFunction) and
            (ast.namespace or "") == "" and
            ast.name.lower() == "ifthenelse" and len(ast.args) == 3):
        return False
    return all(isinstance(c, Constant) and type(c.value) is int and
               abs(c.value) < INT_EXACT_MAX for c in ast.args[1:])


def rows_pad(n: int, n_lanes: int, depth: int) -> int:
    """The rows a block of ``n`` events over ``[n_lanes, depth]`` cells
    sends out (ROW_SHARE)."""
    return max(1 << max(0, (n - 1).bit_length()),
               n_lanes * depth // ROW_SHARE)


def _reject(msg: str):
    raise SiddhiAppCreationError("device grouped-agg path: " + msg)


class GaggOverflow(Exception):
    """A still-in-window time-ring entry was evicted during a step —
    decode() signals the caller to rewind, grow, and replay."""


class _Value:
    """One distinct aggregate argument expression → one V lane."""

    def __init__(self, ast, compiled, int_mode: bool, vidx: int,
                 attr: Optional[str]):
        self.ast = ast
        self.compiled = compiled
        self.int_mode = int_mode
        self.vidx = vidx                 # index within its bank
        self.attr = attr                 # plain-Variable name (int check)
        self.type = compiled.type


class _SplitSquare:
    """x² split across two exactly-representable f32 parts (stdDev lanes):
    hi = f32(x²), lo = x² − hi (the rounding remainder, ≤ ulp(hi)/2 —
    f32-representable).  x² itself is exact in float64 for f32 inputs."""

    def __init__(self, base, part: str):
        self._base = base
        self._part = part
        self.type = AttrType.DOUBLE

    def fn(self, ctx):
        x = np.asarray(self._base.fn(ctx), np.float64)
        sq = x * x
        if np.any(sq > 3.0e38):
            # x² must fit the f32 hi lane; |x| > ~1.8e19 would ride as
            # inf and poison the running sums — loud data error instead
            # (routed via the junction's @OnError boundary)
            raise SiddhiAppRuntimeException(
                "device grouped-agg path: stdDev argument magnitude "
                "exceeds the f32 square range (|x| > 1.8e19); re-plan "
                "with @app:engine('host')")
        hi = sq.astype(np.float32).astype(np.float64)
        return hi if self._part == "hi" else sq - hi


@persistent_schema(
    "gagg-engine", version=1,
    schema=Struct(carry=CarryTuple(), n_lanes=Scalar("int"),
                  n_groups=Scalar("int"), window=Scalar("opt_num"),
                  ts_base=Scalar("opt_int"), gid_map=MapOf("int"),
                  lane_gids=MapOf("int")),
    dims={"L": "free", "G": "free", "wkind": "exact"},
    doc="lane/group capacities are adopted wholesale by restore; the "
        "window kind (length vs time carry layout) is plan-fixed")
class CompiledGroupedAgg:
    """One aggregation query over [lane, group, value] device state."""

    def __init__(self, app, query: Query, n_lanes: int = 1,
                 keyed: bool = False, definition=None):
        s = query.input_stream
        assert isinstance(s, SingleInputStream)
        wh = s.window_handler
        self.window_kind = "length"      # length | time (no-window: W=0)
        self.ts_attr: Optional[str] = None
        kind = wh.name.lower() if wh is not None and \
            not (wh.namespace or "") else ("" if wh is None else "?")
        if wh is None:
            self.window = 0
        elif kind == "length":
            if not wh.params or not isinstance(wh.params[0], Constant):
                _reject("window.length needs a constant length")
            self.window = int(wh.params[0].value)
            if not 0 < self.window <= MAX_WINDOW:
                _reject(f"window length {self.window} out of device range")
        elif kind in ("time", "externaltime"):
            self.window_kind = "time"
            if kind == "externaltime":
                if len(wh.params) != 2 or \
                        not isinstance(wh.params[0], Variable):
                    _reject("externalTime needs (tsAttr, window)")
                self.ts_attr = wh.params[0].attribute
                span = wh.params[1]
            else:
                span = wh.params[0] if wh.params else None
            if not isinstance(span, Constant):
                _reject(f"{wh.name} needs a constant window length")
            self.window_ms = int(span.value)
            self.window = TIME_CAPACITY_START
            self._ts_base: Optional[int] = None
        else:
            _reject(f"only #window.length / #window.time / "
                    f"#window.externalTime / no window compile "
                    f"(got #{wh.name})")
        # set by the pipelined runtime: retires in-flight work before a
        # timestamp rebase mutates the ring (plan/pipeline.py)
        self.flush_hook = None
        if definition is None:
            definition = app.stream_definitions.get(s.stream_id)
        if definition is None:
            _reject(f"no stream '{s.stream_id}'")
        self.stream_id = s.stream_id
        self.input_definition = definition
        attr_types = {a.name: a.type for a in definition.attributes}
        if self.ts_attr is not None:
            at = attr_types.get(self.ts_attr)
            if at not in (AttrType.INT, AttrType.LONG):
                _reject(f"externalTime: '{self.ts_attr}' must be an "
                        f"INT/LONG attribute")

        scope = Scope()
        scope.add_primary(s.stream_id, s.stream_ref, definition)
        host = ExprCompiler(scope, np)
        self.filters = [host.compile(h.expr) for h in s.handlers
                        if isinstance(h, Filter)]
        if any(not isinstance(h, (Filter, WindowHandler))
               for h in s.handlers):
            _reject("stream functions are host-only")

        # group-by: plain attributes (dictionary-encoded host-side)
        self.group_attrs: List[str] = []
        for g in query.selector.group_by:
            if not isinstance(g, Variable) or g.attribute not in attr_types:
                _reject("group-by must be plain stream attributes")
            self.group_attrs.append(g.attribute)

        # outputs: (name, kind, value|attr) — every distinct aggregate
        # argument gets its own V lane in the float or int bank
        self.values: List[_Value] = []
        by_ast: Dict[Any, _Value] = {}
        self._n_float = 0
        self._n_int = 0

        def value_of(ast) -> _Value:
            v = by_ast.get(ast)      # frozen dataclasses: hash == eq
            if v is not None:
                return v
            ce = host.compile(ast)
            if ce.type not in _NUM_TYPES:
                _reject(f"aggregate argument type {ce.type} not numeric")
            int_mode = ce.type in _INT_TYPES
            attr = ast.attribute if isinstance(ast, Variable) else None
            if int_mode and attr is None and not _const_choice(ast):
                _reject("INT/LONG aggregate arguments must be plain "
                        "attributes or ifThenElse of two integer constants "
                        "(other computed integer expressions cannot be "
                        "exactness-checked)")
            if int_mode:
                v = _Value(ast, ce, True, self._n_int, attr)
                self._n_int += 1
            else:
                v = _Value(ast, ce, False, self._n_float, attr)
                self._n_float += 1
            by_ast[ast] = v
            self.values.append(v)
            return v

        self.outputs: List[Tuple[str, str, Any]] = []
        want_minmax = False
        want_forever = False
        have_agg = False
        for oa in query.selector.attributes:
            e = oa.expr
            if isinstance(e, AttributeFunction) and \
                    (e.namespace or "") == "" and e.name.lower() in _AGGS:
                kind = e.name.lower()
                have_agg = True
                if kind == "count" and not e.args:
                    self.outputs.append((oa.rename, "count", None))
                    continue
                if not e.args:
                    _reject(f"{kind}() needs an argument")
                if kind == "stddev":
                    # stdDev(x) = sqrt(E[x²] − E[x]²) — the reference's
                    # own mean/meanSq formula (StdDevAttributeAggregator
                    # Executor.java), so the cancellation behavior
                    # matches.  x² does not fit one f32 lane (a 24-bit
                    # mantissa squared needs 48), so each square rides
                    # TWO lanes — hi = f32(x²), lo = x² − hi, both exact
                    # — and Σhi + Σlo reconstructs Σx² in float64.
                    arg = e.args[0]
                    vx = value_of(arg)
                    if vx.int_mode:
                        _reject("stdDev over INT/LONG arguments would "
                                "square outside the exact i32 range")
                    parts = []
                    for part in ("hi", "lo"):
                        key = ("__stddev_sq", part, arg)
                        v = by_ast.get(key)
                        if v is None:
                            v = _Value(key, _SplitSquare(vx.compiled, part),
                                       False, self._n_float, None)
                            self._n_float += 1
                            by_ast[key] = v
                            self.values.append(v)
                        parts.append(v)
                    self.outputs.append(
                        (oa.rename, "stddev", (vx, parts[0], parts[1])))
                    continue
                val = value_of(e.args[0])
                if kind in ("min", "max"):
                    want_minmax = True
                if kind in ("minforever", "maxforever"):
                    want_forever = True
                self.outputs.append((oa.rename, kind, val))
            elif isinstance(e, Variable) and e.attribute in attr_types:
                self.outputs.append((oa.rename, "key", e.attribute))
            else:
                _reject("select supports aggregates plus plain attributes")
        # selection tail (having / order-by / limit / offset): compiled
        # into a device egress program when expressible; atoms may pull
        # in min/max planes the select clause alone didn't want, so this
        # runs BEFORE _build_step fixes the kernel program
        from .select_compiler import (SelectionBlocked, compile_selection,
                                      selection_active)
        self.selection = None
        if selection_active(query.selector):
            try:
                self.selection = compile_selection(
                    query.selector, self.outputs, attr_types,
                    keyed=keyed,
                    windowed=(self.window != 0))
            except SelectionBlocked as e:
                _reject(f"selection tail stays on the host "
                        f"QuerySelector: {e.reason}")
            have_agg = have_agg or self.selection.has_agg
            want_minmax = want_minmax or self.selection.uses_minmax
            want_forever = want_forever or self.selection.uses_forever
        if not have_agg:
            _reject("no aggregates to run (plain projection is the filter "
                    "path)")
        self.want_minmax = want_minmax
        self.want_forever = want_forever
        # the INT_GROUP_MAX egress guard protects EXACT int sums; queries
        # whose int lanes feed only min/max/count need no such bound
        self._int_sum_needed = any(
            kind in ("sum", "avg") and isinstance(ref, _Value) and
            ref.int_mode for (_n, kind, ref) in self.outputs)

        # group lanes: an unpartitioned group-by whose window keeps each
        # group's events apart (none, time, externalTime) steps one lane
        # per group tuple, G = 1; the caller maps group tuples to lanes.
        # A length window evicts across groups and keeps one lane
        self.group_lanes = (not keyed and bool(self.group_attrs) and
                            (self.window == 0 or
                             self.window_kind == "time") and
                            all(attr_types[a] in _LANE_KEY_TYPES
                                for a in self.group_attrs))
        # running aggregates on group lanes take the lane-minor step,
        # whose events come up and go out as rows
        self.lane_rows = self.group_lanes and self.window == 0 and \
            self.selection is None
        self._gather, self._row_at = self._gather_spec()
        self.n_lanes = n_lanes
        self.n_groups = 1 if self.group_lanes else G_START
        self.gid_map: Dict[Tuple, int] = {}      # (lane, key tuple) → gid
        self._lane_gids: Dict[int, int] = {}     # lane → next local gid
        # numeric sentinels (core/numguard.py, SIDDHI_TPU_NUMGUARD):
        # armed at compile time — the device sentinel output is part of
        # the compiled program, not a runtime toggle
        from ..core.numguard import numeric_sentinels, numguard_enabled
        self._numguard = numguard_enabled()
        self.sentinels = numeric_sentinels(app.name or "?") \
            if self._numguard else None
        self._build_step()
        self.carry = self._make_carry(n_lanes)

    # ------------------------------------------------------------ shapes

    def _gather_spec(self):
        """What decode reads of an accepted event's outputs: per plane of
        the step's 13-tuple (ops/grouped_agg.PLANES) the value columns,
        in plane order, the count plane always (the exact-sum bound and
        avg read it); and plane -> (its place among the rows, value
        column -> its place in the plane's rows)."""
        planes: Dict[int, set] = {CNT: set()}
        # (float plane(s), int plane(s)) an aggregate reads
        reads = {"sum": ((0, 1), (2, 3)), "avg": ((0, 1), (2, 3)),
                 "stddev": ((0, 1), ()), "min": ((5,), (7,)),
                 "max": ((6,), (8,)), "minforever": ((9,), (11,)),
                 "maxforever": ((10,), (12,))}
        for (_name, kind, ref) in self.outputs:
            if kind not in reads:
                continue
            for v in (ref if kind == "stddev" else (ref,)):
                for p in reads[kind][v.int_mode]:
                    planes.setdefault(p, set()).add(v.vidx)
        spec = tuple((p, None if p == CNT else tuple(sorted(planes[p])))
                     for p in sorted(planes))
        return spec, {p: (i, {j: k for k, j in enumerate(cols or ())})
                      for i, (p, cols) in enumerate(spec)}

    def _build_step(self):
        from .shapes import shape_registry
        # shape-class dims exclude lanes/groups: those grow under the
        # same jit (a plain retrace), only these facts change the program
        rows = None if self.selection is not None else self._gather
        if self.lane_rows:
            # running sums of group lanes (min/max and their forever
            # forms are one lane here); donated unless exact int sums
            # are wanted (their bound rewinds to the pre-carry)
            donate = () if self._int_sum_needed else (0,)
            minmax = self.want_minmax or self.want_forever
            self._step = shape_registry().jit(
                "gagg.step",
                {"kind": "lanes", "vf": self._n_float, "vi": self._n_int,
                 "minmax": minmax, "donate": bool(donate),
                 "numguard": self._numguard},
                build_lane_group_step(minmax, rows,
                                      numguard=self._numguard),
                static_argnums=(4,), donate_argnums=donate)
        elif self.window_kind == "time":
            # no donation: decode's GaggOverflow rewind replays from the
            # chunk's pre-carry, which must survive the step
            step = build_grouped_time_step(
                self.window_ms, self.window, self.want_forever)
            self._step = shape_registry().jit(
                "gagg.time.step",
                {"win_ms": self.window_ms, "win": self.window,
                 "vf": self._n_float, "vi": self._n_int,
                 "forever": self.want_forever},
                step if rows is None else build_row_gather(step, rows))
        else:
            # length/running carries donate (XLA aliases the [P, G, V]
            # slabs in place) UNLESS exact int sums are wanted — their
            # bound trips in decode and rewinds to the pre-carry
            donate = () if self._int_sum_needed else (0,)
            # NUMGUARD (core/numguard.py): the sentinel flag appends a
            # 14th output, a different compiled program — so it is part
            # of the shape-class key, like every program-changing fact
            step = build_grouped_step(
                self.window, self.want_minmax, self.want_forever,
                numguard=self._numguard)
            self._step = shape_registry().jit(
                "gagg.step",
                {"kind": self.window_kind, "win": self.window,
                 "vf": self._n_float, "vi": self._n_int,
                 "minmax": self.want_minmax, "forever": self.want_forever,
                 "donate": bool(donate), "numguard": self._numguard},
                step if rows is None else build_row_gather(step, rows),
                donate_argnums=donate)
        if getattr(self, "selection", None) is not None:
            from ..ops.select import build_select_step
            p = self.selection
            self._select = shape_registry().jit(
                "select.step",
                {"sig": p.key, "vf": self._n_float, "vi": self._n_int},
                build_select_step(p))

    def _make_carry(self, n_lanes: int, n_groups: Optional[int] = None):
        g = self.n_groups if n_groups is None else n_groups
        if self.lane_rows:
            return make_lane_group_carry(n_lanes, self._n_float,
                                         self._n_int)
        if self.window_kind == "time":
            return make_grouped_time_carry(n_lanes, self.window, g,
                                           self._n_float, self._n_int)
        return make_grouped_carry(n_lanes, self.window, g,
                                  self._n_float, self._n_int)

    def grow_lanes(self, n_lanes: int) -> None:
        if n_lanes <= self.n_lanes:
            return
        fresh = self._make_carry(n_lanes - self.n_lanes)
        axis = -1 if self.lane_rows else 0      # the lane-minor carry
        self.carry = type(self.carry)(
            *[jnp.concatenate([a, b], axis=axis)
              for a, b in zip(self.carry, fresh)])
        self.n_lanes = n_lanes

    # ------------------------------------------------ partition shard-out

    def pin_to_device(self, device) -> None:
        """Commit the carry to one device (parallel/shards.py): jit
        dispatch follows committed operands, so steps, group growth and
        ring compaction stay shard-local."""
        self.shard_device = device
        self.carry = jax.device_put(self.carry, device)

    def clone_for_shard(self, device) -> "CompiledGroupedAgg":
        """Fresh-state shard clone pinned to `device`: shares the jitted
        step and compiled value/filter plans; owns its carry AND its
        group-id dictionaries — gid_map/_lane_gids mutate in place, so
        sharing them across shards would hand one shard's group slots to
        another's keys."""
        import copy
        cl = copy.copy(self)
        cl.shard_device = device
        cl.gid_map = {}
        cl._lane_gids = {}
        cl.n_groups = G_START
        if cl.window_kind == "time":
            cl._ts_base = None
        cl.carry = jax.device_put(cl._make_carry(cl.n_lanes), device)
        # never fused into the app egress slab: cross-device concat
        # would force a device hop
        cl.egress_fuser = None
        cl.flush_hook = None
        return cl

    def _grow_groups(self, n_groups: int) -> None:
        if n_groups <= self.n_groups:
            return
        pad = self._make_carry(self.n_lanes,
                               n_groups=n_groups - self.n_groups)
        c, p = self.carry, pad
        gfields = ("fmin_f", "fmax_f", "fmin_i", "fmax_i")
        if self.window_kind != "time":
            gfields += ("fsum_hi", "fsum_lo", "isum_hi", "isum_lo", "gcnt")
        self.carry = c._replace(**{
            f: jnp.concatenate([getattr(c, f), getattr(p, f)], axis=1)
            for f in gfields})
        self.n_groups = n_groups

    def _grow_time_capacity(self, new_capacity: int) -> None:
        """Double the time ring (chronological compaction so the
        slot-fill invariant `valid slots = [0, cnt)` holds), keeping the
        value/gid planes aligned with their timestamps."""
        assert self.window_kind == "time"
        if new_capacity <= self.window:
            return
        old = self.carry
        rts = np.asarray(old.ring_ts)
        P, W = rts.shape
        W2 = new_capacity
        # empty slots (TS_EMPTY, the int32 minimum) sort first: a lane's
        # cnt live entries are the last cnt of its ordered slots
        order = np.argsort(rts, axis=1, kind="stable")
        cnt = (rts != TS_EMPTY).sum(axis=1)
        i = np.arange(W2)[None, :]
        live = i < cnt[:, None]
        src = np.take_along_axis(
            order, np.minimum(W - cnt[:, None] + i, W - 1), axis=1)
        lane = np.arange(P)[:, None]

        def moved(a, empty):
            a = np.asarray(a)[lane, src]
            keep = live if a.ndim == 2 else live[..., None]
            return jnp.asarray(np.where(keep, a, empty).astype(a.dtype))
        self.window = W2
        self.carry = GroupedTimeCarry(
            ring_f=moved(old.ring_f, 0), ring_i=moved(old.ring_i, 0),
            ring_gid=moved(old.ring_gid, -1),
            ring_ts=moved(old.ring_ts, TS_EMPTY),
            pos=jnp.asarray(cnt % W2, jnp.int32),
            cnt=jnp.asarray(cnt, jnp.int32),
            overflow=jnp.zeros((P,), bool),
            fmin_f=old.fmin_f, fmax_f=old.fmax_f,
            fmin_i=old.fmin_i, fmax_i=old.fmax_i)
        self._build_step()

    def _gids_for(self, lanes: np.ndarray, key_cols: List[np.ndarray]
                  ) -> np.ndarray:
        """(lane, group-key tuple) → stable per-lane group ids, growing the
        slab when a lane's group population exceeds capacity."""
        n = len(lanes)
        out = np.empty(n, np.int64)
        for i in range(n):
            lane = int(lanes[i])
            key = (lane,) + tuple(c[i].item() if hasattr(c[i], "item")
                                  else c[i] for c in key_cols)
            gid = self.gid_map.get(key)
            if gid is None:
                gid = self._lane_gids.get(lane, 0)
                self._lane_gids[lane] = gid + 1
                self.gid_map[key] = gid
            out[i] = gid
        need = max(self._lane_gids.values(), default=0)
        if need > self.n_groups:
            cap = self.n_groups
            while cap < need:
                cap *= 2
            self._grow_groups(cap)
        return out

    def _ts_offsets(self, data, lanes32, row, ok, shape) -> np.ndarray:
        """[P, T] i32 ts offsets for the time kernel (shared rebase
        protocol: ops/ts32.rebase_offsets — only ACCEPTED rows decide the
        base; filter-rejected rows may carry junk timestamps).
        externalTime reads the event's own ts attribute."""
        from ..ops.ts32 import rebase_offsets
        src = (np.asarray(data.columns[self.ts_attr], np.int64)
               if self.ts_attr else
               np.asarray(data.timestamps, np.int64))
        offs, base, new_ring = rebase_offsets(
            src, ok, self._ts_base, self.window_ms,
            self.carry.ring_ts, TS_EMPTY,
            sentinels=self.sentinels, site="gagg.ts32")
        if new_ring is not self.carry.ring_ts:
            # rebase shifts the carried ring: retire in-flight work first
            # so every queued step (and any overflow replay) shares one
            # timestamp base, then recompute against the settled carry
            if self.flush_hook is not None:
                self.flush_hook()
            offs, base, new_ring = rebase_offsets(
                src, ok, self._ts_base, self.window_ms,
                self.carry.ring_ts, TS_EMPTY,
                sentinels=self.sentinels, site="gagg.ts32")
            self.carry = self.carry._replace(ring_ts=new_ring)
        self._ts_base = base
        plane = np.zeros(shape, np.int32)
        plane[lanes32, row] = offs
        return plane

    # ------------------------------------------------------------ execute

    def encode(self, data) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The filters and the value lanes of a chunk, on the host ->
        (accepted mask, vals_f [n, VF] f32, vals_i [n, VI] i32).  Data
        errors that are host-detectable (2^31 integer lanes) raise HERE,
        before any carry mutation."""
        n = len(data)
        ctx = EvalCtx(data.columns, data.timestamps, n)
        ok = np.ones(n, bool)
        for f in self.filters:
            m = np.asarray(f.fn(ctx), bool)
            ok &= np.broadcast_to(m, ok.shape)

        vals_f = np.zeros((n, self._n_float), np.float32)
        vals_i = np.zeros((n, self._n_int), np.int32)
        for v in self.values:
            col = np.broadcast_to(np.asarray(v.compiled.fn(ctx)), (n,))
            if v.int_mode:
                iv = np.asarray(col, np.int64)
                bad = ok & (np.abs(iv) >= INT_EXACT_MAX)
                if bad.any():
                    raise SiddhiAppRuntimeException(
                        "device grouped-agg path: integer aggregate value "
                        f"|{int(iv[bad][0])}| >= 2^31 does not fit the "
                        "i32 device lanes; re-plan with "
                        "@app:engine('host')")
                vals_i[:, v.vidx] = iv.astype(np.int32)
            else:
                vals_f[:, v.vidx] = np.asarray(col, np.float32)
        return ok, vals_f, vals_i

    def dispatch(self, lanes: np.ndarray, data, encoded=None
                 ) -> Optional[Dict[str, Any]]:
        """data: EventChunk of CURRENT events, lanes: per-event lane
        index; ``encoded``: what ``encode(data)`` gave, where the caller
        ran it.  Host-side encode + ONE kernel dispatch; returns a work
        dict whose un-read device handles `decode` consumes later
        (pipelined ingest), or None when no event passes the filters.
        On group lanes every event of ``data`` is accepted (the caller
        kept those alone) and its lane is its group's."""
        from ..native_ext import assign_rows
        ok, vals_f, vals_i = encoded or self.encode(data)
        if not ok.any():
            return None
        n = len(data)
        gids = np.zeros(n, np.int64)        # group lanes: G = 1
        if not self.group_lanes:
            # group ids only for ACCEPTED rows — filter-rejected keys
            # must not allocate slab entries (high-cardinality streams
            # would grow the [P, G, V] state for groups that never hold
            # data)
            key_cols = [np.asarray(data.columns[a])[ok]
                        for a in self.group_attrs]
            gids[ok] = self._gids_for(np.asarray(lanes)[ok], key_cols)
        lanes32 = np.ascontiguousarray(lanes, np.int32)
        P = self.n_lanes
        work: Dict[str, Any] = {"data": data, "ok": ok}
        with _ledger().span("device", "pack"):
            row, _counts, T = assign_rows(lanes32, P)
            T = 1 << (T - 1).bit_length()
            work["cells"] = P * T
            if self.lane_rows:
                # compact rows: the step scatters them into its block
                n_pad = rows_pad(n, P, T)
                at = np.full(n_pad, T * P, np.int32)
                at[:n] = row * P + lanes32
                xf = np.zeros((n_pad, self._n_float), np.float32)
                xi = np.zeros((n_pad, self._n_int), np.int32)
                xf[:n] = vals_f
                xi[:n] = vals_i
                work.update(n_ok=n, planes=(xf, xi, at, T))
            else:
                f_plane = np.zeros((P, T, self._n_float), np.float32)
                i_plane = np.zeros((P, T, self._n_int), np.int32)
                g_plane = np.zeros((P, T), np.int32)
                ok_plane = np.zeros((P, T), bool)
                f_plane[lanes32, row] = vals_f
                i_plane[lanes32, row] = vals_i
                g_plane[lanes32, row] = gids
                ok_plane[lanes32, row] = ok
        if self.lane_rows:
            self.redispatch(work)
            return work
        n_ok = work["n_ok"] = int(ok.sum())
        if self.selection is not None:
            # padded per-emission gather vectors for the select step —
            # pow2-bucketed like T so chunk-size jitter reuses traces;
            # padding rows carry ok=False and sort behind out_count
            n_pad = 1 << max(0, (n - 1).bit_length())
            lp = np.zeros(n_pad, np.int32)
            rp = np.zeros(n_pad, np.int32)
            op = np.zeros(n_pad, bool)
            lp[:n] = lanes32
            rp[:n] = row
            op[:n] = ok
            work["sel_pad"] = (lp, rp, op)
        else:
            # the accepted events' cells, read in the step's own call
            n_pad = rows_pad(n_ok, P, T)
            lp = np.zeros(n_pad, np.int32)
            rp = np.zeros(n_pad, np.int32)
            lp[:n_ok] = lanes32[ok]
            rp[:n_ok] = row[ok]
            work["at"] = (lp, rp)
        if self.window_kind == "time":
            ts_plane = self._ts_offsets(data, lanes32, row, ok, (P, T))
            work["planes"] = (f_plane, i_plane, g_plane, ts_plane,
                              ok_plane)
        else:
            work["planes"] = (f_plane, i_plane, g_plane, ok_plane)
        self.redispatch(work)
        return work

    def redispatch(self, work: Dict[str, Any]) -> None:
        """(Re)run a work item's kernel step on the CURRENT carry —
        used at dispatch and when replaying in-flight chunks after a
        ring growth rewind.  Donated configs (length/running without
        exact int sums — see _build_step) never rewind, so pre_carry is
        None there: touching it is a bug, not a stale read."""
        donated = (self.window_kind != "time" and
                   not self._int_sum_needed)
        work["pre_carry"] = None if donated else self.carry
        self.carry, outs = self._step(self.carry, *work["planes"],
                                      *work.get("at", ()))
        if self.selection is not None:
            # chain the egress selection kernel: having mask, ordering
            # permutation and limit bound computed on device over the 13
            # grouped planes; the numguard sentinel (14th output) stays
            # appended behind the select outputs
            base, tail = outs[:13], outs[13:]
            outs = tuple(self._select(*base, *work["sel_pad"])) + \
                tuple(tail)
        fuser = getattr(self, "egress_fuser", None)
        if fuser is not None:
            # outputs (and the time ring's overflow flag, read first in
            # decode) ride the app's per-ingest-block slab
            extra = ([self.carry.overflow]
                     if self.window_kind == "time" else [])
            work["fuse"] = fuser.register(self, list(outs) + extra)
        else:
            work["fuse"] = None
            for o in outs:
                o.copy_to_host_async()
        work["outs"] = outs
        work["post_carry"] = self.carry

    def grow_time_window(self) -> None:
        """Double the time-window ring (the caller has already rewound
        self.carry to the failing chunk's pre-carry)."""
        if self.window * 2 > MAX_WINDOW + 1:
            # check BEFORE growing: the compaction + fresh kernel build
            # would be wasted work right before the raise
            raise SiddhiAppRuntimeException(
                "device grouped-agg path: time window needs more "
                "than 2^15 live entries (exact int-sum bound) — "
                "re-plan with @app:engine('host')")
        self._grow_time_capacity(self.window * 2)

    def decode(self, work: Dict[str, Any]) -> Dict[str, Any]:
        """Block on a work item's device handles and decode the per-event
        outputs.  Raises GaggOverflow when a still-in-window time-ring
        entry was evicted (results would undercount) — the caller rewinds
        to work["pre_carry"], grows, and replays this and every later
        in-flight chunk.  Raises SiddhiAppRuntimeException on the exact
        integer-sum bound — the caller rewinds likewise (the reference's
        @OnError continuation must not see the chunk half-applied)."""
        data, ok = work["data"], work["ok"]
        token = work.get("fuse")
        if token is not None:
            fetched = token.fetch()
            if self.window_kind == "time":
                if bool(np.asarray(fetched[-1]).any()):
                    raise GaggOverflow()
                fetched = fetched[:-1]
            outs_host = fetched
        else:
            if self.window_kind == "time" and \
                    bool(np.asarray(work["post_carry"].overflow).any()):
                raise GaggOverflow()
            outs_host = [np.asarray(o) for o in work["outs"]]
        if self._numguard and self.window_kind != "time":
            # device sentinel plane (appended last — see _build_step)
            sent, outs_host = outs_host[-1], outs_host[:-1]
            if self.sentinels is not None:
                self.sentinels.observe_sentinel_plane("gagg.step", sent)
        if self.selection is not None:
            # device selection (ops/select.py): sel_rows is the ordering
            # permutation over chunk rows, meta = [out_count, max_cnt];
            # the 13 planes arrive already gathered + compacted, so the
            # selected rows are simply the first out_count entries
            meta = np.asarray(outs_host[1])
            k = int(meta[0])
            sel_idx = np.asarray(outs_host[0])[:k]
            planes = outs_host[2:]

            def col(p, j=None):
                a = planes[p][:k]
                return a if j is None else a[:, j]
        else:
            # the accepted events' rows, in chunk order (_gather_spec)
            k = work["n_ok"]
            sel_idx = None

            def col(p, j=None):
                at, pos = self._row_at[p]
                a = outs_host[at][:k]
                return a if j is None else a[:, pos[j]]
        counts = col(CNT).astype(np.int64)
        if self.sentinels is not None:
            # host-rim witness over rows this decode already fetched:
            # bit-identical by construction (reads only, no compute path
            # change) — covers the time kernel, which has no device plane
            if 0 in self._row_at or self.selection is not None:
                self.sentinels.observe_floats("gagg.decode", col(0))
            self.sentinels.observe_counts("gagg.decode", counts)
        # the bound sees every accepted event's count, the ones a having
        # clause filtered out too
        cnt_max = int(meta[1]) if sel_idx is not None else \
            int(counts.max(initial=0))
        if self._int_sum_needed and self.window == 0 and \
                cnt_max >= INT_GROUP_MAX:
            raise SiddhiAppRuntimeException(
                "device grouped-agg path: a group accumulated >= 2^15 "
                "events; exact running integer sums exceed the i32 "
                "partial-sum bound — re-plan with @app:engine('host')")
        out: Dict[str, Any] = {"mask": ok} if sel_idx is None else \
            {"sel_rows": sel_idx}
        for (name, kind, ref) in self.outputs:
            if kind == "key":
                rows_sel = ok if sel_idx is None else sel_idx
                out[name] = np.asarray(data.columns[ref])[rows_sel]
                continue
            if kind == "count":
                out[name] = counts
                continue
            if kind == "stddev":
                vx, vh, vl = ref

                def pair(v):
                    return col(0, v.vidx).astype(np.float64) + \
                        col(1, v.vidx).astype(np.float64)
                sx = pair(vx)
                sxx = pair(vh) + pair(vl)
                with np.errstate(invalid="ignore", divide="ignore"):
                    c = np.maximum(counts, 1)
                    var = sxx / c - (sx / c) ** 2
                    out[name] = np.where(counts > 0,
                                         np.sqrt(np.maximum(var, 0.0)),
                                         np.nan)
                continue
            v: _Value = ref
            j = v.vidx
            if kind in ("sum", "avg"):
                if v.int_mode:
                    sums = reassemble_int_sums(col(2, j), col(3, j))
                else:
                    # two-float pair → f64 (tracks the host's float64
                    # accumulation to ~2^-48 relative)
                    sums = col(0, j).astype(np.float64) + \
                        col(1, j).astype(np.float64)
                if kind == "sum":
                    out[name] = sums
                else:
                    with np.errstate(invalid="ignore", divide="ignore"):
                        out[name] = np.where(
                            counts > 0,
                            sums.astype(np.float64) / np.maximum(counts, 1),
                            np.nan)
                continue
            plane = {"min": 5, "max": 6, "minforever": 9,
                     "maxforever": 10}[kind] + 2 * v.int_mode
            out[name] = col(plane, j)
        return out

    # ------------------------------------------------------------ types

    def output_attr_type(self, kind: str, ref) -> AttrType:
        """Host-parity output types (reference typed aggregator returns)."""
        if kind == "key":
            return {a.name: a.type for a in
                    self.input_definition.attributes}[ref]
        if kind == "count":
            return AttrType.LONG
        if kind == "stddev":
            return AttrType.DOUBLE
        if kind == "sum":
            return AttrType.LONG if ref.int_mode else AttrType.DOUBLE
        if kind == "avg":
            return AttrType.DOUBLE
        # min/max/minForever/maxForever return the input type
        return ref.type

    # ------------------------------------------------------------ snapshot

    def schema_dims(self) -> dict:
        return {"L": int(self.n_lanes), "G": int(self.n_groups),
                "wkind": self.window_kind}

    def current_state(self) -> dict:
        return {"carry": [np.asarray(a) for a in self.carry],
                "n_lanes": self.n_lanes, "n_groups": self.n_groups,
                "window": self.window,
                "ts_base": getattr(self, "_ts_base", None),
                "gid_map": {repr(k): v for k, v in self.gid_map.items()},
                "lane_gids": dict(self._lane_gids)}

    def restore_state(self, state: dict) -> None:
        self.n_lanes = state["n_lanes"]
        self.n_groups = state["n_groups"]
        if self.window_kind == "time":
            self._ts_base = state.get("ts_base")
            if state.get("window", self.window) != self.window:
                self.window = state["window"]
                self._build_step()
        self.carry = type(self.carry)(
            *[jnp.asarray(a) for a in state["carry"]])
        import ast
        self.gid_map = {ast.literal_eval(k): v
                        for k, v in state["gid_map"].items()}
        self._lane_gids = {int(k): v
                           for k, v in state["lane_gids"].items()}

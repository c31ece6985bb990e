"""Keyed window join → the device step (ops/keyed_join.py).

``plan_keyed_join`` reads a ``JoinInputStream`` and says whether the
keyed device runtime takes it (plan/planner.py DeviceKeyedJoinRuntime) —
an inner join of two streams, each filtered and under ``#window.time(t)``
or no window, whose ``on`` is a conjunction with one key equality
``left.x == right.y`` over strings or integers and otherwise comparisons
over float and int attributes — or raises the reason core/join.py keeps
it.  It touches no jax, so the static schema extractor
(analysis/state_schema.py) asks the same question the planner does.

``CompiledKeyedJoin`` is the engine: the carry (per windowed side a ring
``[K, P]``), one registry-jitted step per input stream, lane and slot
growth, the int32 timestamp base, the decode of a block's rows and the
state a snapshot holds.
"""
from __future__ import annotations

from functools import reduce
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..core.stateschema import (Carry, ListOf, Scalar, Struct,
                                persistent_schema)
from ..query_api import (EventTrigger, Filter, JoinInputStream, JoinType,
                         WindowHandler)
from ..query_api.definition import AttrType
from ..query_api.expression import (And, AttributeFunction, Compare,
                                    CompareOp, Constant, In, IsNull,
                                    Variable, variables_of, walk)
from ..query_api.query import OutputEventsFor
from ..utils.errors import SiddhiAppCreationError

#: the lanes an attribute of a windowed side rides in its ring: float32
#: or int32 as they are, 64-bit values as the two halves of their bits,
#: a string as its code in the engine's dictionary (0: null)
_PLANES = {AttrType.FLOAT: ("f",), AttrType.INT: ("i",),
           AttrType.BOOL: ("i",), AttrType.LONG: ("i", "i"),
           AttrType.DOUBLE: ("i", "i"), AttrType.STRING: ("i",)}
_KEY_TYPES = ({AttrType.STRING}, {AttrType.INT, AttrType.LONG})
#: a window the int32 offsets hold with room to spare (12 days)
MAX_WINDOW_MS = 1 << 30
#: rows a block of depth T may deliver before the egress buffer doubles
ROWS_PER_TICK = 2048
#: a block's build side goes up as ``P * T / ROW_SHARE`` compact rows,
#: doubled until they hold the events that carry its values
ROW_SHARE = 64


def attr_planes(attr: str, typ: AttrType) -> Tuple[Tuple[str, str], ...]:
    """The planes of one attribute, as ``(name, "f" | "i")``: its own
    name, or ``<attr>#0`` and ``<attr>#1`` for the halves of 64 bits."""
    kinds = _PLANES[typ]
    return tuple((attr if len(kinds) == 1 else f"{attr}#{i}", k)
                 for i, k in enumerate(kinds))


def _reject(why: str):
    raise SiddhiAppCreationError(f"device keyed join: {why}")


class SidePlan(NamedTuple):
    """One side of a keyed join as the device runtime serves it."""
    index: int                      # 0 left, 1 right
    stream_id: str
    ref: str
    definition: Any
    filters: Tuple[Any, ...]        # filter expressions, before the window
    window_ms: Optional[int]
    key: str                        # the attribute the key equality reads
    carried: Tuple[str, ...]        # attributes a ring entry holds

    @property
    def types(self) -> Dict[str, AttrType]:
        return {a.name: a.type for a in self.definition.attributes}

    @property
    def planes(self) -> Tuple[Tuple[str, str], ...]:
        types = self.types
        return tuple(p for attr in self.carried
                     for p in attr_planes(attr, types[attr]))


class JoinPlan(NamedTuple):
    sides: Tuple[SidePlan, SidePlan]
    triggers: Tuple[bool, bool]
    residual: Any                   # expression, or None
    #: attributes of each side that the step reads off an arriving event:
    #: what its ring carries and what the residual compares
    event_attrs: Tuple[Tuple[str, ...], Tuple[str, ...]]


def joined_names(sides) -> Dict[Tuple[Optional[str], str], Any]:
    """(qualifier or None, attribute) -> the side a variable of the
    joined scope reads: each side by its alias and, where that differs,
    by its stream's name; an unqualified name is the first side's that
    defines it (core/join.py builds its scope from the same table)."""
    names: Dict[Tuple[Optional[str], str], Any] = {}
    for side in sides:
        for a in side.definition.attributes:
            names[(side.ref, a.name)] = side
            if side.stream_id != side.ref:
                names[(side.stream_id, a.name)] = side
            names.setdefault((None, a.name), side)
    return names


def _conjuncts(e) -> List[Any]:
    return _conjuncts(e.left) + _conjuncts(e.right) \
        if isinstance(e, And) else [e]


def _side_plan(index: int, stream, kind_of) -> "SidePlan":
    if stream.is_inner or stream.is_fault:
        _reject("inner and fault streams stay on the host path")
    kind, definition = kind_of(stream.stream_id)
    if kind != "stream":
        _reject(f"'{stream.stream_id}' is a {kind}: its rows are not a "
                f"window ring's")
    filters, window_ms = [], None
    for h in stream.handlers:
        if isinstance(h, Filter):
            if window_ms is not None:
                _reject("a filter behind the window is host-only")
            filters.append(h.expr)
        elif isinstance(h, WindowHandler):
            if h.namespace or h.name.lower() != "time":
                _reject(f"#window.{h.name} has no keyed ring on the device "
                        f"(time windows do)")
            if len(h.params) != 1 or not isinstance(h.params[0], Constant):
                _reject("#window.time needs one constant length")
            window_ms = int(h.params[0].value)
            if not 0 < window_ms < MAX_WINDOW_MS:
                _reject(f"#window.time({window_ms} ms) is outside the "
                        f"int32 offsets' range")
        else:
            _reject("stream functions on a join side are host-only")
    return SidePlan(index, stream.stream_id,
                    stream.stream_ref or stream.stream_id, definition,
                    tuple(filters), window_ms, key="", carried=())


def plan_keyed_join(jis: JoinInputStream, query,
                    kind_of: Callable[[str], Tuple[str, Any]]) -> JoinPlan:
    """The plan of a join the keyed device runtime takes; raises
    ``SiddhiAppCreationError`` with the reason for one it does not.
    ``kind_of(stream id)`` -> ("stream" | "table" | "named window" |
    "aggregation", its definition)."""
    if jis.join_type != JoinType.JOIN:
        _reject(f"{jis.join_type.value} join: null-padded rows are "
                f"host-only")
    if jis.within is not None or jis.per is not None:
        _reject("`within`/`per` belong to aggregation joins")
    if getattr(query.output_stream, "events_for",
               OutputEventsFor.CURRENT) != OutputEventsFor.CURRENT:
        _reject("expired-event output is host-only")
    raw = [_side_plan(i, s, kind_of)
           for i, s in enumerate((jis.left, jis.right))]
    if all(s.window_ms is None for s in raw):
        _reject("neither side has a window: no event meets another")
    if jis.on is None:
        _reject("no on-condition (a cross product has no key)")
    names = joined_names(raw)
    types = [s.types for s in raw]

    def side_of(v: Variable) -> Optional[int]:
        if v.stream_index is not None:
            _reject("indexed event references are a pattern's")
        owner = names.get((v.stream_id, v.attribute))
        return None if owner is None else owner.index

    # the key: the first equality between a string or an integer
    # attribute of each side; the rest of the conjunction is the residual
    key, residual = None, []
    for c in _conjuncts(jis.on):
        if key is None and isinstance(c, Compare) and \
                c.op == CompareOp.EQ and \
                isinstance(c.left, Variable) and \
                isinstance(c.right, Variable):
            a, b = side_of(c.left), side_of(c.right)
            if a is not None and b is not None and a != b:
                pair = {types[a][c.left.attribute],
                        types[b][c.right.attribute]}
                if any(pair <= ok for ok in _KEY_TYPES):
                    key = {a: c.left.attribute, b: c.right.attribute}
                    continue
        residual.append(c)
    if key is None:
        _reject("no key equality left.x == right.y over string or "
                "integer attributes (a non-equi condition probes the "
                "whole window)")
    if raw[0].stream_id == raw[1].stream_id and key[0] != key[1]:
        _reject("a self-join on two different key attributes gives an "
                "event two lanes")

    read = [set(), set()]           # attributes the residual compares
    for c in residual:
        for node in walk(c):
            if isinstance(node, (AttributeFunction, In, IsNull)):
                _reject("functions, `in` and `is null` in the on-condition "
                        "are host-only")
        for v in variables_of(c):
            s = side_of(v)
            if s is None:
                _reject(f"'{v.attribute}' in the on-condition is no "
                        f"attribute of either side")
            if types[s][v.attribute] not in (AttrType.FLOAT, AttrType.INT):
                _reject(f"the residual compares '{v.attribute}' "
                        f"({types[s][v.attribute].name}): only float and "
                        f"int attributes have 32-bit lanes")
            read[s].add(v.attribute)

    # what the select reads of each side (a name that is no attribute of
    # either side is an output name in a having or an order-by)
    sel = query.selector
    want = [set(read[0]), set(read[1])]
    if sel.select_all:
        for s in (0, 1):
            want[s] |= set(types[s])
    exprs = [oa.expr for oa in sel.attributes] + list(sel.group_by) + \
        [sel.having] + [o.variable for o in sel.order_by]
    for e in exprs:
        for v in variables_of(e) if e is not None else ():
            s = side_of(v)
            if s is not None:
                want[s].add(v.attribute)

    sides = []
    for i, s in enumerate(raw):
        carried = ()
        if s.window_ms is not None:
            carried = tuple(a for a in types[i]
                            if a in want[i] and a != key[i])
            for a in carried:
                if types[i][a] not in _PLANES:
                    _reject(f"'{s.ref}.{a}' ({types[i][a].name}) of a "
                            f"windowed side has no lane in the ring")
        sides.append(s._replace(key=key[i], carried=carried))
    trig = jis.trigger
    return JoinPlan(
        sides=tuple(sides),
        triggers=(trig in (EventTrigger.ALL, EventTrigger.LEFT),
                  trig in (EventTrigger.ALL, EventTrigger.RIGHT)),
        residual=reduce(And, residual) if residual else None,
        event_attrs=tuple(
            tuple(a for a in types[i]
                  if a in sides[i].carried or a in read[i])
            for i in (0, 1)))


# ------------------------------------------------------------- the engine

def halves(col: np.ndarray, typ: AttrType) -> List[np.ndarray]:
    """A numeric column as the arrays of its planes (`attr_planes`)."""
    if typ == AttrType.FLOAT:
        return [np.asarray(col, np.float32)]
    if typ in (AttrType.INT, AttrType.BOOL):
        return [np.asarray(col).astype(np.int32)]
    bits = np.ascontiguousarray(
        col, np.int64 if typ == AttrType.LONG else np.float64) \
        .view(np.int64)
    return [(bits >> 32).astype(np.int32),
            (bits & 0xFFFFFFFF).astype(np.uint32).view(np.int32)]


def compact_rows(lanes: np.ndarray, ticks: np.ndarray, vals: np.ndarray,
                 P: int, T: int) -> Tuple[np.ndarray, np.ndarray]:
    """One group of a ``[P, T]`` block's int planes as the step takes it:
    ``(idx [R], vals [n, R])`` int32, per row the flat cell ``tick * P +
    lane`` of the event at ``lanes``, ``ticks`` and its values, then
    padding at ``P * T``, which the step drops.  ``R`` is ``P * T /
    ROW_SHARE``, doubled until it holds the rows and at most ``P * T``:
    a function of the block's shape unless its build side is that
    full."""
    n_cells = P * T
    R = max(n_cells // ROW_SHARE, 1)
    while R < len(lanes):
        R *= 2
    R = min(R, n_cells)
    idx = np.full(R, n_cells, np.int32)
    idx[:len(lanes)] = ticks * P + lanes
    out = np.zeros((len(vals), R), np.int32)
    out[:, :len(lanes)] = vals
    return idx, out


def _whole(lanes: List[np.ndarray], typ: AttrType) -> np.ndarray:
    """The inverse of `halves`, from int32 bit lanes."""
    if typ == AttrType.FLOAT:
        return lanes[0].view(np.float32)
    if typ == AttrType.INT:
        return lanes[0]
    if typ == AttrType.BOOL:
        return lanes[0] != 0
    bits = (lanes[0].astype(np.int64) << 32) | \
        lanes[1].view(np.uint32).astype(np.int64)
    return bits if typ == AttrType.LONG else bits.view(np.float64)


@persistent_schema(
    "join-engine", version=1,
    schema=Struct(carry=Carry(), n_lanes=Scalar("int"),
                  n_slots=Scalar("int"), ts_base=Scalar("opt_int"),
                  str_decoder=ListOf("str")),
    dims={"P": "free", "K": "free"},
    doc="a keyed join's rings: the lane and slot counts are adopted by "
        "restore (both only ever grow); a carried string is its code in "
        "str_decoder")
class CompiledKeyedJoin:
    """The rings of one keyed window join over ``n_lanes`` key lanes."""

    def __init__(self, plan: JoinPlan, n_lanes: int, n_slots: int):
        from ..ops.keyed_join import JOIN_CTR, JoinSpec, Ring, make_carry
        self.plan = plan
        self.n_lanes = n_lanes
        self.n_slots = n_slots
        self.spec = JoinSpec(
            rings=tuple(None if s.window_ms is None
                        else Ring(s.window_ms, s.planes)
                        for s in plan.sides),
            triggers=plan.triggers,
            residual=self._compile_residual())
        self.carry = make_carry(self.spec, n_lanes, n_slots)
        self.ts_base: Optional[int] = None
        self._max_window = max(s.window_ms or 0 for s in plan.sides)
        self._cap_shift = 0
        self._steps: Dict[Tuple[bool, bool], Any] = {}
        #: JOIN_CTR summed over the lanes, as the last retired tail gave
        #: them
        self._counts = np.zeros(len(JOIN_CTR), np.uint32)
        #: the strings the rings hold, by code (grows on first sight of
        #: a value, as an automaton's str_encoder does); 0 is null
        self.str_decoder: List[Optional[str]] = [None]
        self._str_code: Dict[str, int] = {}
        #: per attribute, the side bits of the events whose value the
        #: step reads off the block (it enters their side's ring, or the
        #: residual compares it): only those are placed, and take a code
        self._read_sides: Dict[str, int] = {}
        for i in (0, 1):
            for a in plan.event_attrs[i]:
                self._read_sides[a] = self._read_sides.get(a, 0) | (1 << i)

    # ------------------------------------------------------------- build

    def _compile_residual(self):
        if self.plan.residual is None:
            return None
        import jax.numpy as jnp

        from .expr_compiler import EvalCtx, ExprCompiler, Scope
        scope = Scope()
        for (qual, attr), side in joined_names(self.plan.sides).items():
            scope.add(qual, attr, side.types[attr],
                      lambda ctx, _i=side.index, _a=attr:
                      ctx.qualified[_i][_a])
        on = ExprCompiler(scope, jnp).compile(self.plan.residual)
        stamp = np.zeros(1, np.int32)
        return lambda lv, rv: on.fn(EvalCtx({}, stamp, 1,
                                            qualified={0: lv, 1: rv}))

    def event_attrs(self, present: Tuple[bool, bool]):
        """(attribute, type) of the columns the step reads off the
        events of a block that holds the sides ``present``."""
        return tuple(dict.fromkeys(
            (a, self.plan.sides[i].types[a]) for i in (0, 1) if present[i]
            for a in self.plan.event_attrs[i]))

    def row_groups(self, present: Tuple[bool, bool]):
        """The columns the step reads off the events of a block that
        holds the sides ``present`` and that ride int planes, grouped by
        the events that carry them: per group ``(side bits, ((attribute,
        type), ...))``.  In q20 one group: the auction's nine columns."""
        mask = present[0] | present[1] << 1
        groups: Dict[int, list] = {}
        for attr, typ in self.event_attrs(present):
            if typ != AttrType.FLOAT:
                groups.setdefault(self._read_sides[attr] & mask, []) \
                    .append((attr, typ))
        return tuple((bits, tuple(cols)) for bits, cols in groups.items())

    def _group_planes(self, present: Tuple[bool, bool]):
        """The names of the int planes of each of ``row_groups``."""
        return tuple(tuple(name for attr, typ in cols
                           for name, _k in attr_planes(attr, typ))
                     for _bits, cols in self.row_groups(present))

    def event_planes(self, present: Tuple[bool, bool],
                     columns: Dict[str, np.ndarray], bits: np.ndarray):
        """The values the step reads off the events of a block (``bits``:
        per event the sides it is on), of the events that carry them
        only -> (``{"f:<name>": (at, float32 values)}`` per float
        column, ``(at, [n, len(at)] int32)`` per group of
        ``row_groups(present)``), ``at`` those events' places.  In q20
        they are the auctions, one placed event in eighty."""
        floats = {}
        for attr, typ in self.event_attrs(present):
            if typ == AttrType.FLOAT:
                at = np.flatnonzero(bits & self._read_sides[attr])
                floats[f"f:{attr}"] = (
                    at, np.asarray(columns[attr], np.float32)[at])
        groups = []
        for side_bits, cols in self.row_groups(present):
            at = np.flatnonzero(bits & side_bits)
            groups.append((at, np.stack([
                p for attr, typ in cols
                for p in self._int_planes(np.asarray(columns[attr])[at],
                                          typ)])))
        return floats, groups

    def _int_planes(self, vals: np.ndarray, typ: AttrType
                    ) -> List[np.ndarray]:
        if typ != AttrType.STRING:
            return halves(vals, typ)
        return [np.fromiter(map(self._encode_str, vals.tolist()),
                            np.int32, len(vals))]

    def _encode_str(self, v) -> int:
        if v is None:
            return 0
        code = self._str_code.get(v)
        if code is None:
            code = self._str_code[v] = len(self.str_decoder)
            self.str_decoder.append(v)
        return code

    def _strings(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), object)
        out[:] = [self.str_decoder[c] for c in codes.tolist()]
        return out

    def trace(self, present: Tuple[bool, bool]) -> None:
        """Trace the step of the sides ``present`` over an abstract
        block, compiling nothing: what jnp cannot express raises here."""
        import jax

        from ..ops.keyed_join import build_step
        shape = (self.n_lanes, 1)
        block = {"ts": jax.ShapeDtypeStruct(shape, np.int32),
                 "side": jax.ShapeDtypeStruct(shape, np.int32)}
        for attr, typ in self.event_attrs(present):
            if typ == AttrType.FLOAT:
                block[f"f:{attr}"] = jax.ShapeDtypeStruct(shape, np.float32)
        groups = self._group_planes(present)
        none = np.empty(0, np.int32)
        block["rows"] = tuple(
            compact_rows(none, none, np.empty((len(names), 0), np.int32),
                         self.n_lanes, 1)
            for names in groups)
        fn = build_step(self.spec, present, groups)
        jax.eval_shape(lambda c, b: fn(c, b, 64), self.carry, block)

    def step_for(self, present: Tuple[bool, bool]):
        """The registry-jitted step for blocks of the sides ``present``
        (one per input stream; the two sides of a self-join share one)."""
        step = self._steps.get(present)
        if step is None:
            from ..ops.keyed_join import build_step
            from .shapes import shape_registry
            step = self._steps[present] = shape_registry().jit(
                "join.keyed_step",
                {"sides": "".join("LR"[i] for i in (0, 1) if present[i]),
                 "rings": "".join("LR"[s.index] for s in self.plan.sides
                                  if s.window_ms is not None),
                 "planes": sum(len(s.planes) for s in self.plan.sides),
                 "residual": self.plan.residual is not None},
                build_step(self.spec, present, self._group_planes(present)),
                static_argnums=2)
            self._book()
        return step

    def _book(self) -> None:
        """The carry's bytes onto the step's launch books."""
        import jax
        step = next(iter(self._steps.values()), None)
        if step is not None:
            step.book_live(self, sum(
                a.nbytes for a in jax.tree_util.tree_leaves(self.carry)))

    # ------------------------------------------------------------ growth

    def grow(self, n_lanes: int) -> None:
        if n_lanes > self.n_lanes:
            from ..ops.keyed_join import grow_lanes
            self.carry = grow_lanes(self.carry, n_lanes)
            self.n_lanes = n_lanes
            self._book()

    def grow_slots(self) -> None:
        from ..ops.keyed_join import double_slots
        self.carry = double_slots(self.carry)
        self.n_slots *= 2
        self._book()

    def cap_for(self, T: int) -> int:
        return (ROWS_PER_TICK * T) << self._cap_shift

    def widen(self, tail: np.ndarray, T: int) -> int:
        """After a step whose result is not whole: double the slot ring
        if a lane was full of live entries, and the egress buffer until
        it holds the rows.  -> the ring's doublings."""
        grown = int(tail[1] > 0)
        if grown:
            self.grow_slots()
        while tail[0] > self.cap_for(T):
            self._cap_shift += 1
        return grown

    # ---------------------------------------------------------- the step

    def offsets(self, ts: np.ndarray, retire: Callable[[], None]
                ) -> np.ndarray:
        """A block's timestamps as int32 offsets from the engine's base,
        which moves (and the rings' timestamps with it) before they
        would leave the int32 range (ops/ts32.py); ``retire()`` is
        called first then, for what is in flight started from the rings
        as they were."""
        from ..ops.ts32 import rebase_offsets, safe_max, shift_clamped
        before = self.ts_base
        if before is not None and \
                int(ts.max()) - before > safe_max(self._max_window):
            retire()
        offs, self.ts_base, _ = rebase_offsets(
            ts, np.ones(len(ts), bool), before, self._max_window, None, 0,
            site="join.ts32")
        if before is not None and self.ts_base != before:
            delta = self.ts_base - before
            lo = -(1 << 31) + 1         # expired at every later event
            self.carry = dict(self.carry, ring=tuple(
                None if r is None else
                dict(r, ts=shift_clamped(r["ts"], delta, lo))
                for r in self.carry["ring"]))
        return offs

    def process_block(self, block: Dict[str, np.ndarray],
                      present: Tuple[bool, bool]):
        """Step the rings over one packed block -> (rows, tail), both
        still on the device, and the egress buffer's capacity."""
        cap = self.cap_for(block["ts"].shape[1])
        self.carry, rows, tail = self.step_for(present)(
            self.carry, block, cap)
        return rows, tail, cap

    def count_delta(self, tail: np.ndarray) -> np.ndarray:
        """What JOIN_CTR grew by from the last retired block's tail to
        this one's (the lanes' sums wrap as uint32 do)."""
        counts = tail[2:].astype(np.uint32)
        delta = (counts - self._counts).astype(np.int64)
        self._counts = counts
        return delta

    # ------------------------------------------------------------ decode

    def decode(self, rows: np.ndarray, shape: Tuple[int, int, int],
               present: Tuple[bool, bool]):
        """A block's rows ``[n, 2 + C]`` -> per row its probing side,
        lane, tick, the matched entry's arrival count in its lane, and
        the entry's attributes ``{attr: column}`` per ring side."""
        from ..ops.keyed_join import directions
        T, K, P = shape
        dirs = directions(self.spec, present)
        flat = rows[:, 0].astype(np.int64)
        d, rem = np.divmod(flat, T * K * P)
        tick, rem = np.divmod(rem, K * P)
        lane = rem % P
        side = np.asarray(dirs, np.int64)[d]
        entry: Dict[int, Dict[str, np.ndarray]] = {}
        for s in dirs:
            ring = self.plan.sides[1 - s]
            types = ring.types
            at, cols = 2, {}
            for attr in ring.carried:
                n = len(_PLANES[types[attr]])
                lanes = [np.ascontiguousarray(rows[:, at + j])
                         for j in range(n)]
                # (a row of the other direction holds the other ring's
                # planes here: no code of this dictionary)
                cols[attr] = self._strings(np.where(side == s, lanes[0], 0)) \
                    if types[attr] == AttrType.STRING \
                    else _whole(lanes, types[attr])
                at += n
            entry[1 - s] = cols
        return side, lane, tick, rows[:, 1], entry

    # ------------------------------------------------------------- state

    def schema_dims(self) -> dict:
        return {"P": int(self.n_lanes), "K": int(self.n_slots)}

    def current_state(self) -> dict:
        return {"carry": {path: np.asarray(a)
                          for path, a in _leaves(self.carry)},
                "n_lanes": self.n_lanes, "n_slots": self.n_slots,
                "ts_base": self.ts_base,
                "str_decoder": list(self.str_decoder)}

    def restore_state(self, state: dict) -> None:
        import jax
        import jax.numpy as jnp

        from ..ops.keyed_join import make_carry
        self.n_lanes, self.n_slots = state["n_lanes"], state["n_slots"]
        self.ts_base = state["ts_base"]
        self.str_decoder = list(state["str_decoder"])
        self._str_code = {v: c for c, v in enumerate(self.str_decoder)
                          if c}
        like = make_carry(self.spec, 1, 1)
        self.carry = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(like),
            [jnp.asarray(state["carry"][path]) for path, _ in _leaves(like)])
        self._counts = np.asarray(
            jnp.sum(self.carry["ctr"], axis=1)).astype(np.uint32)
        self._book()


def _leaves(carry):
    """(path, array) of a carry's leaves, the path as `ring/1/ts`."""
    import jax
    return [("/".join(str(getattr(k, "key", getattr(k, "idx", None)))
                      for k in path), a)
            for path, a in jax.tree_util.tree_leaves_with_path(carry)]

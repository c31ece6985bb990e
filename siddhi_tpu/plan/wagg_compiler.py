"""Windowed-aggregation query → TPU kernel (BASELINE config 2 path).

Lowers `from S[filter]#window.length(W) select sum(x)/count()/avg(x) group by
<partition key>` into ops/windowed_agg: the filter and the aggregated value
expression compile once through the shared expression compiler under
jax.numpy and run as one fused [P, T] program; the stateful sliding-window
update runs as the Pallas ring kernel on TPU (jnp scan elsewhere).

The group-by key is the partition axis — the same key→lane mapping the NFA
path and the reference's per-key partitioning use (SURVEY.md §2.8)."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler import SiddhiCompiler
from ..query_api import Filter, Query, SingleInputStream
from ..core.stateschema import (CarryTuple, Scalar, Struct,
                                persistent_schema)
from ..query_api.definition import AttrType
from ..query_api.expression import AttributeFunction, Constant, Variable
from ..utils.errors import SiddhiAppCreationError
from .expr_compiler import EvalCtx, ExprCompiler, Scope
from ..ops.windowed_agg import (LANES, TimeWaggCarry, WaggCarry,
                                build_time_wagg_step, build_wagg_step,
                                build_wagg_step_pallas, make_time_wagg_carry,
                                make_wagg_carry)

_AGGS = {"sum", "count", "avg", "min", "max"}

TIME_CAPACITY_START = 64      # initial time-window ring capacity (doubles
                              # on overflow; the caller replays the block)


@persistent_schema(
    "wagg-engine", version=1,
    schema=Struct(carry=CarryTuple(), n_partitions=Scalar("int"),
                  window_kind=Scalar("str"), window=Scalar("num"),
                  ts_base=Scalar("opt_int")),
    dims={"P": "free", "wkind": "exact"},
    doc="partition-lane count is adopted by restore; the window kind "
        "decides the carry tuple class and is plan-fixed")
class CompiledWindowedAgg:
    """One length-window aggregation query over P group/partition lanes."""

    def __init__(self, app_string, n_partitions: int,
                 t_per_block: int = 16, query_name: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 query: Optional[Query] = None):
        app = (SiddhiCompiler.parse(app_string)
               if isinstance(app_string, str) else app_string)
        if query is None:
            for el in app.execution_elements:
                if isinstance(el, Query) and (query_name is None or
                                              el.name == query_name):
                    query = el
                    break
        if query is None:
            raise SiddhiAppCreationError(f"No query '{query_name}'")
        s = query.input_stream
        if not isinstance(s, SingleInputStream):
            raise SiddhiAppCreationError(
                "windowed-agg path needs a single input stream")
        wh = s.window_handler
        kind = (wh.name.lower() if wh is not None else "")
        if kind == "length":
            self.window_kind = "length"
            self.ts_attr = None
            self.window = int(wh.params[0].value)
        elif kind in ("time", "externaltime"):
            # time(t): arrival-ts driven; externalTime(tsAttr, t): the same
            # masked-expiry ring driven by the event's own timestamp
            # attribute (reference ExternalTimeWindowProcessor)
            self.window_kind = "time"
            if kind == "externaltime":
                if len(wh.params) != 2 or \
                        not isinstance(wh.params[0], Variable):
                    raise SiddhiAppCreationError(
                        "externalTime needs (tsAttr, window)")
                self.ts_attr = wh.params[0].attribute
                span = wh.params[1]
            else:
                self.ts_attr = None
                span = wh.params[0] if wh.params else None
            if not isinstance(span, Constant):
                raise SiddhiAppCreationError(
                    f"{wh.name} needs a constant window length")
            self.window_ms = int(span.value)
            self.window = TIME_CAPACITY_START
            self._ts_base = None      # i64→i32 offset rebasing base
        else:
            raise SiddhiAppCreationError(
                "windowed-agg path needs #window.length(n), "
                "#window.time(t) or #window.externalTime(tsAttr, t)")
        definition = app.stream_definitions[s.stream_id]
        if self.ts_attr is not None:
            at = {a.name: a.type for a in definition.attributes}.get(
                self.ts_attr)
            if at is None:
                raise SiddhiAppCreationError(
                    f"externalTime: '{self.ts_attr}' is not an attribute "
                    f"of '{s.stream_id}'")
            if at not in (AttrType.LONG, AttrType.INT):
                raise SiddhiAppCreationError(
                    f"externalTime: '{self.ts_attr}' must be INT/LONG, "
                    f"got {at}")

        scope = Scope()
        scope.add_primary(s.stream_id, s.stream_ref, definition)
        compiler = ExprCompiler(scope, jnp)
        filters = [compiler.compile(h.expr) for h in s.handlers
                   if isinstance(h, Filter)]
        self.filters = filters

        # outputs: aggregates of ONE value expression + key passthroughs
        # (name, sum|count|avg|key, key_attr_or_None)
        self.outputs: List[Tuple[str, str, Optional[str]]] = []
        value_expr = None
        value_ast = None
        for oa in query.selector.attributes:
            e = oa.expr
            if isinstance(e, AttributeFunction) and e.name.lower() in _AGGS:
                fname = e.name.lower()
                if e.args:
                    # the kernel carries one value lane: every aggregate must
                    # ride the same argument expression (count() is arg-free)
                    if value_ast is not None and e.args[0] != value_ast:
                        raise SiddhiAppCreationError(
                            "windowed-agg path supports aggregates of a "
                            f"single shared argument expression; got both "
                            f"{value_ast} and {e.args[0]}")
                    if value_expr is None:
                        value_expr = compiler.compile(e.args[0])
                        value_ast = e.args[0]
                self.outputs.append((oa.rename, fname, None))
            elif isinstance(e, Variable):
                self.outputs.append((oa.rename, "key", e.attribute))
            else:
                raise SiddhiAppCreationError(
                    "windowed-agg select supports sum/count/avg/min/max of "
                    "one expression plus key attributes")
        self.value = value_expr
        self.want_minmax = any(k in ("min", "max")
                               for _, k, _ in self.outputs)
        self.filter_exprs = [h.expr for h in s.handlers
                             if isinstance(h, Filter)]
        self.input_definition = definition
        self.stream_id = s.stream_id
        self.n_partitions = n_partitions
        self.t_per_block = t_per_block
        if use_pallas is None:
            use_pallas = self.window_kind == "length" and \
                jax.devices()[0].platform == "tpu" and \
                n_partitions % LANES == 0
        self.use_pallas = use_pallas
        # numeric sentinels (core/numguard.py, SIDDHI_TPU_NUMGUARD):
        # host-rim witnesses over arrays the retire path already fetches
        from ..core.numguard import numeric_sentinels, numguard_enabled
        self.sentinels = numeric_sentinels(app.name or "?") \
            if numguard_enabled() else None
        self._build_step()
        self.carry = self._make_carry(n_partitions)

    def _build_step(self):
        if self.window_kind == "length":
            step = (build_wagg_step_pallas(self.window, self.t_per_block,
                                           self.want_minmax)
                    if self.use_pallas
                    else build_wagg_step(self.window, self.want_minmax))
        else:
            step = build_time_wagg_step(self.window_ms, self.window,
                                        self.want_minmax)

        def full_step(carry, block: Dict[str, jnp.ndarray]):
            # filter + projection: one fused elementwise program over [P, T]
            n = block["__ts"].size
            cols = {k: v.reshape(-1) for k, v in block.items()
                    if not k.startswith("__")}
            ctx = EvalCtx(cols, block["__ts"].reshape(-1), n)
            ok = block["__valid"].reshape(-1)
            for f in self.filters:
                m = f.fn(ctx)
                ok = ok & jnp.broadcast_to(jnp.asarray(m, bool), ok.shape)
            vals = (jnp.broadcast_to(
                jnp.asarray(self.value.fn(ctx), jnp.float32), ok.shape)
                if self.value is not None else jnp.zeros(ok.shape,
                                                         jnp.float32))
            shape = block["__ts"].shape
            if self.window_kind == "time":
                # i32 ts offsets (rebased in process_block) for
                # cross-block window expiry
                return step(carry, vals.reshape(shape), block["__ts32"],
                            ok.reshape(shape))
            return step(carry, vals.reshape(shape), ok.reshape(shape))

        # no donation on the time path: overflow replay re-steps the block
        # from the PREVIOUS carry, which donation would have invalidated
        donate = (0,) if self.window_kind == "length" else ()
        from .shapes import shape_registry
        self._step = shape_registry().jit(
            f"wagg.{self.window_kind}.step",
            {"win": self.window,
             "win_ms": getattr(self, "window_ms", 0),
             "filters": len(self.filters),
             "minmax": self.want_minmax, "pallas": self.use_pallas,
             "donate": bool(donate)},
            full_step, donate_argnums=donate)

    def _make_carry(self, n: int):
        return (make_wagg_carry(n, self.window)
                if self.window_kind == "length"
                else make_time_wagg_carry(n, self.window))

    def grow(self, n_partitions: int) -> None:
        """Widen the group-lane axis (keyed partitioning slab growth).
        Growth concatenates onto the COMMITTED carry, so a shard-pinned
        engine (parallel/shards.py) grows on its own device."""
        if n_partitions <= self.n_partitions:
            return
        if self.use_pallas and n_partitions % LANES:
            n_partitions = ((n_partitions // LANES) + 1) * LANES
        fresh = self._make_carry(n_partitions - self.n_partitions)
        self.carry = type(self.carry)(
            *[jnp.concatenate([a, b], axis=0)
              for a, b in zip(self.carry, fresh)])
        self.n_partitions = n_partitions

    # ------------------------------------------------ partition shard-out

    def pin_to_device(self, device) -> None:
        """Commit the carry to one device (parallel/shards.py): jit
        dispatch follows committed operands, so steps and growth stay
        shard-local."""
        self.shard_device = device
        self.carry = jax.device_put(self.carry, device)

    def clone_for_shard(self, device) -> "CompiledWindowedAgg":
        """Fresh-state shard clone pinned to `device`: shares the jitted
        step and all compiled plans; owns its carry (and time-ring
        rebasing base), so capacity growth is shard-local."""
        import copy
        cl = copy.copy(self)
        cl.shard_device = device
        if cl.window_kind == "time":
            cl._ts_base = None
        cl.carry = jax.device_put(cl._make_carry(cl.n_partitions), device)
        return cl

    # ------------------------------------------------- time-window capacity

    def overflowed(self) -> bool:
        """True if any lane evicted a still-in-window entry (time mode) —
        the just-processed block's results undercount; grow and replay."""
        return self.window_kind == "time" and \
            bool(np.asarray(self.carry.overflow).any())

    def grow_capacity(self, new_capacity: int) -> None:
        """Double the time-window ring (keeps entries, chronological
        compaction so the slot-fill invariant `valid slots = [0, cnt)`
        holds in the new ring)."""
        from ..ops.windowed_agg import TS_EMPTY
        assert self.window_kind == "time"
        if new_capacity <= self.window:
            return
        old = self.carry
        P, W = np.asarray(old.ring).shape
        ring = np.asarray(old.ring)
        rts = np.asarray(old.ring_ts)
        cnt = np.array(old.cnt)        # writable copy (compacted counts)
        new_ring = np.zeros((P, new_capacity), np.float32)
        new_rts = np.full((P, new_capacity), TS_EMPTY, np.int32)
        # chronological order survives argsort on ts (TS_EMPTY = empty
        # sorts first and is dropped)
        order = np.argsort(rts, axis=1, kind="stable")
        keep = np.take_along_axis(rts, order, 1) != TS_EMPTY
        for p in range(P):                      # host-side, grow-time only
            sel = order[p][keep[p]]
            k = len(sel)
            new_ring[p, :k] = ring[p, sel]
            new_rts[p, :k] = rts[p, sel]
            cnt[p] = k
        self.window = new_capacity
        self.carry = TimeWaggCarry(
            ring=jnp.asarray(new_ring), ring_ts=jnp.asarray(new_rts),
            pos=jnp.asarray(cnt % new_capacity, jnp.int32),
            cnt=jnp.asarray(cnt, jnp.int32),
            last_ts=old.last_ts,
            overflow=jnp.zeros((P,), bool))
        self._build_step()

    def schema_dims(self) -> dict:
        return {"P": int(self.n_partitions), "wkind": self.window_kind}

    def current_state(self) -> dict:
        return {"carry": [np.asarray(a) for a in self.carry],
                "n_partitions": self.n_partitions,
                "window_kind": self.window_kind, "window": self.window,
                "ts_base": getattr(self, "_ts_base", None)}

    def restore_state(self, state: dict) -> None:
        self.n_partitions = state["n_partitions"]
        if state.get("window", self.window) != self.window and \
                self.window_kind == "time":
            self.window = state["window"]
            self._build_step()
        if self.window_kind == "time":
            self._ts_base = state.get("ts_base")
        cls = WaggCarry if self.window_kind == "length" else TimeWaggCarry
        self.carry = cls(*[jnp.asarray(a) for a in state["carry"]])

    def process_block(self, block):
        """block: [P, T] packed lanes (ops.nfa.pack_blocks; time mode also
        needs block['__ts64'] absolute i64 lanes) →
        (sums [P, T], counts [P, T][, mins, maxs]) running aggregates.
        Time mode: on slot overflow, grows the ring and replays the block
        from the pre-block carry, so results are always exact."""
        if self.window_kind == "length":
            block = {k: v for k, v in block.items() if k != "__ts64"}
            self.carry, outs = self._step(self.carry, block)
            return outs
        block = self._with_ts_offsets(block)
        while True:
            prev = self.carry
            self.carry, outs = self._step(prev, block)
            if not self.overflowed():
                return outs
            self.carry = prev
            self.grow_capacity(self.window * 2)

    def _with_ts_offsets(self, block) -> Dict[str, jnp.ndarray]:
        """Derive the kernel's i32 `__ts32` lanes from the block's absolute
        i64 `__ts64` lanes via the SHARED rebase protocol
        (ops/ts32.rebase_offsets — x64 is disabled under jit; ~24.8 days
        of stream time per base)."""
        from ..ops.ts32 import rebase_offsets, shift_clamped
        from ..ops.windowed_agg import TS_EMPTY
        ts_abs = np.asarray(block["__ts64"], np.int64)
        valid = np.asarray(block["__valid"])
        base_before = self._ts_base
        offs, self._ts_base, new_ring = rebase_offsets(
            ts_abs.reshape(-1), valid.reshape(-1), self._ts_base,
            self.window_ms, self.carry.ring_ts, TS_EMPTY,
            sentinels=self.sentinels, site="wagg.ts32")
        if new_ring is not self.carry.ring_ts:
            # the ring only shifts when a prior base moved by delta
            delta = self._ts_base - (base_before or 0)
            last = shift_clamped(self.carry.last_ts, delta, TS_EMPTY + 1)
            self.carry = self.carry._replace(ring_ts=new_ring, last_ts=last)
        out = {k: v for k, v in block.items() if k != "__ts64"}
        out["__ts32"] = jnp.asarray(offs.reshape(ts_abs.shape))
        return out

    def current_aggregates(self) -> Dict[str, np.ndarray]:
        """Per-lane aggregate values right now."""
        if self.window_kind == "time":
            ring = np.asarray(self.carry.ring)
            rts = np.asarray(self.carry.ring_ts)
            cnt = np.asarray(self.carry.cnt)
            now = np.asarray(self.carry.last_ts)
            valid = (np.arange(self.window)[None, :] < cnt[:, None]) & \
                (rts > (now - self.window_ms)[:, None])
            s = np.where(valid, ring, 0.0).sum(axis=1)
            c = valid.sum(axis=1)
        else:
            s = np.asarray(self.carry.runsum)
            c = np.asarray(self.carry.cnt)
            ring = None               # D2H of the [P, W] ring only if a
            valid = None              # min/max output actually needs it
        if self.sentinels is not None:
            # NUMGUARD witness over the arrays fetched above — reads
            # only, so outputs stay bit-identical with the guard off
            self.sentinels.observe_floats("wagg.retire", s)
            self.sentinels.observe_counts("wagg.retire", c)
        out = {}
        for name, kind, _attr in self.outputs:
            if kind == "sum":
                out[name] = s
            elif kind == "count":
                out[name] = c.astype(np.int64)
            elif kind == "avg":
                with np.errstate(invalid="ignore", divide="ignore"):
                    out[name] = np.where(c > 0, s / np.maximum(c, 1),
                                         np.nan)
            elif kind in ("min", "max"):
                if ring is None:
                    ring = np.asarray(self.carry.ring)
                    valid = np.arange(self.window)[None, :] < c[:, None]
                fill = np.inf if kind == "min" else -np.inf
                red = np.min if kind == "min" else np.max
                masked = np.where(valid, ring, fill)
                out[name] = red(masked, axis=1)
        return out

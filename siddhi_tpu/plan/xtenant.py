"""Cross-tenant super-dispatch (round 14): many apps, one launch.

PR 8 consolidated dispatches *within* one pattern bank (homogeneous
chunks stacked into a super-carry); a production service runs hundreds
of tenant apps whose automata are individually tiny, and each one still
paid its own jitted step + egress pack per ingest block — a fixed
per-dispatch cost (~18 ms on the round-2 remote runtime,
docs/perf_notes.md; unmeasured on the attached chip, ROADMAP A1)
multiplied by app count.  This module extends the consolidation *across apps and
query kinds*:

  - a process-level :class:`TenantPacker` buckets eligible automata by
    shape class (state count S, slot capacity K, partitions P, batch B,
    capture rows/cols — padding only ever happens inside one tenant's
    own block, never across tenants);
  - each bucket defers submitted blocks host-side and steps every
    pending tenant in ONE jitted *gang* dispatch: the gang function
    unrolls each tenant's own ``build_block_step(spec)`` AND its egress
    pack at trace time, so heterogeneous condition programs coexist in
    a single XLA executable (kind `nfa.xstep` in the shape registry);
  - co-scheduled tenants register their match buffers on one shared
    :class:`~..plan.pipeline.EgressFuser` — one concatenated D2H slab
    per bucket flush, with per-tenant decode offsets (`seal_block`).

Deferral is only transparent when the caller is already decoupled, so
the packer piggybacks on the pipelining contract (plan/pipeline.py):
with depth 0 every ingest retires inside itself, the bucket flushes
per-submit and behavior degenerates to exactly the per-app dispatches
the legacy path pays.  With depth ≥ 1 (all-@Async junctions or
``@app:pipeline('D')``) blocks from different tenants accumulate and a
repeat submission by any tenant — or any read — flushes the gang.

The gang's arguments are the tenants' carries, one each, and the
distinct arrays among their pending blocks, each once
(``_distinct_planes``, by identity): ``gang(carries, planes)`` puts
tenant i's block together from ``planes`` inside the trace.  Which
tenant reads which argument is static, so it is part of the gang's
cache key beside the tenants' signatures, and the number of plane
arguments is a dim of its registry entry.  The pattern queries of one
partition hold one block of a chunk (ops/nfa.SharedPlanes: equal inputs
give the same read-only arrays), so four of them upload six planes where
they uploaded 24; tenants of different apps, or queries whose lanes or
codes differ, hold arrays of their own and get the program they always
got.  Each tenant's handle keeps its own complete block (a dict of its
own), whoever else reads the same arrays.

Grow-and-replay stays correct at bucket granularity: tenant sub-steps
inside the gang are mutually independent (separate carries; blocks are
only ever read), so one tenant's slot overflow never corrupts
co-tenants.  The planner rewinds ONLY the overflowing tenant to its
pre-gang carry (handles carry per-tenant snapshots, the gang never
donates), grows its ring and replays through its individual step; the
slot growth re-keys it into a new bucket while co-tenants' gang results
stand.

``SIDDHI_TPU_XTENANT=0`` kills the whole layer (per-app dispatch, the
pre-round-14 behavior); ``SIDDHI_TPU_XTENANT_BUCKET`` bounds tenants
per bucket (compile-size escape hatch).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..core.ledger import ledger as _ledger
from ..core.lockwitness import maybe_wrap

XTENANT_ENV = "SIDDHI_TPU_XTENANT"
BUCKET_CAP_ENV = "SIDDHI_TPU_XTENANT_BUCKET"
# XLA compile time grows superlinearly with the gang's unroll width (a
# 92-tenant gang takes ~3 min on CPU XLA; a 25-tenant one seconds), and
# the dispatch win is already amortized at a few dozen: 100 tenants at
# cap 32 pay ceil(100/32)=4 launches per wall instead of 200.
DEFAULT_BUCKET_CAP = 32


def resolve_xtenant(on: Optional[bool] = None) -> bool:
    if on is None:
        raw = os.environ.get(XTENANT_ENV, "").strip().lower()
        return raw not in ("0", "false", "off", "no")
    return bool(on)


def resolve_bucket_cap() -> int:
    try:
        return max(1, int(os.environ.get(BUCKET_CAP_ENV,
                                         str(DEFAULT_BUCKET_CAP))))
    except ValueError:
        return DEFAULT_BUCKET_CAP


def _shape_key(nfa) -> Tuple:
    """Bucket grouping key: tenants only share a gang when their core
    shapes match (S/K/P/B plus capture geometry and telemetry).  The key
    never forces padding ACROSS tenants — each sub-step runs the
    tenant's own block at its own pow2 T — it just bounds the shape
    diversity one gang executable has to absorb."""
    return (len(nfa.spec.units), nfa.spec.n_slots, nfa.n_partitions,
            nfa.batch_b, max(nfa.spec.n_rows, 1), max(nfa.spec.n_caps, 1),
            bool(nfa.spec.telemetry))


def _gang_sig(nfa) -> Tuple:
    """Per-tenant trace signature: the gang executable bakes in the
    step (spec) and the static egress cap, so any of these changing
    must select a different gang build."""
    return (nfa._xt_id, nfa.spec.n_slots, nfa.n_partitions,
            int(getattr(nfa, "_egress_cap", 1024)))


def _distinct_planes(blocks: List[Dict]) -> Tuple[List[Any], Tuple]:
    """The distinct arrays among the tenants' blocks, by identity, and
    per tenant which of them each of its planes is:
    ((name, index), ...) in the order of the names."""
    planes: List[Any] = []
    index: Dict[int, int] = {}
    reads = []
    for block in blocks:
        mine = []
        for name in sorted(block):
            arr = block[name]
            at = index.get(id(arr))
            if at is None:
                at = index[id(arr)] = len(planes)
                planes.append(arr)
            mine.append((name, at))
        reads.append(tuple(mine))
    return planes, tuple(reads)


def _build_gang(nfas: List[Any], reads: Tuple, trigger: str = "build"):
    """ONE jitted function stepping every tenant's block against its own
    carry and packing its egress — a single XLA executable, a single
    device launch per bucket flush.  Tenants' condition programs are
    heterogeneous (different closures), so this is a trace-time unroll,
    not a vmap; the bucket cap bounds the unroll width.  ``reads`` is
    static (``_distinct_planes``): tenant i's block is put together from
    the gang's plane arguments inside the trace."""
    from ..ops.nfa import build_block_step
    from .shapes import shape_registry
    steps = [build_block_step(n.spec) for n in nfas]
    packs = [n._egress_pack_fn() for n in nfas]
    caps = [int(getattr(n, "_egress_cap", 1024)) for n in nfas]
    absent = [n.has_absent for n in nfas]
    telem = [bool(n.spec.telemetry) for n in nfas]

    def gang(carries, planes):
        out = []
        for i in range(len(steps)):
            block = {name: planes[at] for name, at in reads[i]}
            nc, (mask, cp, ts, enter, seq) = steps[i](carries[i], block)
            dl_st = nc["slot_state"] if absent[i] else None
            dl = nc.get("deadline") if absent[i] else None
            ctr = nc.get("absent_ctr") if absent[i] else None
            buf = packs[i](mask, cp, ts, enter, seq, nc["dropped"],
                           dl_st, dl, caps[i], ctr, nc.get("count_ctr"))
            out.append((nc, buf, (mask, cp, ts, enter, seq),
                        nc.get("telem") if telem[i] else None))
        return out

    # shape-class dims: the bucket's shared shape key (every co-ganged
    # tenant matches it — see _shape_key) plus the gang's unroll width,
    # per-tenant egress caps and the number of its plane arguments,
    # which are baked into the executable
    n0 = nfas[0]
    dims = {"S": len(n0.spec.units), "K": n0.spec.n_slots,
            "P": n0.n_partitions, "B": max(n0.batch_b, 1),
            "R": max(n0.spec.n_rows, 1), "C": max(n0.spec.n_caps, 1),
            "telem": bool(n0.spec.telemetry), "n": len(nfas),
            "caps": tuple(caps),
            "planes": 1 + max(at for r in reads for _name, at in r)}
    return shape_registry().jit("nfa.xstep", dims, gang,
                                trigger=trigger), caps


class TenantBucket:
    """One shape class of packed tenants.  All mutation happens under
    the owning packer's lock; flushes step every pending tenant with one
    gang launch and seal one shared egress slab."""

    def __init__(self, packer: "TenantPacker", key: Tuple):
        from .pipeline import EgressFuser, resolve_egress_fuse
        self.packer = packer
        self.key = key
        S, K, P, B = key[0], key[1], key[2], key[3]
        self.label = f"S{S}K{K}P{P}B{B}"
        self.tenants: List[Any] = []
        self.pending: List[Tuple[Any, Dict, Dict]] = []  # (nfa, block, h)
        self._pending_ids: set = set()
        # cross-tenant fused egress: every co-scheduled tenant's match
        # buffer rides one slab, sealed explicitly at end of flush
        self.fuser = (EgressFuser(f"xtenant:{self.label}")
                      if resolve_egress_fuse() else None)
        self._gangs: Dict[Tuple, Tuple[Any, List[int]]] = {}
        self.deferred_total = 0
        self.flush_total = 0

    # ------------------------------------------------------------ pending

    def has_pending(self, nfa) -> bool:
        return id(nfa) in self._pending_ids

    def submit(self, nfa, block: Dict, ts_range) -> Dict:
        """Queue one packed block; returns the (unresolved) handle the
        planner keeps in flight.  The caller must have called
        :meth:`sync` first (dispatch_events does), so a tenant never has
        two pending blocks."""
        with self.packer._lock:
            h = {"xpend": self, "block": block, "ts_range": ts_range,
                 "base_ts": nfa.base_ts}
            self.pending.append((nfa, block, h))
            self._pending_ids.add(id(nfa))
            self.deferred_total += 1
            return h

    def sync(self, nfa) -> None:
        """Apply this tenant's pending block (by flushing the bucket)
        before any out-of-band carry access: re-submission, timer steps,
        rebase, snapshot/restore."""
        with self.packer._lock:
            if id(nfa) in self._pending_ids:
                self._flush_locked()

    def resolve(self, h: Dict) -> None:
        """Make a deferred handle retirable: if its gang step has not
        run yet, flush the bucket now (any read forces the flush)."""
        with self.packer._lock:
            if "xpend" in h:
                self._flush_locked()

    def flush(self) -> None:
        with self.packer._lock:
            self._flush_locked()

    # ------------------------------------------------------------ the gang

    def _flush_locked(self) -> None:
        entries = self.pending
        if not entries:
            return
        # device.sync: everything of a flush but the gang call itself,
        # which is a device.issue span of its own inside this one
        with _ledger().span("device", "sync"):
            self._gang_step(entries)

    def _gang_step(self, entries: List[Tuple[Any, Dict, Dict]]) -> None:
        self.pending = []
        self._pending_ids = set()
        nfas = [e[0] for e in entries]
        # an array that several tenants' blocks hold (the queries of one
        # partition: ops/nfa.SharedPlanes) is one argument, uploaded once
        planes, reads = _distinct_planes([e[1] for e in entries])
        sig = (tuple(_gang_sig(n) for n in nfas), reads)
        cached = self._gangs.get(sig)
        if cached is None:
            # a second gang build on a live bucket means membership, a
            # tenant's shape or what its tenants share re-keyed — that
            # is a rebucket, not a build
            cached = self._gangs[sig] = _build_gang(
                nfas, reads,
                trigger="build" if not self._gangs else "rebucket")
        gang, caps = cached
        # per-tenant pre-gang snapshots: the gang never donates, so the
        # planner's grow-and-replay can rewind ONE tenant without
        # re-stepping (or corrupting) its co-tenants
        pres = [(n.carry, n.base_ts) for n in nfas]
        t_issue = time.perf_counter_ns()
        out = gang([n.carry for n in nfas], planes)
        gang.note_ticks(max(e[1]["__ts"].shape[-1] for e in entries))
        self.flush_total += 1
        for (nfa, block, h), (nc, buf, outs, tele), (pc, pb), cap in \
                zip(entries, out, pres, caps):
            nfa.carry = nc
            token = None
            if self.fuser is not None:
                bufs = [buf] if tele is None else [buf, tele]
                token = self.fuser.register(nfa, bufs)
            else:
                buf.copy_to_host_async()
                if tele is not None:
                    tele.copy_to_host_async()
            P, T, K = outs[0].shape
            h.update(buf=buf, fuse=token, cap=cap, outs=outs,
                     dropped=nc["dropped"],
                     dl_st=nc["slot_state"] if nfa.has_absent else None,
                     dl=nc.get("deadline") if nfa.has_absent else None,
                     ctr=nc.get("absent_ctr") if nfa.has_absent else None,
                     cctr=nc.get("count_ctr"),
                     dl_base=h["base_ts"], tk=(int(T), int(K)), telem=tele,
                     pre_carry=pc, pre_base=pb, t_issue=t_issue)
            h.pop("xpend", None)
        if self.fuser is not None:
            # all co-scheduled tenants registered: one slab, one D2H
            self.fuser.seal_block()


class TenantPacker:
    """Process-level registry of packed automata.  One lock guards all
    buckets (submit/flush/evict are short host-side sections; the gang
    launch itself is async on device).  Lock order: packer → fuser —
    never the reverse, and never a query lock from under it."""

    def __init__(self):
        self._lock = maybe_wrap(threading.RLock(),
                                "plan.xtenant.TenantPacker._lock")
        self.buckets: Dict[Tuple, List[TenantBucket]] = {}
        self._next_id = 0
        self.tenants_total = 0

    # ------------------------------------------------------------ membership

    def register(self, nfa, app: str = "", query: str = "") -> bool:
        """Adopt an eligible automaton into a bucket.  Eligible means
        single-device (no mesh), live, and replayable (the gang step is
        undonated by construction; a donated tenant could never rewind).
        Returns False when packing is off or the NFA does not qualify."""
        if not resolve_xtenant():
            return False
        if nfa.mesh is not None or nfa.statically_dead or not nfa.replayable:
            return False
        if getattr(nfa, "_tenant_bucket", None) is not None:
            return True
        with self._lock:
            nfa._xt_id = self._next_id
            self._next_id += 1
            nfa._xt_label = f"{app}/{query}" if query else (app or
                                                            f"t{nfa._xt_id}")
            if not hasattr(nfa, "_egress_cap"):
                nfa._egress_cap = 1024
            self._place_locked(nfa)
            self.tenants_total += 1
        return True

    def _place_locked(self, nfa) -> None:
        key = _shape_key(nfa)
        cap = resolve_bucket_cap()
        row = self.buckets.setdefault(key, [])
        for b in row:
            if len(b.tenants) < cap:
                bucket = b
                break
        else:
            bucket = TenantBucket(self, key)
            row.append(bucket)
        bucket.tenants.append(nfa)
        nfa._tenant_bucket = bucket

    def evict(self, nfa) -> None:
        """Remove a tenant (app shutdown).  Its pending block — and only
        a whole-bucket flush can apply it — is stepped first, so
        co-tenants keep byte-identical carries and the leaver's final
        matches still retire normally."""
        bucket = getattr(nfa, "_tenant_bucket", None)
        if bucket is None:
            return
        with self._lock:
            if bucket.has_pending(nfa):
                bucket._flush_locked()
            if nfa in bucket.tenants:
                bucket.tenants.remove(nfa)
            nfa._tenant_bucket = None
            self.tenants_total -= 1
            if not bucket.tenants:
                row = self.buckets.get(bucket.key, [])
                if bucket in row:
                    row.remove(bucket)
                if not row:
                    self.buckets.pop(bucket.key, None)

    def rebucket(self, nfa) -> None:
        """Re-key a tenant whose shape changed (slot-ring growth,
        partition growth, snapshot restore): its old gang signatures are
        stale and its shape class may differ.  Callers flush first
        (grow/restore paths do); a stray pending block is flushed here."""
        bucket = getattr(nfa, "_tenant_bucket", None)
        if bucket is None:
            return
        with self._lock:
            if bucket.has_pending(nfa):
                bucket._flush_locked()
            if nfa in bucket.tenants:
                bucket.tenants.remove(nfa)
            if not bucket.tenants:
                row = self.buckets.get(bucket.key, [])
                if bucket in row:
                    row.remove(bucket)
                if not row:
                    self.buckets.pop(bucket.key, None)
            self._place_locked(nfa)

    # ------------------------------------------------------------ reads

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            rows = []
            for row in self.buckets.values():
                for b in row:
                    rows.append({
                        "bucket": b.label,
                        "tenants": [getattr(n, "_xt_label", "?")
                                    for n in b.tenants],
                        "deferred_total": b.deferred_total,
                        "flush_total": b.flush_total,
                        "egress_d2h": (b.fuser.d2h_count
                                       if b.fuser is not None else 0),
                    })
            return {"enabled": resolve_xtenant(),
                    "tenants_total": self.tenants_total, "buckets": rows}

    def prometheus_lines(self) -> List[str]:
        from ..core.statistics import _fmt_labels
        out: List[str] = []
        with self._lock:
            for row in self.buckets.values():
                for b in row:
                    lb = _fmt_labels({"bucket": b.label})
                    out.append(
                        f"siddhi_xtenant_tenants{lb} {len(b.tenants)}")
                    out.append(f"siddhi_xtenant_deferred_blocks_total{lb} "
                               f"{b.deferred_total}")
                    out.append(f"siddhi_xtenant_gang_flushes_total{lb} "
                               f"{b.flush_total}")
                    if b.fuser is not None:
                        out.append(f"siddhi_xtenant_egress_d2h_total{lb} "
                                   f"{b.fuser.d2h_count}")
        return out


_PACKER = TenantPacker()


def tenant_packer() -> TenantPacker:
    return _PACKER


#: HELP/TYPE headers for the packer series (statistics.prometheus_text)
XTENANT_TYPES = [
    ("siddhi_xtenant_tenants", "gauge",
     "Automata currently packed into a cross-tenant dispatch bucket"),
    ("siddhi_xtenant_deferred_blocks_total", "counter",
     "Per-tenant blocks queued for a shared gang dispatch"),
    ("siddhi_xtenant_gang_flushes_total", "counter",
     "Gang launches: ONE device dispatch stepping every pending tenant "
     "in the bucket"),
    ("siddhi_xtenant_egress_d2h_total", "counter",
     "Shared egress-slab device-to-host reads per bucket"),
]

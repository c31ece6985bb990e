"""Shared ingest pipelining for device runtimes.

A device runtime dispatches a block's step and hands the un-read handle
to an in-flight queue instead of reading the result at once, so that the
host work of the next block (packing, encoding) overlaps the device's
step and its device→host read (≙ the ingest/compute overlap of the
reference's @Async disruptor junction, stream/StreamJunction.java:
280-316).  This module holds the one rule of that queue, for the pattern
runtime's handles and for every ``PipelinedDeviceIngest`` subclass
(filter / grouped-agg / windowed-agg) and the device-window processor:

  **a block is retired when its result is ready** — FIFO from the head,
  at every submit (:func:`retire_after_submit`) and from the junction
  worker's idle hook (:func:`settle_inflight`), neither of which waits
  for the device.  The pipeline depth is only a cap: a submit that finds
  more than ``depth`` blocks in flight blocks on the oldest (memory, and
  the window of the overflow replay).  ``flush()`` blocks on everything.

Contract for subclasses:
  - call ``_init_pipeline(app, stream_ids)`` after ``self.qr`` is set,
    and set ``self.app_name`` (the ledger's per-app histograms);
  - dispatch device work in ``ingest`` and hand the un-read handles to
    ``_submit(work)``;
  - implement ``_retire(work)`` — read the handles, decode, emit
    (data errors raised there surface at the caller's @OnError
    boundary: a later ingest's submit, the junction's idle settle or a
    flush);
  - any operation that mutates shared device state out-of-band (lane
    growth, snapshot, restore, timer steps) must ``flush()`` first.

Depth resolution matches the pattern path: deferred delivery is only
transparent when the sender is already decoupled, so pipelining
auto-enables iff every input junction is @Async (the worker's idle hook
settles what a delivery left in flight; drain and barriers flush);
``@app:pipeline('D')`` forces a cap.  Depth 0 is synchronous: every
ingest retires inside itself.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional

from ..core.ledger import ON_DEPTH, ON_FLUSH, ON_READY, ledger as _ledger
from ..query_api.annotation import find_annotation
from .shapes import shape_registry

_LED = _ledger()

#: the cap of the in-flight queue where nothing says otherwise: above it
#: a submit blocks on the oldest block.  Not a delay: a block leaves as
#: soon as its result is ready.
DEFAULT_DEPTH = 4

#: Fused per-app egress (round 7): every device runtime's compacted
#: match/output buffers for one ingest block concatenate into ONE int32
#: slab read back with a single D2H.  ``=0``/``off`` restores the
#: per-runtime reads.
EGRESS_FUSE_ENV = "SIDDHI_TPU_EGRESS_FUSE"


def resolve_egress_fuse(fuse: Optional[bool] = None) -> bool:
    if fuse is None:
        raw = os.environ.get(EGRESS_FUSE_ENV, "").strip().lower()
        return raw not in ("0", "false", "off", "no")
    return bool(fuse)


def resolve_depth(app, junctions: Iterable[Any]) -> int:
    ann = find_annotation(app.annotations, "app:pipeline") or \
        find_annotation(app.annotations, "pipeline")
    if ann is not None:
        pos = ann.positional()
        return int(pos[0] if pos else ann.get("depth", str(DEFAULT_DEPTH)))
    if all(j.is_async for j in junctions):
        return DEFAULT_DEPTH
    return 0


# ---------------------------------------------- the waits of a block in flight
# Every in-flight handle/work (the pattern runtime's and the ones below)
# carries ``t_submit`` (ns, when it was appended), ``seq`` (the junction's
# dequeue sequence number of its block) and, where its step is not issued
# at once, ``t_issue`` (a gang tenant: stamped at its bucket's flush).

def stamp_submit(work: Dict[str, Any]) -> None:
    stamp = _LED.stamp()
    if stamp is not None:
        work["t_submit"], work["seq"] = stamp


def _result_buffer(work: Dict[str, Any]) -> Any:
    """The device buffer a retire of ``work`` is about to read: the fused
    slab once its group is sealed, else an output of the step itself (the
    outputs of one step are there together).  None where nothing is on
    the device (a dead automaton's handle)."""
    token = work.get("fuse")
    if token is not None and token.group._slab is not None:
        return token.group._slab        # set when the group is sealed
    buf = work.get("buf")
    if buf is None:
        outs = work.get("outs")
        buf = outs[0] if outs else work.get("ok")
    return buf


def _is_ready(buf: Any) -> bool:
    is_ready = getattr(buf, "is_ready", None)     # host data: there
    return is_ready is None or is_ready()


def result_ready(work: Dict[str, Any]) -> bool:
    """May a check that must not wait retire ``work`` now?  Not while its
    gang has not been launched (``xpend``), and not while its fuse group
    is the open one: a fetch would seal a slab for this one query in the
    middle of a block (only :func:`settle_inflight`, which knows the
    block is over, closes a group early).  Else: is the result there?"""
    if "xpend" in work:
        return False
    token = work.get("fuse")
    if token is not None and not token.group.sealed:
        return False
    return _is_ready(_result_buffer(work))


def note_retire(app: str, work: Dict[str, Any], cause: int) -> None:
    """The start of ``work``'s retire: bank ``wait.defer`` and
    ``wait.inflight``, count whether the result the retire is about to
    read was already there (``jax.Array.is_ready()``, before anything
    blocks on it) and what caused the retire (``ON_READY``, ``ON_DEPTH``
    or ``ON_FLUSH`` of core/ledger.py)."""
    t_submit = work.get("t_submit")
    if t_submit is None:        # dispatched with the ledger off
        return
    t_retire = time.perf_counter_ns()
    if "xpend" in work:
        # a gang tenant's block that no flush has launched yet (depth 0,
        # or the last block before a flush): the retire launches it
        _LED.note_retire(app, t_submit, t_retire, t_retire, False, cause)
    else:
        _LED.note_retire(app, t_submit, work.get("t_issue", t_submit),
                         t_retire, _is_ready(_result_buffer(work)), cause)


# ------------------------------------------------- the in-flight queue's rule
# ``retire_head(cause)`` pops the queue's head and retires it; the three
# functions below are every way a block leaves a queue but ``flush()``.

def retire_while_ready(inflight: "deque",
                       retire_head: Callable[[int], None]) -> bool:
    """Retire from the head, in FIFO order (per query and key the rows
    stay in their events' order), every block whose result is ready; stop
    at the first that is not.  Never waits.  -> is work still in flight"""
    while inflight and result_ready(inflight[0]):
        retire_head(ON_READY)
    return bool(inflight)


def retire_after_submit(inflight: "deque", depth: int,
                        retire_head: Callable[[int], None]) -> None:
    """After a submit appended its handle: deliver what is ready, then
    hold the queue to its cap by blocking on the oldest.  Depth 0 keeps
    nothing in flight (the synchronous path: rows before ingest
    returns)."""
    if depth > 0:
        retire_while_ready(inflight, retire_head)
    while len(inflight) > depth:
        retire_head(ON_DEPTH)


def settle_inflight(inflight: "deque",
                    retire_head: Callable[[int], None]) -> bool:
    """The junction worker's idle hook (its delivery is over, so every
    query of the stream has submitted): launch the newest block if its
    gang is still pending, close its fuse group if that is still open,
    then retire what is ready.  Never waits for the device.
    -> is work still in flight (the worker settles again shortly)"""
    if not inflight:
        return False
    newest = inflight[-1]
    bucket = newest.get("xpend")
    if bucket is not None:
        bucket.flush()
    token = newest.get("fuse")
    if token is not None and not token.group.sealed:
        token.group.fuser.seal_block()
    return retire_while_ready(inflight, retire_head)


class PipelinedDeviceIngest:
    """In-flight chunk queue: dispatch now, read/decode when the result
    is ready (FIFO, so emission order is preserved)."""

    def _init_pipeline(self, app, stream_ids: Iterable[str]) -> None:
        self._inflight: "deque" = deque()
        self.pipeline_depth = resolve_depth(
            app.app, [app.junction_of(sid) for sid in stream_ids])
        # dispatch-storm watchdog (core/overload.py): every device
        # submission counts as ingest progress — a storm is timer fires
        # with none
        self._watchdog = getattr(app.app_ctx, "watchdog", None)

    def _submit(self, work: Dict[str, Any]) -> None:
        if self._watchdog is not None:
            self._watchdog.note_progress()
        stamp_submit(work)
        self._inflight.append(work)
        retire_after_submit(self._inflight, self.pipeline_depth,
                            self._retire_oldest)

    def _retire_oldest(self, cause: int) -> None:
        work = self._inflight.popleft()
        with _LED.span("decode", None, work.get("seq"), self.app_name):
            note_retire(self.app_name, work, cause)
            self._retire(work)

    def settle(self) -> bool:
        """Non-blocking counterpart of :meth:`flush` for the junction
        worker's idle hook (:func:`settle_inflight`)."""
        if not self._inflight:
            return False
        with self.qr.lock:
            return settle_inflight(self._inflight, self._retire_oldest)

    def flush(self) -> None:
        """Retire every in-flight chunk, blocking on each: a junction's
        barrier and drain, and before any state read.  Takes the query
        lock (re-entrant) — state reads can race the junction worker."""
        with self.qr.lock:
            while self._inflight:
                self._retire_oldest(ON_FLUSH)

    def _retire(self, work: Dict[str, Any]) -> None:
        raise NotImplementedError


class _FuseToken:
    """One runtime's registration in a fuse group: fetch() returns the
    registered buffers as host ndarrays, decoded from the group's slab."""

    __slots__ = ("group", "index")

    def __init__(self, group: "_FuseGroup", index: int):
        self.group = group
        self.index = index

    def fetch(self) -> List[Any]:
        return self.group.fetch(self.index)


class _FuseGroup:
    """The buffers every device runtime registered during ONE ingest
    block.  seal() packs them into a single int32 slab on device (floats
    bitcast, bools widened) and starts its async D2H; the first fetch()
    blocks on that one transfer and serves per-registration host views."""

    __slots__ = ("fuser", "entries", "owners", "sealed", "_slab", "_host")

    def __init__(self, fuser: "EgressFuser"):
        self.fuser = fuser
        self.entries: List[List[Any]] = []   # per-registration buffer list
        self.owners: set = set()
        self.sealed = False
        self._slab = None
        self._host = None

    def seal(self) -> None:
        if self.sealed:
            return
        self.sealed = True
        with _LED.span(None, "egress_d2h.seal"):
            self._seal()

    def _seal(self) -> None:
        import jax
        import jax.numpy as jnp
        pieces = []
        for bufs in self.entries:
            for b in bufs:
                dt = str(b.dtype)
                if dt == "float32":
                    pieces.append(jax.lax.bitcast_convert_type(
                        b, jnp.int32).reshape(-1))
                elif dt == "int32":
                    pieces.append(b.reshape(-1))
                elif dt == "uint32":
                    pieces.append(jax.lax.bitcast_convert_type(
                        b, jnp.int32).reshape(-1))
                elif dt == "bool":
                    pieces.append(b.reshape(-1).astype(jnp.int32))
                else:
                    # no 4-byte view (x64 lanes etc.): read it separately
                    pieces.append(None)
        fusible = [p for p in pieces if p is not None]
        if fusible:
            self._slab = (jnp.concatenate(fusible) if len(fusible) > 1
                          else fusible[0])
            self._slab.copy_to_host_async()

    def fetch(self, index: int) -> List[Any]:
        import numpy as np
        with self.fuser._lock:
            if self is self.fuser._current:
                # a blocking retire caught up with the open block (depth
                # 0, the cap, a flush): close it so the slab covers what
                # was registered
                self.fuser._rotate()
            self.seal()
            if self._host is None and self._slab is not None:
                with _LED.span("egress_d2h"):
                    self._host = np.asarray(self._slab)   # the ONE D2H
                self.fuser.d2h_count += 1
                self.fuser.last_slab_bytes = self._host.nbytes
                # the slab is the eager concat's, a launch of no kind
                # of its own: its read is booked where its compiles are
                shape_registry().entry("other", {}).d2h_bytes += \
                    self._host.nbytes
            out: List[Any] = []
            off = 0
            host = self._host
            for ri, bufs in enumerate(self.entries):
                for b in bufs:
                    dt = str(b.dtype)
                    n = int(np.prod(b.shape)) if b.shape else 1
                    if dt in ("float32", "int32", "uint32"):
                        view = host[off:off + n].view(dt).reshape(b.shape)
                        off += n
                    elif dt == "bool":
                        view = host[off:off + n].astype(
                            bool).reshape(b.shape)
                        off += n
                    else:
                        view = np.asarray(b)          # unfused extra read
                    if ri == index:
                        out.append(view)
            return out


class EgressFuser:
    """Per-app egress consolidation: device runtimes register the un-read
    output buffers of each dispatched block; registrations between block
    boundaries form a group, and each group is read back as one slab.

    A runtime registers exactly once per ingest block, so a repeat
    registration by the same owner IS the next block — the open group
    seals (slab concat + async D2H start, overlapping later dispatches)
    and a fresh one opens.  Who knows the boundary sooner closes the
    group sooner (``seal_block``): a gang flush, and the junction
    worker's idle hook once its delivery is over.  With
    pipelining depth 0 a runtime retires inside its own ingest and
    groups degenerate to singletons — exactly the per-runtime reads the
    legacy path pays, never worse."""

    def __init__(self, name: str = "app"):
        self.name = name
        self._lock = threading.RLock()
        self._current = _FuseGroup(self)
        self.d2h_count = 0
        self.blocks = 0
        #: size of the most recent fused slab read — surfaced in the
        #: flight ring (planner._record_block) so a bundle shows the
        #: egress volume of the blocks leading up to an incident
        self.last_slab_bytes = 0

    def _rotate(self) -> None:
        grp = self._current
        self._current = _FuseGroup(self)
        self.blocks += 1
        grp.seal()

    def register(self, owner: Any, buffers: List[Any]) -> _FuseToken:
        with self._lock:
            if id(owner) in self._current.owners:
                self._rotate()
            grp = self._current
            grp.owners.add(id(owner))
            grp.entries.append(list(buffers))
            return _FuseToken(grp, len(grp.entries) - 1)

    def seal_block(self) -> None:
        """Close the open group explicitly.  The cross-tenant packer
        (plan/xtenant.py) registers every co-scheduled tenant's buffers
        during one gang flush and knows the block boundary exactly, as
        does the junction worker's idle hook (``settle_inflight``) —
        sealing here starts the shared slab's D2H immediately instead of
        waiting for the next repeat registration."""
        with self._lock:
            if self._current.entries:
                self._rotate()


def egress_fuser_for(app) -> Optional[EgressFuser]:
    """The app runtime's shared fuser (lazily created), or None when
    EGRESS_FUSE_ENV disables fusion."""
    if app is None or not resolve_egress_fuse():
        return None
    fuser = getattr(app, "_egress_fuser", None)
    if fuser is None:
        fuser = EgressFuser(getattr(app, "name", None) or "app")
        app._egress_fuser = fuser
    return fuser

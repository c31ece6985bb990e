"""Canonical shape-class registry + compile-time observatory.

The reference engine builds its object graph once and serves forever;
our jitted reproduction pays an XLA trace + compile for every new
*shape class* — a distinct (kind, static-dims) combination of a jitted
entry point.  Before this module each plan/ compiler derived its jit
signature ad hoc (xtenant had ``_shape_key``, the NFA had its spec, dwin
keyed ``(capacity, T)`` privately), so compile cost was unattributable
and the warmup set was unenumerable.  This module is the single choke
point:

  * :class:`ShapeRegistry` — every jitted entry point (nfa step, bank /
    super-bank, egress pack, dwin, gagg, wagg, filter program, xtenant
    gang, join probe, mesh step) resolves its signature here via
    :meth:`ShapeRegistry.jit` / :meth:`ShapeRegistry.adopt`.  A shape
    class is ``kind`` plus a sorted static-dims mapping rendered into a
    stable, hashable, process-independent signature string
    (``nfa.step[B=1,C=1,K=8,...]``) — the generalization of xtenant's
    ``n_states/K/planes/B`` bucket key.  tests/test_shapes.py enforces
    that ``jax.jit`` appears nowhere else (short allowlist).
  * **Persistent compile cache** — always on, at one placeable path:
    where ``JAX_COMPILATION_CACHE_DIR`` says when it is set (JAX reads
    it; this module sets no other), else ``<checkout>/.jax_cache``.  A
    process restart re-loads XLA executables instead of recompiling
    (proven across subprocesses by tests/test_shapes.py).  JAX's own
    ``JAX_ENABLE_COMPILATION_CACHE=0`` turns it off.
  * **AOT shape-ladder prewarm** — ``SIDDHI_TPU_PREWARM=1`` precompiles
    the grow ladder (K doublings of live NFA shapes) in a background
    ``siddhi-prewarm`` thread via ``jit(...).lower(abstract).compile()``
    so grow-and-replay pays a cache hit, not a cold compile.
  * **Launch books** — :class:`RegisteredJit` is the one wrapper a
    launch passes: it counts the call, the numpy bytes it hands the
    device and its compiles on the shape class's :class:`ShapeEntry`;
    the retire and carry-placement sites add D2H bytes, live bytes and
    scan ticks there (``siddhi_kernel_*`` on /metrics, per kind).
  * **Compile telemetry** — per-shape-class ledger (compile count,
    attributed XLA seconds, call-blocking wall seconds, persistent-cache
    hits/misses, trigger = build|grow|rebucket|prewarm|restart), folded
    into ``siddhi_compile_*`` / ``siddhi_prewarm_*`` series on /metrics,
    a registry table on ``rt.statistics`` / ``GET /stats``, compile rows
    on the flight ring, and a ``CC001`` incident bundle when an
    ingest-blocking compile (grow/rebucket/restart) stalls longer than
    ``SIDDHI_TPU_COMPILE_STALL_MS``.

Attribution uses ``jax.monitoring`` listeners: compile durations
(``/jax/core/compile/*``) and persistent-cache hit/miss events
(``/jax/compilation_cache/*`` — these only fire when a cache dir is
configured) are credited to the shape class currently executing on the
calling thread (a thread-local frame stack pushed by
:class:`RegisteredJit`); compiles outside any registered entry point
land on a catch-all ``other[]`` entry so totals stay honest.

No top-level ``jax`` import: the analyze CLI imports the pure signature
helpers (plan-IR dumps carry the shape-class key) without touching jax.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.ledger import ledger as _ledger

_span = _ledger().span

#: Where the persistent compile cache lives unless JAX was told
#: otherwise (``JAX_COMPILATION_CACHE_DIR``): one fixed path inside the
#: checkout, so every process of a command — and the next command —
#: finds what the last one compiled.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
#: Opt-in AOT shape-ladder prewarm (background grow-ladder compiles).
PREWARM_ENV = "SIDDHI_TPU_PREWARM"
#: An ingest-blocking compile (trigger grow/rebucket/restart) slower
#: than this emits a CC001 incident bundle through the flight bus.
COMPILE_STALL_MS_ENV = "SIDDHI_TPU_COMPILE_STALL_MS"
#: Grace the prewarm worker sleeps before its first compile: tracing is
#: GIL-bound, so a ladder kicked off by the very first step call would
#: otherwise contend with the rest of the foreground build.
PREWARM_GRACE_MS_ENV = "SIDDHI_TPU_PREWARM_GRACE_MS"

DEFAULT_STALL_MS = 2000.0
DEFAULT_PREWARM_GRACE_MS = 500.0
#: Compile-event ledger rows retained (newest first on snapshot).
EVENT_RING = 256
#: Grow-ladder rungs enqueued ahead of the live K (K*2, K*4).
LADDER_RUNGS = (2, 4)

#: The five ways a shape class comes to compile.
TRIGGERS = ("build", "grow", "rebucket", "prewarm", "restart")
#: Triggers that block a live ingest path (candidates for CC001).
_BLOCKING_TRIGGERS = ("grow", "rebucket", "restart")

_FALSY = ("", "0", "false", "off", "no")


# ------------------------------------------------------------ signatures
# Pure helpers — no jax: analysis/plan_ir.py computes the same signature
# for its dumps, and the goldens pin it, so the key format is a contract.

def _fmt_dim(v: Any) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, (tuple, list)):
        return "x".join(_fmt_dim(x) for x in v)
    return str(v)


def shape_signature(kind: str, dims: Dict[str, Any]) -> str:
    """Stable, hashable shape-class key: ``kind[d1=v1,d2=v2,...]`` with
    dims sorted by name.  Process-independent by construction — only
    static shape facts belong in ``dims`` (no ids, no addresses)."""
    body = ",".join(f"{k}={_fmt_dim(v)}" for k, v in sorted(dims.items()))
    return f"{kind}[{body}]"


def nfa_shape_dims(spec, n_partitions: int, batch_b: int,
                   donate: bool = False, **extra) -> Dict[str, Any]:
    """The canonical NFA step dims — S/K/P/B plus capture geometry and
    telemetry, the same facts xtenant's bucket key groups on.  Shared by
    the compiler call sites and the plan-IR extractor so the dumped key
    always matches what the registry records."""
    d = {"S": len(spec.units), "K": spec.n_slots, "P": n_partitions,
         "B": max(batch_b, 1), "R": max(spec.n_rows, 1),
         "C": max(spec.n_caps, 1), "telem": bool(spec.telemetry),
         "donate": bool(donate)}
    d.update(extra)
    return d


# ------------------------------------------------------------ env knobs

def prewarm_enabled() -> bool:
    return os.environ.get(PREWARM_ENV, "").strip().lower() not in _FALSY


def _stall_threshold_ms() -> float:
    try:
        return float(os.environ.get(COMPILE_STALL_MS_ENV, ""))
    except (TypeError, ValueError):
        return DEFAULT_STALL_MS


def _prewarm_grace_s() -> float:
    try:
        return float(os.environ.get(PREWARM_GRACE_MS_ENV, "")) / 1e3
    except (TypeError, ValueError):
        return DEFAULT_PREWARM_GRACE_MS / 1e3


_CACHE_STATE: Dict[str, Any] = {"configured": False, "enabled": False,
                                "dir": ""}
_CACHE_LOCK = threading.Lock()


def configure_compile_cache() -> Dict[str, Any]:
    """Place JAX's persistent compilation cache (idempotent; called
    before the first registry jit — JAX latches the cache decision at a
    process's first compile).  A directory JAX already has — from
    ``JAX_COMPILATION_CACHE_DIR`` or the embedding program — is left
    alone; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    with _CACHE_LOCK:
        if _CACHE_STATE["configured"]:
            return dict(_CACHE_STATE)
        import jax
        if not jax.config.jax_compilation_cache_dir:
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_CACHE_DIR)
        # cache every executable: the default thresholds skip small /
        # fast compiles, but coldstart is the SUM of many of those
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
        _CACHE_STATE.update(
            configured=True,
            enabled=bool(jax.config.jax_enable_compilation_cache),
            dir=jax.config.jax_compilation_cache_dir)
        return dict(_CACHE_STATE)


#: jax.monitoring duration event -> the phase whose ShapeEntry field
#: ``<phase>_seconds`` it is credited to beside ``compile_seconds``
#: (jax/_src/dispatch.py names the three)
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


# ------------------------------------------------------------ entries

class ShapeEntry:
    """Per-shape-class compile ledger line.  Counter fields are plain
    int/float adds under the GIL or the registry lock — monotone, which
    is all the exposition needs."""

    __slots__ = ("signature", "kind", "dims", "compiles", "compile_seconds",
                 "trace_seconds", "lower_seconds", "backend_seconds",
                 "blocked_seconds", "cache_hits", "cache_misses", "calls",
                 "h2d_bytes", "d2h_bytes", "live_bytes", "scan_ticks",
                 "triggers", "last_trigger", "last_compile_unix", "prewarmed")

    def __init__(self, signature: str, kind: str, dims: Dict[str, Any]):
        self.signature = signature
        self.kind = kind
        self.dims = dict(dims)
        self.compiles = 0              # XLA compiles (incl. retraces)
        self.compile_seconds = 0.0     # attributed trace+compile seconds,
        # the sum of its three phases (jax.monitoring, _COMPILE_PHASES):
        self.trace_seconds = 0.0       # Python -> jaxpr; paid on a
        self.lower_seconds = 0.0       # cache hit too, as is jaxpr -> MLIR
        self.backend_seconds = 0.0     # XLA compile, or the cache's load
        self.blocked_seconds = 0.0     # caller wall blocked on a compile
        self.cache_hits = 0            # persistent-cache hits
        self.cache_misses = 0
        self.calls = 0                 # launches
        self.h2d_bytes = 0             # numpy leaves of the calls' arguments
        self.d2h_bytes = 0             # read back by the retire sites
        # persistent device state (a gauge): set by the carry-placement
        # sites; the measured side of analysis/cost_model.py's prediction
        self.live_bytes = 0
        self.scan_ticks = 0            # sequential NFA ticks, ceil(T/B) a call
        self.triggers: Dict[str, int] = {}
        self.last_trigger = ""
        self.last_compile_unix = 0.0
        # (owner_token, AOT executable) left by the prewarm worker for
        # the owner's later rebuild to take over — see ShapeRegistry.jit
        self.prewarmed: Optional[tuple] = None

    def as_dict(self) -> Dict[str, Any]:
        return {"signature": self.signature, "kind": self.kind,
                "dims": dict(self.dims), "compiles": self.compiles,
                "compile_seconds": round(self.compile_seconds, 6),
                "trace_seconds": round(self.trace_seconds, 6),
                "lower_seconds": round(self.lower_seconds, 6),
                "backend_seconds": round(self.backend_seconds, 6),
                "blocked_seconds": round(self.blocked_seconds, 6),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "calls": self.calls, "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes, "live_bytes": self.live_bytes,
                "scan_ticks": self.scan_ticks,
                "triggers": dict(self.triggers),
                "last_trigger": self.last_trigger,
                "last_compile_unix": round(self.last_compile_unix, 3),
                "prewarmed": self.prewarmed is not None}


def _host_bytes(args) -> int:
    """nbytes of the numpy leaves of a call's arguments: the H2D transfer
    the call implies (device-resident jax arrays transfer nothing)."""
    total = 0
    stack = list(args)
    while stack:
        a = stack.pop()
        if isinstance(a, np.ndarray):
            total += a.nbytes
        elif isinstance(a, dict):
            stack.extend(a.values())
        elif isinstance(a, (list, tuple)):
            stack.extend(a)
    return total


def _unbook_live(book: list) -> None:
    book[0].live_bytes -= book[1]


class _AotHandoff:
    """Prewarm-to-rebuild executable handoff: call the AOT-compiled
    ladder rung when the runtime arguments match its lowered avals; any
    mismatch (a differently-sized ingest block, dtype drift) falls back
    to the plain jit, which retraces per shape like any registry jit.
    The handoff erases the re-trace a persistent-cache hit still pays."""

    __slots__ = ("_aot", "_jitted")

    def __init__(self, aot, jitted):
        self._aot = aot
        self._jitted = jitted

    def _cache_size(self) -> int:
        fn = getattr(self._jitted, "_cache_size", None)
        try:
            return int(fn()) if fn is not None else 0
        except Exception:   # noqa: BLE001 — introspection is best-effort
            return 0

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        try:
            return self._aot(*args, **kwargs)
        except (TypeError, ValueError):
            return self._jitted(*args, **kwargs)


class RegisteredJit:
    """The registry's wrapper around one jitted callable, and the only
    one a launch passes.  Per call it pushes a thread-local attribution
    frame (so jax.monitoring compile durations and cache hit/miss events
    credit this shape class), counts the launch and the host bytes it
    carries, and detects compiles via the jit's in-memory cache-size
    delta."""

    __slots__ = ("_jitted", "entry", "registry", "trigger",
                 "_first_call_hook", "_last_cs", "_span")

    def __init__(self, jitted, entry: ShapeEntry, registry: "ShapeRegistry",
                 trigger: str, first_call_hook: Optional[Callable] = None):
        self._jitted = jitted
        self.entry = entry
        # XLA modules keep their jit_<function> names; the host line of
        # a trace names each launch by its registry kind
        self._span = f"device.issue/{entry.kind}"
        self.registry = registry
        self.trigger = trigger
        self._first_call_hook = first_call_hook
        self._last_cs = 0

    def _cache_size(self) -> int:
        """The jit's in-memory cache size.  A rebuilt step is a new jit
        with a cache of its own, so the compile delta is per wrapper."""
        fn = getattr(self._jitted, "_cache_size", None)
        try:
            return int(fn()) if fn is not None else 0
        except Exception:   # noqa: BLE001 — introspection is best-effort
            return 0

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def note_ticks(self, T: int) -> None:
        """The launch just made scanned a block of depth ``T``:
        ``ceil(T/B)`` sequential ticks, B the entry's events per tick."""
        n = -(-T // self.entry.dims["B"])
        reg = self.registry
        with reg._lock:
            self.entry.scan_ticks += n
            reg.scan_ticks += n

    def book_live(self, owner: Any, nbytes: int) -> None:
        """``owner`` (an engine) now holds ``nbytes`` of device state
        under this launch's shape class.  The gauge is the sum over the
        engines that hold state there: what ``owner`` booked before,
        under whichever class, is taken back first, and goes when
        ``owner`` does."""
        book = owner.__dict__.get("_live_book")
        with self.registry._lock:
            if book is None:
                book = owner._live_book = [self.entry, 0]
                weakref.finalize(owner, _unbook_live, book)
            book[0].live_bytes -= book[1]
            book[0], book[1] = self.entry, int(nbytes)
            self.entry.live_bytes += book[1]

    def __call__(self, *args, **kwargs):
        reg = self.registry
        stack = getattr(reg._tls, "frames", None)
        if stack is None:
            stack = reg._tls.frames = []
        stack.append(self.entry)
        t0 = time.perf_counter_ns()
        try:
            with _span(None, self._span):
                out = self._jitted(*args, **kwargs)
        finally:
            t1 = time.perf_counter_ns()
            stack.pop()
        e = self.entry
        nb = _host_bytes(args)
        with reg._lock:     # several junction and shard workers launch
            e.calls += 1
            e.h2d_bytes += nb
            reg.calls += 1
        cs = self._cache_size()
        if cs > self._last_cs:
            n = cs - self._last_cs
            self._last_cs = cs
            reg._note_compile(e, self.trigger, n, (t1 - t0) / 1e9)
        if self._first_call_hook is not None:
            hook, self._first_call_hook = self._first_call_hook, None
            try:
                hook(args, kwargs)
            except Exception:   # noqa: BLE001 — ladder hints must not fail
                pass            # the call that produced the result
        return out


# ------------------------------------------------------------ registry

class ShapeRegistry:
    """Process-global shape-class registry + compile observatory."""

    def __init__(self):
        self._lock = threading.RLock()
        self._entries: Dict[str, ShapeEntry] = {}
        self._events: "deque" = deque(maxlen=EVENT_RING)
        self._tls = threading.local()
        # every entry's calls / scan_ticks, summed as they are made: a
        # runtime diffs :meth:`marks` around an ingest block
        self.calls = 0
        self.scan_ticks = 0
        # prewarm worker state: a transient thread that exits when the
        # queue drains (the tier-1 thread-leak sentinel treats lingering
        # siddhi- threads as failures)
        self._pw_queue: "deque" = deque()
        self._pw_queued: set = set()
        self._pw_thread: Optional[threading.Thread] = None
        self._pw_idle = threading.Event()
        self._pw_idle.set()
        self._pw_atexit = False
        self.prewarm_compiled = 0
        self.prewarm_skipped = 0
        self.prewarm_errors = 0
        self.prewarm_handoffs = 0
        self.prewarm_seconds = 0.0

    # ------------------------------------------------------------ entries

    def entry(self, kind: str, dims: Dict[str, Any]) -> ShapeEntry:
        sig = shape_signature(kind, dims)
        with self._lock:
            e = self._entries.get(sig)
            if e is None:
                e = self._entries[sig] = ShapeEntry(sig, kind, dims)
            return e

    def _catch_all(self) -> ShapeEntry:
        return self.entry("other", {})

    def _frame_entry(self) -> ShapeEntry:
        stack = getattr(self._tls, "frames", None)
        return stack[-1] if stack else self._catch_all()

    # ------------------------------------------------------------ jit

    def jit(self, kind: str, dims: Dict[str, Any], fn: Callable, *,
            trigger: str = "build",
            first_call_hook: Optional[Callable] = None,
            prewarm_owner: Optional[Any] = None,
            **jit_kwargs) -> RegisteredJit:
        """The one place engine code constructs ``jax.jit``: resolves the
        shape-class entry, arms the compile cache + monitoring listeners,
        and returns the attributing wrapper.

        ``prewarm_owner``: opt-in AOT handoff.  When the prewarm worker
        already traced AND compiled this shape class for the same owner
        token, the rebuild takes over the finished executable instead of
        re-jitting — a cache hit still pays a full re-trace, the handoff
        pays nothing.  Owner-gated because a shape-class signature only
        pins array shapes: the predicate constants baked into the HLO
        differ between apps that share a signature, so the executable is
        only valid for the instance that queued the ladder."""
        configure_compile_cache()
        _install_listeners()
        import jax
        jitted = jax.jit(fn, **jit_kwargs)
        e = self.entry(kind, dims)
        pw = e.prewarmed
        if prewarm_owner is not None and pw is not None \
                and pw[0] == prewarm_owner:
            jitted = _AotHandoff(pw[1], jitted)
            with self._lock:
                self.prewarm_handoffs += 1
                e.triggers["prewarm-handoff"] = \
                    e.triggers.get("prewarm-handoff", 0) + 1
        return self.adopt(kind, dims, jitted, trigger=trigger,
                          first_call_hook=first_call_hook)

    def adopt(self, kind: str, dims: Dict[str, Any], jitted, *,
              trigger: str = "build",
              first_call_hook: Optional[Callable] = None) -> RegisteredJit:
        """Route an externally built jitted callable (parallel/mesh.py's
        sharded step) through the registry without re-jitting."""
        configure_compile_cache()
        _install_listeners()
        e = self.entry(kind, dims)
        with self._lock:
            e.triggers[trigger] = e.triggers.get(trigger, 0) + 1
            e.last_trigger = trigger
        return RegisteredJit(jitted, e, self, trigger, first_call_hook)

    # ------------------------------------------------------------ compile
    # bookkeeping

    def _note_compile(self, e: ShapeEntry, trigger: str, n: int,
                      blocked_s: float) -> None:
        now = time.time()
        with self._lock:
            e.compiles += n
            e.blocked_seconds += blocked_s
            e.last_trigger = trigger
            e.last_compile_unix = now
            self._events.append({"t": now, "signature": e.signature,
                                 "kind": e.kind, "trigger": trigger,
                                 "compiles": n,
                                 "blocked_s": round(blocked_s, 4)})
        # the call itself is a span already, ``device.issue/<kind>``
        _ledger().instant(f"jit-compile:{e.kind}", cat="jit")
        try:
            from ..core.flight import flight
            fl = flight()
            fl.record_compile(e.kind, e.signature, trigger, blocked_s)
            blocked_ms = blocked_s * 1e3
            if trigger in _BLOCKING_TRIGGERS and \
                    blocked_ms > _stall_threshold_ms():
                fl.emit("compile_stall", detail={
                    "code": "CC001", "signature": e.signature,
                    "kind": e.kind, "trigger": trigger,
                    "blocked_ms": round(blocked_ms, 2),
                    "threshold_ms": _stall_threshold_ms(),
                    "cache": dict(_CACHE_STATE),
                    "hint": "an ingest-blocking XLA compile outran "
                            f"{COMPILE_STALL_MS_ENV}; enable "
                            f"{PREWARM_ENV} so grown shapes are compiled "
                            "ahead of need"})
        except Exception:   # noqa: BLE001 — telemetry must not fail a step
            pass

    def _credit_event(self, event: str) -> None:
        e = self._frame_entry()
        if event.endswith("/cache_hits"):
            e.cache_hits += 1
        elif event.endswith("/cache_misses"):
            e.cache_misses += 1

    def _credit_duration(self, event: str, secs: float) -> None:
        if event.startswith("/jax/core/compile/"):
            e = self._frame_entry()
            e.compile_seconds += float(secs)
            phase = _COMPILE_PHASES.get(event)
            if phase is not None:
                field = f"{phase}_seconds"
                setattr(e, field, getattr(e, field) + float(secs))

    # ------------------------------------------------------------ prewarm

    def prewarm_submit(self, kind: str, dims: Dict[str, Any],
                       build: Callable[[], Tuple[Callable, tuple, dict]],
                       owner: Optional[Any] = None) -> bool:
        """Queue one grow-ladder rung: ``build()`` (run on the worker)
        returns ``(fn, abstract_args, jit_kwargs)`` and the worker AOT
        compiles ``jax.jit(fn, **kw).lower(*abstract).compile()`` under a
        ``prewarm`` attribution frame, landing the executable in the
        persistent cache the later real build will hit.  With ``owner``
        set, the finished executable is also kept on the shape entry for
        the owner's rebuild to take over outright (see ``jit``).
        Dedupes on the shape-class signature; no-op unless
        ``SIDDHI_TPU_PREWARM=1``."""
        if not prewarm_enabled():
            return False
        sig = shape_signature(kind, dims)
        with self._lock:
            done = self._entries.get(sig)
            if (done is not None and done.compiles > 0) or \
                    sig in self._pw_queued:
                self.prewarm_skipped += 1
                return False
            self._pw_queued.add(sig)
            self._pw_queue.append((kind, dims, build, owner))
            self._pw_idle.clear()
            t = self._pw_thread
            if t is None or not t.is_alive():
                from ..core.threads import engine_thread_name
                t = threading.Thread(
                    target=self._prewarm_loop, daemon=True,
                    name=engine_thread_name("siddhi-prewarm"))
                self._pw_thread = t
                if not self._pw_atexit:
                    # tearing the interpreter down mid-XLA-compile
                    # aborts the process (std::terminate) — drain the
                    # ladder before exit, bounded so a wedged compile
                    # cannot hold shutdown hostage forever
                    import atexit
                    atexit.register(self.prewarm_join, 120.0)
                    self._pw_atexit = True
                t.start()
        return True

    def _prewarm_loop(self) -> None:
        # let the foreground build finish its own (GIL-bound) traces
        # before the ladder starts burning the interpreter lock
        time.sleep(_prewarm_grace_s())
        while True:
            with self._lock:
                if not self._pw_queue:
                    self._pw_idle.set()
                    self._pw_thread = None
                    return
                kind, dims, build, owner = self._pw_queue.popleft()
            self._prewarm_one(kind, dims, build, owner)

    def _prewarm_one(self, kind: str, dims: Dict[str, Any],
                     build: Callable, owner: Optional[Any] = None) -> None:
        sig = shape_signature(kind, dims)
        e = self.entry(kind, dims)
        if e.compiles > 0:          # the grow beat us to it
            self.prewarm_skipped += 1
            return
        stack = getattr(self._tls, "frames", None)
        if stack is None:
            stack = self._tls.frames = []
        t0 = time.perf_counter()
        stack.append(e)
        try:
            import jax
            fn, abstract_args, jit_kwargs = build()
            compiled = \
                jax.jit(fn, **jit_kwargs).lower(*abstract_args).compile()
            if owner is not None:
                e.prewarmed = (owner, compiled)
        except Exception:   # noqa: BLE001 — a failed rung must not kill
            self.prewarm_errors += 1        # the worker loop
            return
        finally:
            stack.pop()
            self.prewarm_seconds += time.perf_counter() - t0
        self.prewarm_compiled += 1
        with self._lock:
            e.triggers["prewarm"] = e.triggers.get("prewarm", 0) + 1
        self._note_compile(e, "prewarm", 1, 0.0)

    def prewarm_join(self, timeout: float = 60.0) -> bool:
        """Block until the ladder queue drains and the worker exits
        (tests synchronize here)."""
        ok = self._pw_idle.wait(timeout)
        t = self._pw_thread
        if t is not None:
            t.join(timeout=5.0)
        return ok

    def prewarm_pending(self) -> int:
        with self._lock:
            return len(self._pw_queue)

    # ------------------------------------------------------------ reads

    def marks(self) -> Tuple[int, int]:
        """(launches, scan ticks) made so far, process-wide."""
        return self.calls, self.scan_ticks

    def kernels(self) -> Dict[str, Dict[str, int]]:
        """The launch books per kind, summed over the kind's shape
        classes (``rt.statistics["kernels"]``, ``GET /stats``, the flight
        bundle, ``siddhi_kernel_*``)."""
        with self._lock:
            es = list(self._entries.values())
        out: Dict[str, Dict[str, int]] = {}
        for e in es:
            row = out.setdefault(e.kind, dict.fromkeys(_KERNEL_BOOKS, 0))
            for f in _KERNEL_BOOKS:
                row[f] += getattr(e, f)
        return out

    def totals(self) -> Dict[str, Any]:
        with self._lock:
            es = list(self._entries.values())
        return {"shape_classes": len(es),
                "compiles": sum(e.compiles for e in es),
                "compile_seconds": sum(e.compile_seconds for e in es),
                "trace_seconds": sum(e.trace_seconds for e in es),
                "lower_seconds": sum(e.lower_seconds for e in es),
                "backend_seconds": sum(e.backend_seconds for e in es),
                "blocked_seconds": sum(e.blocked_seconds for e in es),
                "cache_hits": sum(e.cache_hits for e in es),
                "cache_misses": sum(e.cache_misses for e in es)}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            entries = [e.as_dict() for e in self._entries.values()]
            events = list(self._events)
        entries.sort(key=lambda d: d["signature"])
        return {"cache": dict(_CACHE_STATE),
                "prewarm": {"enabled": prewarm_enabled(),
                            "compiled": self.prewarm_compiled,
                            "skipped": self.prewarm_skipped,
                            "errors": self.prewarm_errors,
                            "handoffs": self.prewarm_handoffs,
                            "pending": self.prewarm_pending(),
                            "seconds": round(self.prewarm_seconds, 4)},
                "totals": {k: (round(v, 6) if isinstance(v, float) else v)
                           for k, v in self.totals().items()},
                "entries": entries, "recent_compiles": events}

    def prometheus_lines(self) -> List[str]:
        lines: List[str] = []
        with self._lock:
            es = sorted(self._entries.values(), key=lambda e: e.signature)
            pw_pending = len(self._pw_queue)
        for e in es:
            lb = (f'{{kind="{e.kind}",signature="{e.signature}"}}')
            lines.append(
                f"siddhi_compile_seconds_total{lb} "
                f"{e.compile_seconds:.9g}")
            for phase in _COMPILE_PHASES.values():
                lines.append(
                    f"siddhi_compile_phase_seconds_total"
                    f'{{kind="{e.kind}",signature="{e.signature}",'
                    f'phase="{phase}"}} '
                    f'{getattr(e, f"{phase}_seconds"):.9g}')
            lines.append("siddhi_compile_blocked_seconds_total"
                         f"{lb} {e.blocked_seconds:.9g}")
            lines.append(f"siddhi_compile_total{lb} {e.compiles}")
            lines.append(
                f"siddhi_compile_cache_hits_total{lb} {e.cache_hits}")
            lines.append(
                f"siddhi_compile_cache_misses_total{lb} {e.cache_misses}")
        for kind, row in sorted(self.kernels().items()):
            lb = f'{{kernel="{kind}"}}'
            for f, series in _KERNEL_BOOKS.items():
                lines.append(f"{series}{lb} {row[f]}")
        lines.append(f"siddhi_shape_classes {len(es)}")
        lines.append(f"siddhi_prewarm_compiled_total {self.prewarm_compiled}")
        lines.append(f"siddhi_prewarm_skipped_total {self.prewarm_skipped}")
        lines.append(f"siddhi_prewarm_errors_total {self.prewarm_errors}")
        lines.append(
            f"siddhi_prewarm_handoffs_total {self.prewarm_handoffs}")
        lines.append(f"siddhi_prewarm_pending {pw_pending}")
        lines.append(
            f"siddhi_prewarm_seconds_total {self.prewarm_seconds:.9g}")
        return lines

    def reset(self) -> None:
        """Test hook: drop entries/events and prewarm tallies (the
        monitoring listeners stay installed — they dispatch through the
        module-level singleton accessor)."""
        self.prewarm_join(timeout=10.0)
        with self._lock:
            self._entries.clear()
            self._events.clear()
            self._pw_queue.clear()
            self._pw_queued.clear()
            self.calls = 0
            self.scan_ticks = 0
            self.prewarm_compiled = 0
            self.prewarm_skipped = 0
            self.prewarm_errors = 0
            self.prewarm_handoffs = 0
            self.prewarm_seconds = 0.0


#: ShapeEntry field -> the /metrics series that carries its per-kind sum
_KERNEL_BOOKS = {
    "calls": "siddhi_kernel_dispatches_total",
    "compiles": "siddhi_kernel_compile_count",
    "h2d_bytes": "siddhi_kernel_h2d_bytes_total",
    "d2h_bytes": "siddhi_kernel_d2h_bytes_total",
    "live_bytes": "siddhi_kernel_live_bytes",
    "scan_ticks": "siddhi_kernel_scan_ticks_total",
}

#: /metrics HELP/TYPE headers — rendered exactly once by
#: core/statistics.prometheus_text before any samples.
SHAPES_TYPES = [
    ("siddhi_kernel_dispatches_total", "counter",
     "Device launches of a kernel kind"),
    ("siddhi_kernel_compile_count", "gauge",
     "XLA compiles (incl. retraces) of a kernel kind"),
    ("siddhi_kernel_h2d_bytes_total", "counter",
     "Host bytes (numpy arguments) handed to a kernel kind's launches"),
    ("siddhi_kernel_d2h_bytes_total", "counter",
     "Device bytes read back from a kernel kind's results"),
    ("siddhi_kernel_live_bytes", "gauge",
     "Persistent device state (carry) placed for a kernel kind"),
    ("siddhi_kernel_scan_ticks_total", "counter",
     "Sequential scan ticks, ceil(T/B) a launch, of an NFA kernel kind"),
    ("siddhi_compile_seconds_total", "counter",
     "Attributed XLA trace+compile seconds per shape class"),
    ("siddhi_compile_phase_seconds_total", "counter",
     "The same seconds by phase: trace (Python to jaxpr), lower (jaxpr "
     "to MLIR), backend (XLA compile, or the persistent cache's load)"),
    ("siddhi_compile_blocked_seconds_total", "counter",
     "Caller wall seconds blocked on a compile per shape class"),
    ("siddhi_compile_total", "counter",
     "XLA compiles (incl. retraces) per shape class"),
    ("siddhi_compile_cache_hits_total", "counter",
     "Persistent compile-cache hits per shape class"),
    ("siddhi_compile_cache_misses_total", "counter",
     "Persistent compile-cache misses per shape class"),
    ("siddhi_shape_classes", "gauge",
     "Shape classes registered with the compile observatory"),
    ("siddhi_prewarm_compiled_total", "counter",
     "Grow-ladder rungs AOT-compiled ahead of need"),
    ("siddhi_prewarm_skipped_total", "counter",
     "Ladder rungs skipped because the shape was already compiled"),
    ("siddhi_prewarm_errors_total", "counter",
     "Ladder rungs that failed to compile"),
    ("siddhi_prewarm_handoffs_total", "counter",
     "Rebuilds that took over a prewarmed AOT executable (no re-trace)"),
    ("siddhi_prewarm_pending", "gauge",
     "Ladder rungs queued behind the prewarm worker"),
    ("siddhi_prewarm_seconds_total", "counter",
     "Background seconds spent prewarming the shape ladder"),
]


_REGISTRY = ShapeRegistry()


def shape_registry() -> ShapeRegistry:
    return _REGISTRY


# ------------------------------------------------------------ monitoring
# Listeners are installed once per process; the callbacks dispatch
# through the module-level registry so a test-reset registry keeps
# receiving credit.

_LISTENERS = {"installed": False}


def _on_event(event: str, **kwargs) -> None:
    _REGISTRY._credit_event(event)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    _REGISTRY._credit_duration(event, duration_secs)


def _install_listeners() -> None:
    with _CACHE_LOCK:
        if _LISTENERS["installed"]:
            return
        import jax.monitoring as monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENERS["installed"] = True

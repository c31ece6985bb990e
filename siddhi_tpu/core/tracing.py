"""The operator's Chrome-trace exporter (Perfetto-loadable).

The program has one span source, the latency ledger's span
(core/ledger.py): it credits the stage accumulators, lies on the
profiler's clock as a ``jax.profiler.TraceAnnotation`` and, while this
exporter is enabled (``@app:statistics(tracing='true')``,
``rt.enable_tracing()``), hands each finished span here as one complete
("ph": "X") trace event.  What is left in this module is the bounded
buffer and its three read surfaces: ``SiddhiAppRuntime.dump_trace(path)``,
``GET /siddhi/apps/{app}/trace`` and the incident bundle's ``trace``.
It holds no span and reads no clock.  The buffer is process-global for
the same reason the shape registry is — compiled plan objects outlive
and predate individual app runtimes.
"""
from __future__ import annotations

import json
import logging
import threading
from typing import Any, Dict, List, Optional

log = logging.getLogger(__name__)


class Tracer:
    def __init__(self, max_events: int = 500_000):
        self.enabled = False
        self.max_events = max_events
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def enable(self):
        self.enabled = True
        from .ledger import LEDGER_ENV, ledger_enabled
        if not ledger_enabled():
            log.warning(
                "tracing enabled with %s=0: the ledger's spans are this "
                "exporter's only source, so the trace stays empty while "
                "the ledger is switched off", LEDGER_ENV)

    def disable(self):
        self.enabled = False

    def clear(self):
        with self._lock:
            self._events.clear()

    def add(self, event: Dict[str, Any]) -> None:
        """One finished trace event (the ledger builds it)."""
        with self._lock:
            self._events.append(event)
            if len(self._events) > self.max_events:
                # bound memory: drop the oldest half
                del self._events[:len(self._events) // 2]

    def to_dict(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """Chrome-trace document.  ``limit`` keeps only the newest N
        events — incident bundles embed the trace, and a full buffer
        (up to 500k events) would dwarf everything else in the dump."""
        with self._lock:
            events = list(self._events if limit is None
                          else self._events[-limit:])
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"engine": "siddhi_tpu"}}

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


_GLOBAL = Tracer()


def tracer() -> Tracer:
    return _GLOBAL


def enable_tracing():
    _GLOBAL.enable()


def disable_tracing():
    _GLOBAL.disable()

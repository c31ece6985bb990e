"""What the observability surfaces need that is no profiler: the
backend's identity (``device_info``) and the always-on host-rim counters
(``RimStats``).  A launch's own books — calls, compiles, bytes, scan
ticks per kernel kind — are the shape registry's (plan/shapes.py)."""
from __future__ import annotations

from typing import Any, Dict, List


def device_info() -> Dict[str, Any]:
    """The backend as JAX reports it: {"platform", "kind", "count"}.
    Everything that prints a rate or a time prints this beside it — a
    number from the CPU backend must never read as a device number.
    Initialises the backend, so on a chip the caller becomes its owner."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class RimStats:
    """Always-on host-rim accounting (the measured side of the columnar
    end-to-end claim).  Two process-global counters:

      * ``events_materialized`` — per-event ``Event`` objects built from
        columnar chunks (``EventChunk.to_events``).  Zero across a
        columnar ingest→match→columnar-sink run IS the zero-copy
        property (tests/test_columnar_parity.py asserts it).
      * ``rim_ns`` — host-rim wall time (ingress conversion/validation +
        egress callback/sink delivery).

    Not gated on @app:statistics.  Increments are plain int adds under
    the GIL: the materialization counter's contract is exact on
    single-threaded paths and monotone everywhere, which is all its
    readers need."""

    __slots__ = ("events_materialized", "rim_ns")

    def __init__(self):
        self.events_materialized = 0
        self.rim_ns = 0

    # hot paths add to the attributes directly; these are for readers
    def snapshot(self) -> Dict[str, Any]:
        return {"events_materialized": self.events_materialized,
                "host_rim_seconds": self.rim_ns / 1e9}

    def reset(self) -> None:
        self.events_materialized = 0
        self.rim_ns = 0

    def prometheus_lines(self) -> List[str]:
        return [
            f"siddhi_events_materialized_total {self.events_materialized}",
            f"siddhi_host_rim_seconds_total {self.rim_ns / 1e9:.9g}",
        ]


_RIM = RimStats()


def rim_stats() -> RimStats:
    return _RIM

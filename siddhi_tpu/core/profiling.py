"""What the observability surfaces need that is no profiler: the
backend's identity (``device_info``) and the always-on host-rim counter
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
    end-to-end claim): ``events_materialized``, the per-event ``Event``
    objects built from columnar chunks (``EventChunk.to_events``).  Zero
    across a columnar ingest→match→columnar-sink run IS the zero-copy
    property (tests/test_columnar_parity.py asserts it).  The rim's time
    is the latency ledger's (core/ledger.py: its ``ingress`` and
    ``publish`` stages).

    Not gated on @app:statistics.  Increments are plain int adds under
    the GIL: the materialization counter's contract is exact on
    single-threaded paths and monotone everywhere, which is all its
    readers need."""

    __slots__ = ("events_materialized",)

    def __init__(self):
        self.events_materialized = 0

    # hot paths add to the attributes directly; these are for readers
    def snapshot(self) -> Dict[str, Any]:
        return {"events_materialized": self.events_materialized}

    def reset(self) -> None:
        self.events_materialized = 0

    def prometheus_lines(self) -> List[str]:
        return [
            f"siddhi_events_materialized_total {self.events_materialized}",
        ]


_RIM = RimStats()


def rim_stats() -> RimStats:
    return _RIM

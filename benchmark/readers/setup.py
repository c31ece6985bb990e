"""Reader of the set-up's compile time by phase, from the program's shape
registry (siddhi_tpu/plan/shapes.py).  The registry's totals stand for
everything since the process started when the metrics are taken: that is
the set-up, because a window compiles nothing (`compiles_in_window`).

    op "total": the sum of the named fields of `shape_registry().totals()`
                in seconds: `trace_seconds` (Python to jaxpr) and
                `lower_seconds` (jaxpr to MLIR) are paid again on a
                persistent-cache hit, `backend_seconds` is XLA's compile
                or, on a hit, the cache's load.

A program whose registry does not split its compile seconds by phase
reports nothing.
"""


def read(ctx, op, fields):
    if op != "total":
        raise ValueError(f"setup reader: unknown op {op!r}")
    from siddhi_tpu.plan.shapes import shape_registry
    totals = shape_registry().totals()
    if any(f not in totals for f in fields):
        return None
    return float(sum(totals[f] for f in fields))

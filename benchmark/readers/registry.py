"""Reader of the program's shape registry (siddhi_tpu/plan/shapes.py):
compiles, compile seconds and persistent-cache hits and misses per kind.

    op "delta": how much a total (`compiles`, `cache_misses`, ...) grew
                across the window.
"""


def _registry():
    from siddhi_tpu.plan.shapes import shape_registry
    return shape_registry()


def totals():
    return dict(_registry().totals())


def delta(before):
    now = totals()
    return {k: now[k] - before.get(k, 0) for k in now
            if isinstance(now[k], (int, float))}


def read(ctx, op, field="compiles"):
    if op != "delta":
        raise ValueError(f"registry reader: unknown op {op!r}")
    return float(ctx["window"]["registry"][field])


def print_table(log):
    """Per kind: shapes, compiles, compile seconds, cache hits, misses."""
    table = {}
    for e in _registry().snapshot()["entries"]:
        row = table.setdefault(e["kind"], [0, 0, 0.0, 0, 0])
        row[0] += 1
        row[1] += e["compiles"]
        row[2] += e["compile_seconds"]
        row[3] += e["cache_hits"]
        row[4] += e["cache_misses"]
    log("[registry] kind | shapes | compiles | compile_s | cache_hits | "
        "cache_misses")
    for kind in sorted(table):
        r = table[kind]
        log(f"[registry] {kind} | {r[0]} | {r[1]} | {r[2]:.3f} | {r[3]} | "
            f"{r[4]}")

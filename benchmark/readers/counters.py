"""Reader of the program's per-app counters (siddhi_tpu/core/ledger.py
`snapshot()["apps"][app]`, the ones `/stats` and `/metrics` show), as
they stand when the metrics are taken: since the process started, as
`readers/setup.py` reads the registry.

    op "ratio": counter `num` over counter `den`, in percent.

A program that does not keep one of the two counters, or an app whose
`den` is still 0, reports nothing.
"""
import re


def read(ctx, op, num, den):
    if op != "ratio":
        raise ValueError(f"counters reader: unknown op {op!r}")
    from siddhi_tpu.core.ledger import ledger
    m = re.search(r"@app:name\('([^']+)'\)", ctx["config"]["app"])
    if m is None:
        return None
    app = ledger().snapshot(m.group(1))["apps"].get(m.group(1), {})
    if num not in app or not app.get(den):
        return None
    return 100.0 * float(app[num]) / float(app[den])

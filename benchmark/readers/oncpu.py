"""Reader of the program's second clock on its spans
(siddhi_tpu/core/ledger.py `oncpu_seconds()`): per stage or declared
sub-span, the exclusive wall and CPU time of the spans that were
recorded.  Spans are recorded only while a profiler session (a traced
run's window) or the operator's exporter is on, so in a traced run the
pairs hold the window's spans and nothing of the warm-up.

    op "offcpu_share": 100 x (1 - CPU / wall) over the named keys: the
                share of their wall time the thread spent off its CPU
                (the GIL, the scheduler, another thread's work).

A program without the pairs, or whose named keys recorded no wall time,
reports nothing.
"""


def read(ctx, op, keys):
    if op != "offcpu_share":
        raise ValueError(f"oncpu reader: unknown op {op!r}")
    from siddhi_tpu.core.ledger import ledger
    pairs = getattr(ledger(), "oncpu_seconds", None)
    if pairs is None:
        return None
    pairs = pairs()
    wall = sum(pairs[k]["wall"] for k in keys if k in pairs)
    if wall <= 0:
        return None
    cpu = sum(pairs[k]["cpu"] for k in keys if k in pairs)
    return 100.0 * (1.0 - cpu / wall)

"""Reader of the generator's own clock: how late each send started after
it was due (open-loop cells only; a back-to-back cell has no due times).

    op "late_percentile": that percentile of (actual - due) in ms.
"""
import numpy as np


def _late_ms(win):
    gen = win["gen"]
    if gen["due"] is None or not len(gen["starts"]):
        return None
    return (gen["starts"] - gen["due"]) * 1e3


def read(ctx, op, q=95):
    if op != "late_percentile":
        raise ValueError(f"generator reader: unknown op {op!r}")
    late = _late_ms(ctx["window"])
    return None if late is None else float(np.percentile(late, q))


def print_lateness(win, log):
    late = _late_ms(win)
    if late is None:
        gaps = np.diff(win["gen"]["starts"]) * 1e3
        if len(gaps):
            log(f"[generator] back to back: {len(gaps) + 1} sends, gap "
                f"p50={np.percentile(gaps, 50):.3f} "
                f"p95={np.percentile(gaps, 95):.3f} max={gaps.max():.3f} ms")
        return
    log(f"[generator] open loop: {len(late)} sends, late "
        f"p50={np.percentile(late, 50):.3f} p95={np.percentile(late, 95):.3f}"
        f" max={late.max():.3f} ms")

"""Reader of the program's latency ledger (siddhi_tpu/core/ledger.py):
seven exclusive-time host-clock stage spans per block.  Its `device`
stage is the time the host spent issuing device steps and blocked on
their results, not device busy time; metrics built on it say so.

    op "share": the named stages' nanoseconds across the window, as a
                share of the window in percent;
    op "percentile": a percentile of the per-block values of the named
                stages over the window's blocks, in ms (the program's
                log-bucketed histograms: about 6% resolution); several
                stages add up.
"""

_STAT = {50: "p50", 95: "p95", 99: "p99"}


def _ledger():
    from siddhi_tpu.core.ledger import ledger
    return ledger()


def _app_name(config):
    import re
    m = re.search(r"@app:name\('([^']+)'\)", config["app"])
    return m.group(1) if m else None


def open_window(config):
    """Forget the app's per-block histograms (warm-up's blocks), keep the
    process-wide accumulators, and note where they stand."""
    led = _ledger()
    app = _app_name(config)
    if app is not None:
        led.drop_app(app)
    return led.stage_ns()


def close_window(config, ns_open):
    led = _ledger()
    now = led.stage_ns()
    app = _app_name(config)
    stages = led.snapshot(app)["apps"].get(app, {}).get("stages_ms", {}) \
        if app is not None else {}
    return {"stage_ns": {s: now[s] - ns_open.get(s, 0) for s in now},
            "stages_ms": stages}


def read(ctx, op, stages, q=50):
    led = ctx["window"]["ledger"]
    if op == "share":
        ns = sum(led["stage_ns"].get(s, 0) for s in stages)
        return 100.0 * ns / 1e9 / ctx["window"]["window_s"]
    if op == "percentile":
        vals = [led["stages_ms"][s][_STAT[q]] for s in stages
                if s in led["stages_ms"] and led["stages_ms"][s]["count"]]
        return float(sum(vals)) if vals else None
    raise ValueError(f"ledger reader: unknown op {op!r}")


def print_waterfall(win, log):
    led = win["ledger"]
    total = sum(led["stage_ns"].values()) or 1
    for s, ns in led["stage_ns"].items():
        h = led["stages_ms"].get(s, {})
        log(f"[ledger] {s:<10} {ns / 1e9:9.4f} s "
            f"{100.0 * ns / 1e9 / win['window_s']:6.2f}% of window  "
            f"per block p50={h.get('p50', 0):.3f} p95={h.get('p95', 0):.3f}"
            f" ms n={h.get('count', 0)}")
    log(f"[ledger] all stages {total / 1e9:.4f} s over a window of "
        f"{win['window_s']:.4f} s (stages of different threads overlap)")

#!/usr/bin/env python3
"""One process, one cell, one run: load, warm up, measure, compare, print.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file this harness finds by the name BENCHMARK.json
gives; no such name appears in this file.  The last line of standard
output is the result object; every intermediate number goes on earlier
lines.  See benchmark/README.md.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

_T_IMPORT = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg):
    print(msg, flush=True)


def process_start_unix():
    """When this process started, by the kernel's account; the stamp taken
    as this file was first read where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return _T_IMPORT


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py as a module, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """What BENCHMARK.json and the data files say about one cell."""

    def __init__(self, workload):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"run.py: no workload {workload!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, conf["file"]))
        self.workload = load_json(os.path.join(
            HERE, "workloads", f"{workload}.json"))
        self.peaks = load_json(os.path.join(HERE, "peaks.json"))

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The metrics that list this cell, and those that list none and
        move an end-to-end metric this cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def metric_file(self, name):
        """metrics/<name>.json; a quantity split by the end-to-end metric
        it moves (`x.<suffix>`) may keep one file, metrics/x.json."""
        path = os.path.join(HERE, "metrics", f"{name}.json")
        if not os.path.exists(path) and "." in name:
            path = os.path.join(HERE, "metrics",
                                f"{name.rsplit('.', 1)[0]}.json")
        return load_json(path)


# ------------------------------------------------------------------ device

def device_header(chips, require_tpu=True):
    """Platform, kind, count as JAX reports them; no TPU, or fewer chips
    than the cell asks for, ends the run with code 2 and no result."""
    import importlib.metadata as md

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            vers[pkg] = "absent"
    log(f"[device] platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} " +
        " ".join(f"{k}={v}" for k, v in vers.items()))
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < chips):
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"platform={dev['platform']} count={dev['count']}",
              file=sys.stderr)
        raise SystemExit(2)
    return dev


def memory_peak_bytes():
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ------------------------------------------------------------------ stages

# each size of the warm-up ladder is sent twice: the first block of a size
# compiles (or loads) its step, the second its egress read of that step
LADDER_REPEATS = 2


class GcPauses:
    """How long the interpreter's collector stopped the process inside the
    window, by generation: a log line, so that a pause seen in a latency
    tail or in the generator's lateness can be told from one of the
    system's own."""

    def __init__(self):
        self.pauses = []            # (generation, seconds)
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self):
        out = {}
        for gen in (0, 1, 2):
            p = [s for g, s in self.pauses if g == gen]
            out[f"gen{gen}"] = {"n": len(p), "total_ms": 1e3 * sum(p),
                                "max_ms": 1e3 * max(p, default=0.0)}
        return out

def warm_up(served, traffic, workload):
    """Every shape the window can meet, compiled before it opens: single
    batches of 1x, 2x, ... sends (the sizes the junction's re-batching
    can deliver), each closed by a flush so none coalesce, then a stretch
    of the cell's own traffic."""
    warm = workload.get("warmup", {})
    for k in warm.get("ladder", [1]):
        for _ in range(LADDER_REPEATS):
            cols, ts = traffic.take(int(k))
            served.send(cols, ts)
            served.flush()
    secs = float(warm.get("seconds", 0))
    if secs > 0:
        traffic.run_window(served.send, secs)
        served.flush()


def measure(served, traffic, seconds, tracer=None):
    """The window: opens at a flush barrier, closes at the flush after the
    generator's last send.  -> what the readers and metrics need."""
    from readers import ledger as ledger_reader
    from readers import registry as registry_reader
    served.flush()
    n_chunks0 = len(served.chunks)
    led0 = ledger_reader.open_window(served.config)
    reg0 = registry_reader.totals()
    import gc
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    if tracer is not None:
        tracer.start()
    t_open = time.perf_counter()
    gen = traffic.run_window(served.send, seconds)
    served.flush()
    t_close = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    gc.callbacks.remove(pauses)
    return {"t_open": t_open, "gc": pauses.summary(), "t_close": t_close,
            "window_s": t_close - t_open, "gen": gen,
            "events": gen["n_sends"] * traffic.send_events,
            "chunks": served.chunks[n_chunks0:],
            "ledger": ledger_reader.close_window(served.config, led0),
            "registry": registry_reader.delta(reg0)}


def window_rows(win, traffic):
    """The rows delivered for the window's own events."""
    from system import table
    tab = table(win["chunks"])
    keep = tab["__ts"] >= traffic.first_ts_of_send(win["gen"]["first_send"])
    return {k: v[keep] for k, v in tab.items()}


def reference_rows(cell, traffic, dtype=None):
    """The plain reference over everything sent so far."""
    ref_spec = cell.config["reference"]
    ref = load_module("references", ref_spec["name"])
    cols, ts = traffic.sent_events()
    kw = {} if dtype is None else {"dtype": dtype}
    return ref.run(cols, ts, ref_spec["args"], **kw)


def judge(cell, served_rows, traffic, win, guards):
    """Reference over everything sent, rows of the window's events
    compared; -> (checks, seconds the reference and comparison took)."""
    import numpy as np

    from compare import compare_rows
    t = time.perf_counter()
    ref_rows = reference_rows(cell, traffic)
    keep = ref_rows["__ts"] >= traffic.first_ts_of_send(
        win["gen"]["first_send"])
    ref_rows = {k: np.asarray(v)[keep] for k, v in ref_rows.items()}
    checks = compare_rows(served_rows, ref_rows, cell.config["compare"],
                          traffic.key_columns)
    for name, value in guards.items():
        checks[name] = {"value": value, "limit": 0, "op": "<="}
    return checks, time.perf_counter() - t


def serve_window(cell, seed, seconds, tracer=None, system_factory=None):
    """Build the app, warm it up, measure one window, read the device's
    peak memory and the guards, shut the app down (its state is freed
    before any reference runs).  -> (traffic, window, the window's rows)."""
    from readers import registry as registry_reader
    from system import Served
    from traffic import Traffic
    traffic = Traffic(cell.config, cell.workload, seed)
    served = (system_factory or Served)(cell.config)
    warm_up(served, traffic, cell.workload)
    log(f"[warm] sends={traffic.next_send} "
        f"events={traffic.next_send * traffic.send_events}")
    registry_reader.print_table(log)
    opened_unix = time.time()
    win = measure(served, traffic, float(seconds), tracer)
    win["opened_unix"] = opened_unix
    win["memory_peak_bytes"] = memory_peak_bytes()
    win["guards"] = {"queries_off_device": len(served.not_on_device()),
                     "events_lost": sum(served.lost_events().values())}
    log(f"[guards] backends={served.backends()} lost={served.lost_events()}")
    served.shutdown()
    return traffic, win, window_rows(win, traffic)


# ---------------------------------------------------------------- the run

def execute(opts, require_tpu=True, system_factory=None):
    """One run of one cell; -> the result object (also printed by main).
    Tests pass require_tpu=False and a system_factory that breaks the
    timed path underneath."""
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    t_start = process_start_unix()
    cell = Cell(opts.workload)
    dev = device_header(cell.chips, require_tpu)
    if dev["kind"] not in cell.peaks["devices"] and require_tpu:
        raise SystemExit(f"run.py: device_kind {dev['kind']!r} is not in "
                         f"peaks.json")

    from siddhi_tpu.native_ext import native_status
    from siddhi_tpu.plan.shapes import configure_compile_cache
    native = native_status()
    log(f"[native] built_now={native['built']} loaded={native['loaded']} "
        f"error={native['error']!r}")
    if not native["loaded"]:
        raise SystemExit("run.py: the native packer did not load")
    cache = configure_compile_cache()
    log(f"[cache] dir={cache['dir']} enabled={cache['enabled']}")

    import endtoend
    from readers import generator as generator_reader
    from readers import ledger as ledger_reader
    from readers import trace as trace_reader

    tracer = trace_reader.Tracer(os.path.join(ROOT, ".bench_trace")) \
        if opts.trace else None
    traffic, win, rows = serve_window(cell, opts.seed, opts.seconds, tracer,
                                      system_factory)
    setup = win["opened_unix"] - t_start
    guards = win["guards"]
    e2e = endtoend.values(win, rows, traffic, setup)
    log(f"[window] seconds={win['window_s']:.4f} sends={win['gen']['n_sends']}"
        f" events={win['events']} rows={len(rows['__ts'])} "
        f"set-up={setup:.3f} s")
    ledger_reader.print_waterfall(win, log)
    generator_reader.print_lateness(win, log)
    log(f"[gc] collector pauses in the window: {win['gc']}")
    log(f"[registry] in window: {win['registry']}")
    log(f"[e2e] {e2e}")

    ctx = {"config": cell.config, "window": win, "rows": len(rows["__ts"]),
           "device": dev, "peaks": cell.peaks, "trace": None,
           "load_module": load_module}
    device = dict(dev, memory_peak_bytes=win["memory_peak_bytes"])
    result = {}
    if tracer is not None:
        ctx["trace"] = tracer.reduce(cell.config["kernel"]["step_modules"])
        tracer.discard()
        trace_reader.print_summary(ctx["trace"], log)
        device.update(busy_s=ctx["trace"]["busy_s"],
                      window_s=ctx["trace"]["window_s"])
        result["breakdown"] = ctx["trace"]["breakdown"]

    metrics = {}
    if opts.trace:
        for m in cell.per_layer():
            spec = cell.metric_file(m["name"])
            reader = load_module("readers", spec["reader"])
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    checks, judge_s = judge(cell, rows, traffic, win, guards)
    from compare import holds, verdict
    log(f"[compare] reference and comparison took {judge_s:.2f} s")
    attempted = win["events"]
    failed = int(checks["rows_unmatched"]["value"]) + guards["events_lost"]
    compared = {k: {"value": c["value"], "limit": c["limit"]}
                for k, c in checks.items()}
    out = {"correct": verdict(checks), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    out.update(result)
    out["compared"] = compared
    for k, c in checks.items():
        print(f"[compared] {k} = {c['value']} (limit {c['op']} "
              f"{c['limit']}) {'ok' if holds(c) else 'FAILED'}",
              file=sys.stderr, flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    opts = ap.parse_args(argv)
    out = execute(opts)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

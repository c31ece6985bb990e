"""The one reduction from a jax.profiler trace to numbers.

A traced run wraps its window in jax.profiler.start_trace/stop_trace (no
Python tracer) and reads the .xplane.pb with jax.profiler.ProfileData.
Per device plane (`/device:TPU:<n>`):

  busy_s    union of the intervals on the "XLA Ops" line, averaged over
            the device planes that ran anything;
  modules   per XLA module (the "XLA Modules" line; names are
            `jit_<python function>(<fingerprint>)` because the program
            jits bare — a configuration's `kernel.step_modules` regex says
            which of them are its step): executions and device seconds;
  breakdown the ten ops with most device time, and the ten host
            activities that cover most of the device's idle gaps.

    op "step_ms_per_mev": device ms of the step modules per million
                          events of the window;
    op "step_roofline":   the least time the chip could take for the same
                          blocks, events and rows (kernels/<family>.py
                          over peaks.json) as a share of the step
                          modules' device time, in percent.

A reader that finds no device plane, or no step module, returns nothing.
"""
import glob
import os
import re
import shutil
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    def __init__(self, directory):
        self.dir = directory
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def path(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def reduce(self, step_regex):
        path = self.path()
        if path is None:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        t = time.perf_counter()
        red = reduce_xplane(path, step_regex)
        red["reduce_s"] = time.perf_counter() - t
        red["window_s"] = self.t_stop - self.t_start
        red["xplane_bytes"] = os.path.getsize(path)
        return red

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _base(name):
    """`jit_step(123456)` -> `jit_step`."""
    return re.sub(r"\(\d+\)$", "", name)


ENCLOSING = {"while", "conditional", "call"}


def _short(name):
    """An "XLA Ops" event is named by its whole HLO line, `%name = shape
    opcode(operands...)`; keep the op's name, its opcode and its (first)
    output shape.  -> (short, opcode)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80], ""
    if rest.startswith("("):                # a tuple of shapes
        depth = 0
        for k, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[1:k], rest[k + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0].strip()
    first = re.split(r"[{ ]", shape, maxsplit=1)[0].rstrip(",")
    return f"{head} {opcode} {first}"[:80], opcode


def reduce_xplane(path, step_regex, device_plane=DEVICE_PLANE):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    step = re.compile(step_regex)
    busy, modules, ops, host = [], {}, {}, []
    busy_iv = []
    for plane in data.planes:
        if device_plane.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv = []
                    for ev in line.events:
                        iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                        row = ops.setdefault(ev.name, [0, 0.0])
                        row[0] += 1
                        row[1] += ev.duration_ns / 1e9
                    if iv:
                        merged = _union(iv)
                        busy.append(sum(e - s for s, e in merged) / 1e9)
                        busy_iv.append(merged)
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        row = modules.setdefault(_base(ev.name), [0, 0.0])
                        row[0] += 1
                        row[1] += ev.duration_ns / 1e9
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        thread = line.name.split("/")[0] or "main"
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     f"{thread}:{ev.name}"[:80]))
    step_mods = {m: v for m, v in modules.items() if step.search(m)}
    # a while or a call encloses its body's ops, which are listed too
    short = {}
    for name, (n, secs) in ops.items():
        label, opcode = _short(name)
        if opcode in ENCLOSING:
            continue
        row = short.setdefault(label, [0, 0.0])
        row[0] += n
        row[1] += secs
    top_ops = sorted(short.items(), key=lambda kv: -kv[1][1])[:10]
    return {
        "device_planes": len(busy),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "modules": modules,
        "step_s": sum(v[1] for v in step_mods.values()),
        "step_runs": sum(v[0] for v in step_mods.values()),
        "step_modules": sorted(step_mods),
        "breakdown": {
            "device_ops": [[n, v[1]] for n, v in top_ops],
            "idle_gaps": _idle_gaps(busy_iv[0] if busy_iv else [], host),
        },
    }


LOOKBACK_NS = 2_000_000_000
UNNAMED = "no host span (untraced Python)"


def _idle_gaps(busy, host, top=10, longest=4000):
    """The device's idle gaps by what the host was doing: each of the
    `longest` gaps goes to the shortest host span that covers at least
    half of it (so an enclosing span does not hide what ran inside it),
    among the spans that start in the LOOKBACK_NS before the gap's end:
    by time and not by a count of events, since the runtime writes tens
    of thousands of short events for one upload.  A gap that no span
    covers by half (a wait for the next send, then its packing) is cut in
    two and each half named alone, down to an eighth of the gap; what no
    span covers then is the host running untraced Python.
    -> the `top` activities by gap seconds."""
    import numpy as np
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:longest]
    host = sorted(host)
    start = np.asarray([h[0] for h in host], np.int64)
    end = np.asarray([h[1] for h in host], np.int64)
    by = {}

    def credit(g0, g1, halvings):
        lo, hi = np.searchsorted(start, [g1 - LOOKBACK_NS, g1 + 1])
        s, e = start[lo:hi], end[lo:hi]
        covers = np.flatnonzero(
            2 * (np.minimum(e, g1) - np.maximum(s, g0)) >= g1 - g0)
        if not len(covers) and halvings:
            mid = (g0 + g1) // 2
            credit(g0, mid, halvings - 1)
            credit(mid, g1, halvings - 1)
            return
        who = host[lo + covers[np.argmin((e - s)[covers])]][2] \
            if len(covers) else UNNAMED
        by[who] = by.get(who, 0.0) + (g1 - g0) / 1e9

    for _length, g0, g1 in gaps:
        credit(g0, g1, 3)
    return [[n, s] for n, s in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def read(ctx, op):
    tr = ctx["trace"]
    if not tr or not tr["device_planes"] or tr["step_s"] <= 0:
        return None
    win = ctx["window"]
    if op == "step_ms_per_mev":
        return tr["step_s"] * 1e3 / (win["events"] / 1e6)
    if op == "step_roofline":
        kern = ctx["config"]["kernel"]
        peaks = ctx["peaks"]["devices"].get(ctx["device"]["kind"])
        if peaks is None:
            return None
        # blocks as the deployment has them (one per query and delivered
        # block, by the ledger's count), not the implementation's calls
        blocks = win["ledger"]["stages_ms"].get("dispatch", {}).get(
            "count") or tr["step_runs"]
        cost = ctx["load_module"]("kernels", kern["family"]).cost(
            kern["shape"], blocks, win["events"], ctx["rows"])
        least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                    cost["flops"] / peaks["flops_per_s"])
        return 100.0 * least / tr["step_s"]
    raise ValueError(f"trace reader: unknown op {op!r}")


def print_summary(tr, log):
    log(f"[trace] device_planes={tr['device_planes']} busy_s="
        f"{tr['busy_s']:.4f} window_s={tr['window_s']:.4f} idle_share="
        f"{100 * (1 - tr['busy_s'] / tr['window_s']):.2f}% xplane_bytes="
        f"{tr['xplane_bytes']} reduce_s={tr['reduce_s']:.2f}")
    for name, (n, s) in sorted(tr["modules"].items(),
                               key=lambda kv: -kv[1][1])[:12]:
        log(f"[trace] module {name}: runs={n} device_s={s:.4f}")
    log(f"[trace] step modules {tr['step_modules']}: runs={tr['step_runs']} "
        f"device_s={tr['step_s']:.4f}")
    for name, s in tr["breakdown"]["device_ops"]:
        log(f"[trace] op {name}: {s:.4f} s")
    for name, s in tr["breakdown"]["idle_gaps"]:
        log(f"[trace] idle gap under {name}: {s:.4f} s")

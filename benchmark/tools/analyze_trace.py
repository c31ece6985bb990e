"""A builder's tool, not part of a run: run one traced cell through
benchmark/run.py and, before the trace is discarded, look into the
.xplane.pb for what `breakdown.idle_gaps` cannot say (PERF.md 5 and 6
quote its numbers):

  longest_gaps   the 12 longest device idle gaps: how many host events
                 start inside each, and which program spans (`siddhi/...`,
                 `bench....`) overlap it;
  gaps_by_program_span
                 the reader's rule (`readers/trace.py` `_idle_gaps`: the
                 shortest span that covers at least half of the gap names
                 it) over the program's spans alone, the runtime's own
                 events left out;
  step_ms        the step modules' runs by device duration, rounded to
                 0.1 ms: one depth of block shows as one duration;
  block_join     per block and query, by the spans' `block` stat: send ->
                 dequeue -> submit -> retire -> callback, the table that
                 reconciles a paced cell's `match_latency_p50_ms`.

    python3 benchmark/tools/analyze_trace.py --workload pattern_10k.paced \
        --seed 7 --seconds 20 --out chiprun_out/pattern_10k.paced.json
"""
import argparse
import bisect
import collections
import json
import os
import re
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]
import run as bench_run                      # noqa: E402  benchmark/run.py
from readers import trace as trace_reader    # noqa: E402


def _program_span(name):
    return name.startswith("siddhi/") or name.startswith("bench.")


def _host_busy_steps(path, step_regex):
    """-> (host events sorted by start, the device's busy intervals, the
    step modules' runs by duration in ms)."""
    from jax.profiler import ProfileData
    step = re.compile(step_regex)
    host, busy, step_ms = [], [], collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if trace_reader.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reader.OPS_LINE:
                    busy = trace_reader._union(
                        [(e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events])
                elif line.name == trace_reader.MODULES_LINE:
                    step_ms.update(
                        round(e.duration_ns / 1e6, 1) for e in line.events
                        if step.search(trace_reader._base(e.name)))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                thread = line.name.split("/")[0] or "main"
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    block = None
                    if ev.name.startswith("siddhi/"):
                        for k, v in ev.stats:
                            if k == "block":
                                block = int(v)
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name, thread, block))
    host.sort()
    return host, busy, step_ms


def _longest_gaps(host, gaps):
    starts = [h[0] for h in host]
    rows = []
    for length, g0, g1 in gaps[:12]:
        hi = bisect.bisect_right(starts, g1)
        over = collections.Counter()
        for s, e, name, *_ in host[bisect.bisect_left(
                starts, g0 - 400_000_000):hi]:
            if _program_span(name):
                o = min(e, g1) - max(s, g0)
                if o > 0:
                    over[name] += o / 1e6
        rows.append({
            "gap_ms": length / 1e6,
            "host_events_starting_in_gap":
                hi - bisect.bisect_left(starts, g0),
            "program_spans_overlap_ms":
                [[n, round(v, 2)] for n, v in over.most_common(8)]})
    return rows


def _block_join(host):
    """A block's delivery is its `siddhi/deliver` span; query q's submit
    is the end of the q-th `siddhi/device` span inside it, its retire the
    q-th `siddhi/decode` (windowed agg) or `siddhi/device.retire`
    (pattern) span of that block, and its callback the first
    `bench.callback` after the retire starts."""
    blocks = collections.defaultdict(lambda: collections.defaultdict(list))
    for s, e, name, _thread, block in host:
        if block is not None:
            blocks[block][name].append((s, e))
    sends = sorted((s, e) for s, e, n, *_ in host if n == "bench.send_batch")
    cbs = sorted((s, e) for s, e, n, *_ in host if n == "bench.callback")
    send_starts = [s[0] for s in sends]
    cb_starts = [c[0] for c in cbs]
    comp = collections.defaultdict(list)
    for _block, sp in sorted(blocks.items()):
        dl = sp.get("siddhi/deliver")
        keys = sp.get("siddhi/dispatch.keys", [])
        retires = sorted(sp.get("siddhi/decode", [])) or \
            sorted(sp.get("siddhi/device.retire", []))
        if not dl or not keys or len(retires) != len(keys):
            continue
        t_deq = dl[0][0]
        k = bisect.bisect_right(send_starts, t_deq) - 1
        devs = sorted(x for x in sp.get("siddhi/device", [])
                      if dl[0][0] <= x[0] <= dl[0][1])
        if k < 0 or len(devs) != len(keys):
            continue
        t_send = sends[k][0]
        for q, ((rs, _re), dv) in enumerate(zip(retires, devs)):
            j = bisect.bisect_left(cb_starts, rs)
            nxt = retires[q + 1][0] if q + 1 < len(retires) else None
            if j >= len(cbs) or (nxt is not None and cbs[j][0] > nxt) \
                    or cbs[j][0] - rs > 50_000_000:
                continue        # this query had no rows in this block
            comp["A_send_to_dequeue"].append((t_deq - t_send) / 1e6)
            comp["B_dequeue_to_submit"].append((dv[1] - t_deq) / 1e6)
            comp["C_submit_to_retire"].append((rs - dv[1]) / 1e6)
            comp["D_retire_to_callback"].append((cbs[j][1] - rs) / 1e6)
            comp["total"].append((cbs[j][1] - t_send) / 1e6)
    return {"rows": len(comp["total"]), "blocks": len(blocks),
            "median_ms": {k: statistics.median(v)
                          for k, v in comp.items() if v},
            "mean_ms": {k: statistics.fmean(v) for k, v in comp.items() if v}}


def analyze(path, out, step_regex):
    host, busy, step_ms = _host_busy_steps(path, step_regex)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)
    res = {"host_events": len(host),
           "idle_s": sum(g[0] for g in gaps) / 1e9,
           "longest_gaps": _longest_gaps(host, gaps),
           "gaps_by_program_span": trace_reader._idle_gaps(
               busy, [h[:3] for h in host if _program_span(h[2])], top=12),
           "step_ms": sorted(step_ms.items()),
           "block_join": _block_join(host)}
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    print("[analyze]", json.dumps(res)[:6000], flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    opts = ap.parse_args()
    opts.trace = 1
    discard = trace_reader.Tracer.discard
    step_regex = bench_run.Cell(opts.workload).config["kernel"][
        "step_modules"]

    def analyze_then_discard(self):
        try:
            analyze(self.path(), opts.out, step_regex)
        finally:
            discard(self)

    trace_reader.Tracer.discard = analyze_then_discard
    import system
    shutdown = system.Served.shutdown

    def counters_then_shutdown(self):
        from siddhi_tpu.core.ledger import ledger
        for app, entry in ledger().snapshot()["apps"].items():
            print("[counters]", app, json.dumps(
                {k: v for k, v in entry.items() if k.startswith("retire_")}),
                flush=True)
        shutdown(self)

    system.Served.shutdown = counters_then_shutdown
    print(json.dumps(bench_run.execute(opts)), flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The one-time sweep that finds a paced cell's rate: the same app, for
each offered rate (lowest first) `--repeats` windows.  A window sustains
its rate when

  - the generator kept up: the 95th percentile of its lateness is under
    one send period (a maximum is hit by any pause of the process);
  - no backlog was left: the closing flush drained in under DRAIN_S;
  - no backlog grew: the latency p95 of the window's second half is under
    GROWTH times that of its first half;

and a rate is sustained when every one of its windows is.  The knee is the
highest sustained rate: a system that sustains a rate has the capacity for
every lower one, so a window that fails below the knee is a transient and
is reported as such.  Each window also gives its latency p95 as a share of
the send period (`p95_periods`: under 0.9, a block's rows have left before
the next send is due, so no send waits behind another) and the 5th
percentile beside the 50th and 95th (rows that leave in two groups show
as a wide p05..p95).  Its output goes into PERF.md by hand and the rate
chosen into the workload file, with `rate_from` saying by which rule; no
run reads it.

    python3 benchmark/sweep.py --workload <cell> --rates 50000,100000,... \
        [--seed 1] [--seconds 6] [--repeats 3]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import endtoend  # noqa: E402
import run  # noqa: E402

DRAIN_S = 0.25
GROWTH = 1.25


def one_window(served, traffic, seconds):
    win = run.measure(served, traffic, seconds)
    rows = run.window_rows(win, traffic)
    gen = win["gen"]
    lat, send = endtoend.latencies_ms(win, rows, traffic)
    half = send < gen["n_sends"] // 2
    late = (gen["starts"] - gen["due"]) * 1e3
    period_ms = traffic.send_events / traffic.rate * 1e3
    p05, p50, p95 = (float(v) for v in np.percentile(lat, [5, 50, 95]))
    row = {"sends": gen["n_sends"], "rows": len(lat),
           "p05_ms": p05, "p50_ms": p50, "p95_ms": p95,
           "p95_periods": p95 / period_ms,
           "growth": float(np.percentile(lat[~half], 95)
                           / np.percentile(lat[half], 95)),
           "late_p95_ms": float(np.percentile(late, 95)),
           "late_max_ms": float(late.max()),
           "drain_s": win["t_close"] - gen["t_last_send_done"],
           "compiles": win["registry"]["compiles"]}
    row["sustained"] = bool(row["late_p95_ms"] < period_ms
                            and row["drain_s"] < DRAIN_S
                            and row["growth"] < GROWTH)
    return row


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--repeats", type=int, default=3)
    opts = ap.parse_args(argv)
    cell = run.Cell(opts.workload)
    run.device_header(cell.chips, require_tpu)

    from siddhi_tpu.plan.shapes import configure_compile_cache
    from system import Served
    from traffic import Traffic
    configure_compile_cache()
    traffic = Traffic(cell.config, cell.workload, opts.seed)
    served = Served(cell.config)
    run.warm_up(served, traffic, cell.workload)
    table = []
    for rate in sorted(float(r) for r in opts.rates.split(",")):
        traffic.rate = rate
        wins = [one_window(served, traffic, opts.seconds)
                for _ in range(opts.repeats)]
        row = {"rate": rate,
               "period_ms": traffic.send_events / rate * 1e3,
               "sustained": all(w["sustained"] for w in wins),
               "windows": wins}
        table.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
    served.shutdown()
    print("[sweep] rate | period_ms | p05_ms | p50_ms | p95_ms | p95_periods "
          "| growth | late_p95_ms | drain_s | sustained   (median, worst "
          f"of {opts.repeats} windows)")
    for r in table:
        def two(k):
            v = [w[k] for w in r["windows"]]
            return f"{np.median(v):.2f}, {max(v):.2f}"
        print(f"[sweep] {r['rate']:.0f} | {r['period_ms']:.2f} | "
              f"{two('p05_ms')} | {two('p50_ms')} | {two('p95_ms')} | "
              f"{two('p95_periods')} | {two('growth')} | "
              f"{two('late_p95_ms')} | {two('drain_s')} | {r['sustained']}")
    good = [r["rate"] for r in table if r["sustained"]]
    knee = max(good) if good else None
    below = [r["rate"] for r in table
             if not r["sustained"] and knee and r["rate"] < knee]
    print(f"[sweep] knee={knee} 0.8 of it={0.8 * knee if knee else None} "
          f"transients below it at {below}")
    return table


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Readings for a cell's limits: in one process, per seed, a short window
at the cell's own load; the program's rows compared with the plain
reference (the lower reading of each number), and the control's — the
reference computed in the nearest precision below the one the
configuration states (bfloat16 for float32), put in the program's place
and compared the same way (the upper reading).  The control has to come
out not correct.  The benchmark's own runs never run this.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 5]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import run  # noqa: E402


def control_rows(cell, traffic, win, dtype):
    """The reference at `dtype` over everything sent, cut to the window's
    events, shaped as the program serves it (key strings)."""
    rows = run.reference_rows(cell, traffic, dtype)
    keep = rows["__ts"] >= traffic.first_ts_of_send(win["gen"]["first_send"])
    rows = {k: np.asarray(v)[keep] for k, v in rows.items()}
    for col, table in traffic.key_columns.items():
        if col in rows:
            rows[col] = table[rows[col]]
    return rows


def main(argv=None, require_tpu=True):
    import ml_dtypes

    from compare import verdict
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    opts = ap.parse_args(argv)
    cell = run.Cell(opts.workload)
    run.device_header(cell.chips, require_tpu)

    from siddhi_tpu.plan.shapes import configure_compile_cache
    configure_compile_cache()
    for seed in (int(s) for s in opts.seeds.split(",")):
        traffic, win, rows = run.serve_window(cell, seed, opts.seconds)
        guards = win["guards"]
        prog, _ = run.judge(cell, rows, traffic, win, guards)
        low = control_rows(cell, traffic, win, ml_dtypes.bfloat16)
        ctrl, _ = run.judge(cell, low, traffic, win, {})
        print("[control] " + json.dumps({
            "workload": cell.name, "seed": seed, "events": win["events"],
            "rows": len(rows["__ts"]),
            "program": {k: c["value"] for k, c in prog.items()},
            "program_correct": verdict(prog),
            "control": {k: c["value"] for k, c in ctrl.items()},
            "control_correct": verdict(ctrl)}), flush=True)


if __name__ == "__main__":
    main()

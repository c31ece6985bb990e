#!/usr/bin/env python
"""Tier-1 timing report: turn a pytest log into a per-file table.

The tier-1 suite runs under a wall-clock budget, so knowing WHERE the
seconds go is the difference between "the suite is slow" and "one file
regressed 3x".  This parses the output of

    pytest tests/ -q -m 'not slow' --durations=0 ... 2>&1 | tee t1.log

(the ``--durations=0`` section lists every test phase as
``<sec>s <call|setup|teardown> <file>::<test>``) and emits

  * a per-file timing table on stdout (seconds by phase, test count),
  * optionally a JSON artifact (``-o T1_rNN.json``) so rounds can be
    diffed.

Also extracted: the pass/fail/skip/error tallies, total wall time, and
the DOTS count (progress characters), which is the cross-round
comparison number the tier-1 budget workflow uses.

Usage:
    python tools/t1_report.py /tmp/_t1.log [-o T1_r10.json] [--top 25]
    python tools/t1_report.py --compare T1_r11.json T1_r12.json

``--compare OLD.json NEW.json`` diffs two such artifacts: per-file
regressions beyond 2x are flagged (exit 1), new and vanished files are
listed, and the tally deltas are printed — the round-over-round
regression gate for the tier-1 timing budget.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict

DUR_RE = re.compile(
    r"^\s*(\d+(?:\.\d+)?)s\s+(call|setup|teardown)\s+"
    r"([\w./\\-]+\.py)::(\S+)")
#: "==== 857 passed, 3 skipped in 612.33s ====" (plain form under -q:
#: "857 passed, 3 skipped in 612.33s (0:10:12)")
SUMMARY_RE = re.compile(
    r"^(?:=+ )?((?:\d+ [a-z]+,? ?)+) in (\d+(?:\.\d+)?)s")
TALLY_RE = re.compile(r"(\d+) (passed|failed|skipped|errors?|xfailed|"
                      r"xpassed|warnings?|deselected)")
#: pytest -q progress lines: dots/letters, optionally ending "[ 37%]"
DOTS_RE = re.compile(r"^[.FEsx]+( *\[ *\d+%\])?$")


def parse_log(lines):
    per_file = defaultdict(lambda: {"call_s": 0.0, "setup_s": 0.0,
                                    "teardown_s": 0.0, "tests": set()})
    tallies, wall_s, dots = {}, None, 0
    for line in lines:
        line = line.rstrip("\n")
        m = DUR_RE.match(line)
        if m:
            sec, phase, path, test = m.groups()
            rec = per_file[path]
            rec[f"{phase}_s"] += float(sec)
            rec["tests"].add(test.split("[")[0])
            continue
        m = DOTS_RE.match(line)
        if m:
            dots += line.split("[")[0].count(".")
            continue
        m = SUMMARY_RE.search(line)
        if m:
            # a concatenation of several pytest runs (the 870 s budget
            # forces the suite into slices) sums naturally
            wall_s = round((wall_s or 0.0) + float(m.group(2)), 2)
            for n, what in TALLY_RE.findall(m.group(1)):
                key = what.rstrip("s") if what != "passed" else what
                tallies[key] = tallies.get(key, 0) + int(n)
    files = {}
    for path, rec in sorted(per_file.items()):
        total = rec["call_s"] + rec["setup_s"] + rec["teardown_s"]
        files[path] = {
            "total_s": round(total, 2),
            "call_s": round(rec["call_s"], 2),
            "setup_s": round(rec["setup_s"], 2),
            "teardown_s": round(rec["teardown_s"], 2),
            "n_tests": len(rec["tests"]),
        }
    return {"files": files, "tallies": tallies, "wall_s": wall_s,
            "dots_passed": dots,
            "timed_s": round(sum(f["total_s"] for f in files.values()), 2)}


def render_table(report, top=None):
    files = sorted(report["files"].items(),
                   key=lambda kv: -kv[1]["total_s"])
    if top:
        files = files[:top]
    w = max([len(p) for p, _ in files] or [4])
    out = [f"{'file':<{w}}  {'total':>8}  {'call':>8}  {'setup':>8}  "
           f"{'teardn':>8}  {'tests':>5}"]
    out.append("-" * len(out[0]))
    for path, f in files:
        out.append(f"{path:<{w}}  {f['total_s']:>7.2f}s  "
                   f"{f['call_s']:>7.2f}s  {f['setup_s']:>7.2f}s  "
                   f"{f['teardown_s']:>7.2f}s  {f['n_tests']:>5}")
    out.append("-" * len(out[1]))
    t = report["tallies"]
    out.append(f"{'TOTAL':<{w}}  {report['timed_s']:>7.2f}s   "
               f"wall={report['wall_s']}s  dots={report['dots_passed']}  "
               + " ".join(f"{k}={v}" for k, v in sorted(t.items())))
    return "\n".join(out)


#: a file is only a flagged regression when it grew beyond both the
#: ratio and this absolute floor — 2x of 0.1 s is scheduler noise
_COMPARE_MIN_S = 1.0


def compare(old, new, ratio=2.0):
    """Diff two parse_log artifacts.  Returns (lines, regressed) where
    ``regressed`` is True when any per-file total grew > ``ratio``x
    (above the noise floor) or a tally got worse."""
    lines, regressed = [], False
    of, nf = old.get("files", {}), new.get("files", {})
    for path in sorted(set(of) | set(nf)):
        o, n = of.get(path), nf.get(path)
        if o is None:
            lines.append(f"NEW      {path}  {n['total_s']:.2f}s "
                         f"({n['n_tests']} tests)")
            continue
        if n is None:
            lines.append(f"VANISHED {path}  was {o['total_s']:.2f}s "
                         f"({o['n_tests']} tests)")
            continue
        os_, ns_ = o["total_s"], n["total_s"]
        if ns_ > max(os_ * ratio, _COMPARE_MIN_S):
            lines.append(f"SLOWER   {path}  {os_:.2f}s -> {ns_:.2f}s "
                         f"({ns_ / os_ if os_ else float('inf'):.1f}x)")
            regressed = True
        elif os_ > max(ns_ * ratio, _COMPARE_MIN_S):
            lines.append(f"faster   {path}  {os_:.2f}s -> {ns_:.2f}s")
    osh, nsh = old.get("shards"), new.get("shards")
    if nsh is not None and osh is not None:
        od, nd = osh.get("routing_digest"), nsh.get("routing_digest")
        if od != nd:
            # the key->shard map is part of the checkpoint contract:
            # a digest change silently orphans every saved shard state
            lines.append(f"shards   routing_digest: {od} -> {nd}")
            regressed = True
    oc, nc = old.get("compile"), new.get("compile")
    if nc is not None and oc is not None:
        os_, ns_ = oc.get("seconds_total", 0.0), nc.get("seconds_total", 0.0)
        if ns_ > max(os_ * ratio, _COMPARE_MIN_S):
            lines.append(f"compile  probe seconds_total: {os_:.2f}s -> "
                         f"{ns_:.2f}s "
                         f"({ns_ / os_ if os_ else float('inf'):.1f}x)")
            regressed = True
    osc, nsc = old.get("schema"), new.get("schema")
    if osc is not None and nsc is not None:
        osm, nsm = osc.get("samples", {}), nsc.get("samples", {})
        for fname in sorted(set(osm) & set(nsm)):
            by_app = {r.get("app"): r for r in osm[fname]}
            for row in nsm[fname]:
                o = by_app.get(row.get("app"))
                if o is None or o.get("digest") == row.get("digest"):
                    continue
                ov, nv = o.get("versions", {}), row.get("versions", {})
                bumped = any(nv.get(k) != ov.get(k)
                             for k in set(ov) | set(nv))
                lines.append(
                    f"schema   {fname}:{row.get('app')}  "
                    f"{o.get('digest')} -> {row.get('digest')}"
                    + ("" if bumped else "  (NO version bump)"))
                if not bumped:
                    # a layout change that kept every declaration version
                    # breaks old checkpoints silently — SC010 at the
                    # round-artifact level
                    regressed = True
    osel, nsel = old.get("selection"), new.get("selection")
    if osel is not None and nsel is not None:
        osm, nsm = osel.get("samples", {}), nsel.get("samples", {})
        for fname in sorted(set(osm) & set(nsm)):
            od = osm[fname].get("device", 0)
            nd = nsm[fname].get("device", 0)
            oh = osm[fname].get("host", 0)
            nh = nsm[fname].get("host", 0)
            if (od, oh) == (nd, nh):
                continue
            lines.append(f"select   {fname}  device {od} -> {nd}, "
                         f"host {oh} -> {nh}")
            if nd < od or nh > oh:
                # a query that compiled to the device selection kernel
                # last round now pays the per-emission host pass — the
                # silent-perf-regression this artifact section exists
                # to catch
                regressed = True
    onum, nnum = old.get("numeric"), new.get("numeric")
    if nnum is not None:
        # old artifacts predating the NS verifier simply count as 0
        ot = onum.get("findings_total", 0) if onum else 0
        nt = nnum.get("findings_total", 0)
        if nt != ot:
            lines.append(
                f"numeric  NS findings: {ot} -> {nt}  (codes: "
                + (",".join(sorted({c for by in
                                    nnum.get("samples", {}).values()
                                    for c in by})) or "-") + ")")
            if nt > ot:     # new numeric-safety findings are a regression
                regressed = True
    oe, ne = old.get("engine_lint"), new.get("engine_lint")
    if ne is not None:
        od = oe.get("diagnostics", 0) if oe else 0
        nd = ne.get("diagnostics", 0)
        if nd != od:
            lines.append(f"engine   diagnostics: {od} -> {nd}  "
                         f"(codes: {','.join(ne.get('codes', [])) or '-'})")
            if nd > od:     # new CE/LW findings are a regression, full stop
                regressed = True
    ot, nt = old.get("tallies", {}), new.get("tallies", {})
    for key in sorted(set(ot) | set(nt)):
        a, b = ot.get(key, 0), nt.get(key, 0)
        if a != b:
            lines.append(f"tally    {key}: {a} -> {b}")
            if key in ("failed", "error") and b > a:
                regressed = True
            if key == "passed" and b < a:
                regressed = True
    lines.append(f"timed    {old.get('timed_s')}s -> "
                 f"{new.get('timed_s')}s   wall {old.get('wall_s')}s -> "
                 f"{new.get('wall_s')}s")
    return lines, regressed


def _engine_lint_summary():
    """Snapshot of the CE/LW engine self-audit, carried in the round
    artifact so --compare flags newly-introduced findings.  Returns
    None (key still written, tolerated by compare) if the package is
    not importable from here."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from siddhi_tpu.analysis.engine import analyze_engine
        rep = analyze_engine()
    except Exception as e:
        sys.stderr.write(f"[t1_report] engine lint skipped: {e}\n")
        return None
    return {"diagnostics": len(rep.diagnostics),
            "allowlisted": len(rep.allowlisted),
            "codes": sorted({d.code for d in rep.diagnostics})}


def _shards_summary():
    """Pin the key-routing contract into the round artifact: the FNV-1a
    owner digest must never drift (it addresses per-shard checkpoint
    state), so --compare treats any change as a regression.  Same
    import/tolerance pattern as the engine lint."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from siddhi_tpu.parallel.shards import routing_digest
    except Exception as e:
        sys.stderr.write(f"[t1_report] shards summary skipped: {e}\n")
        return None
    return {"routing_digest": routing_digest()}


def _compile_summary():
    """Pin the compile-observatory health into the round artifact: one
    tiny registry-routed probe compile, reported as attributed seconds +
    persistent-cache traffic.  --compare flags a > 2x compile-seconds
    growth (above a 1 s floor) — the early-warning for 'every round got
    slower because every test recompiles more'.  Same import/tolerance
    pattern as the engine lint."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from siddhi_tpu.plan.shapes import shape_registry
        import jax.numpy as jnp
        reg = shape_registry()
        rj = reg.jit("t1.probe", {"n": 32}, lambda x: (x * 2 + 1).sum())
        rj(jnp.arange(32))
        tot = reg.totals()
    except Exception as e:
        sys.stderr.write(f"[t1_report] compile summary skipped: {e}\n")
        return None
    return {"seconds_total": round(tot["compile_seconds"], 4),
            "cache_hits": tot["cache_hits"],
            "cache_misses": tot["cache_misses"]}


def _schema_summary():
    """Pin the static persistent-state schema of every shipped sample
    into the round artifact (analysis/state_schema.py — jax-free).
    --compare flags any per-sample digest change whose declaration
    versions did NOT move: layout drift without a version bump is the
    report-level twin of the SC010 restore diagnostic.  Same
    import/tolerance pattern as the engine lint."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from siddhi_tpu.analysis.state_schema import sample_schema_digests
        samples = sample_schema_digests(os.path.join(root, "samples"))
    except Exception as e:
        sys.stderr.write(f"[t1_report] schema summary skipped: {e}\n")
        return None
    return {"samples": samples}


def _selection_summary():
    """Pin the device-selection coverage of every shipped sample into
    the round artifact (analysis/state_schema.py — jax-free): per
    sample, how many selection-active queries (having / order-by /
    limit / offset) compile to the device egress kernel vs stay on the
    host QuerySelector, with the blocking reason for each host one.
    --compare treats any device->host slide as a regression.  Same
    import/tolerance pattern as the engine lint."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from siddhi_tpu.analysis.state_schema import \
            sample_selection_coverage
        samples = sample_selection_coverage(os.path.join(root, "samples"))
    except Exception as e:
        sys.stderr.write(f"[t1_report] selection summary skipped: {e}\n")
        return None
    return {"samples": samples,
            "device_total": sum(v["device"] for v in samples.values()),
            "host_total": sum(v["host"] for v in samples.values())}


def _numeric_summary():
    """Pin the numeric-safety posture of every shipped sample into the
    round artifact (analysis/ranges.py — jax-free): warning-level NS0xx
    finding counts per sample plus the total.  --compare treats any
    growth in the total as a regression (a sample started overflowing,
    or the verifier got stricter without the samples being annotated).
    Same import/tolerance pattern as the engine lint."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from siddhi_tpu.analysis.ranges import sample_numeric_counts
        samples = sample_numeric_counts(os.path.join(root, "samples"))
    except Exception as e:
        sys.stderr.write(f"[t1_report] numeric summary skipped: {e}\n")
        return None
    return {"samples": {f: by for f, by in sorted(samples.items()) if by},
            "findings_total": sum(sum(by.values())
                                  for by in samples.values())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("log", nargs="?",
                    help="pytest log (run with --durations=0)")
    ap.add_argument("-o", "--out", help="write bench-style JSON artifact")
    ap.add_argument("--top", type=int, default=None,
                    help="only show the N slowest files in the table")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"),
                    help="diff two JSON artifacts (exit 1 on a > 2x "
                         "per-file regression or worse tallies)")
    args = ap.parse_args(argv)
    if args.compare:
        with open(args.compare[0]) as f:
            old = json.load(f)
        with open(args.compare[1]) as f:
            new = json.load(f)
        lines, regressed = compare(old, new)
        print("\n".join(lines))
        if regressed:
            sys.stderr.write("[t1_report] FAIL: regression vs "
                             f"{args.compare[0]}\n")
        return 1 if regressed else 0
    if not args.log:
        ap.error("a pytest log is required (or use --compare)")
    with open(args.log, errors="replace") as f:
        report = parse_log(f)
    if not report["files"]:
        sys.stderr.write("no --durations entries found in the log — "
                         "run pytest with --durations=0\n")
    print(render_table(report, top=args.top))
    if args.out:
        report["engine_lint"] = _engine_lint_summary()
        report["numeric"] = _numeric_summary()
        report["shards"] = _shards_summary()
        report["compile"] = _compile_summary()
        report["schema"] = _schema_summary()
        report["selection"] = _selection_summary()
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        sys.stderr.write(f"wrote {args.out}\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Builds a temporary copy of the benchmark's directories with tiny
configurations, cells and a metric dropped in as NEW files (no existing
file is edited), and loads that copy's run.py.  Shared by the tests."""
import argparse
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp, send_events=512, pool_sends=50):
    """-> (root, names of the tiny cells)."""
    root = str(tmp)
    bdir = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bdir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", ".trace*"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = []
    have = {m["name"] for m in bench["end_to_end"]}
    for name, unit, better, mode in (
            ("events_per_s", "events/s", "higher", "saturate"),
            ("match_latency_p50_ms", "ms", "lower", "paced"),
            ("match_latency_p95_ms", "ms", "lower", "paced")):
        if name not in have:    # the tiny cells of a mode need its metrics
            bench["end_to_end"].append({
                "name": name, "unit": unit, "better": better, "bound": 0.25,
                "source": "host_clock", "workloads": []})
    mode_of = {"events_per_s": "saturate", "match_latency_p50_ms": "paced",
               "match_latency_p95_ms": "paced"}
    for src, keys, extra in (("pattern_10k", 40, {}),
                             ("agg_keyed_1k", 16, {"length": 32})):
        with open(os.path.join(bdir, "configs", f"{src}.json")) as f:
            cfg = json.load(f)
        name = f"tiny_{src}"
        cfg["name"] = name
        cfg["app"] = cfg["app"].replace(f"@app:name('{src}')",
                                        f"@app:name('{name}')")
        cfg["input"]["columns"]["sym"]["count"] = keys
        cfg["keys"] = keys
        cfg["kernel"]["shape"]["keys"] = keys
        if "length" in extra:
            cfg["app"] = cfg["app"].replace("window.length(1000)",
                                            f"window.length({extra['length']})")
            cfg["reference"]["args"]["length"] = extra["length"]
            cfg["kernel"]["shape"]["length"] = extra["length"]
        _dump(cfg, os.path.join(bdir, "configs", f"{name}.json"))
        bench["configs"].append({
            "name": name, "source": cfg["source"][:200],
            "file": f"benchmark/configs/{name}.json", "reduced": ["keys"],
            "why": "tiny copy for the CPU tests"})
        for mode, rate in (("saturate", None), ("paced", 20480)):
            cell = f"{name}.{mode}"
            _dump({"name": cell, "config": name, "mode": mode,
                   "send_events": send_events, "rate": rate,
                   "event_time_rate": 5120, "pool_sends": pool_sends,
                   "warmup": {"ladder": [1, 2], "seconds": 0.2},
                   "why": "tiny", "users": "tests"},
                  os.path.join(bdir, "workloads", f"{cell}.json"))
            bench["workloads"].append({"name": cell, "config": name,
                                       "traffic": mode, "chips": 1,
                                       "why": "tiny"})
            cells.append(cell)
            for m in bench["end_to_end"]:
                if mode_of.get(m["name"]) == mode:
                    m["workloads"].append(cell)
    # a per-layer metric as a new file: publish share, read by the
    # existing ledger reader
    _dump({"reader": "ledger",
           "args": {"op": "share", "stages": ["publish"]}},
          os.path.join(bdir, "metrics", "publish_share.sat.json"))
    bench["per_layer"].append({
        "name": "publish_share.sat", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "publish",
        "moves": "events_per_s"})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root, cells


def load_run(root):
    """The copy's run.py as a module (its ROOT is the copy)."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    path = os.path.join(root, "benchmark", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_copy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def opts(workload, seed=3, seconds=0.6, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)

"""`pattern_absent_10k` on the CPU: its plain reference against its own
event-by-event loop, a tiny copy of its cell through the harness (correct;
and not correct under the lower-precision control and under three broken
timed paths), its two metric files and its counters reader.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import tiny  # noqa: E402
from compare import compare_rows, verdict  # noqa: E402
from run import load_module  # noqa: E402
from traffic import Traffic  # noqa: E402

CONFIG = "pattern_absent_10k"
TINY = "tiny_pattern_absent_10k"
CELL = f"{TINY}.saturate"
KEYS = 100          # 5.12 events per key and event-second, as the cell


def _config(keys=KEYS):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    cfg["input"]["columns"]["sym"]["count"] = keys
    return cfg


def _events(cfg, seed, sends=12):
    tr = Traffic(cfg, {"send_events": 512, "event_time_rate": 512,
                       "rate": None, "pool_sends": 12}, seed)
    tr.next_send = sends
    return tr


@pytest.mark.parametrize("seed", [1, 2, 2147483999])
def test_reference_equals_its_loop(seed):
    cfg = _config()
    tr = _events(cfg, seed)
    ref = load_module("references", cfg["reference"]["name"])
    cols, ts = tr.sent_events()
    args = cfg["reference"]["args"]
    # the deployment's thresholds leave few rows at this size: lower them
    args = dict(args, queries=[{"a_price_gt": 40.0 + 10 * q}
                               for q in range(4)])
    stats = {}
    fast = ref.run(cols, ts, args)
    slow = ref.run_loop(cols, ts, args, stats)
    checks = compare_rows(fast, slow, cfg["compare"])
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 100
    assert sum(stats["armed"]) > sum(stats["fired"]) > 0
    assert sum(stats["killed"]) > 0


def test_reference_wants_ordered_timestamps_and_the_loop_does_not():
    cfg = _config()
    ref = load_module("references", cfg["reference"]["name"])
    cols = {"sym": np.zeros(3, np.int64), "kind": np.array([0, 1, 2]),
            "price": np.array([95.0, 96.0, 0.0], np.float32)}
    ts = np.array([1000, 1100, 1050])
    with pytest.raises(ValueError):
        ref.run(cols, ts, cfg["reference"]["args"])
    # the late C (ts 1050 < d = 2100) arrives before the clock reaches d
    assert len(ref.run_loop(cols, ts, cfg["reference"]["args"])["__ts"]) == 0


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(seed):
    import ml_dtypes
    cfg = _config()
    tr = _events(cfg, seed)
    ref = load_module("references", cfg["reference"]["name"])
    cols, ts = tr.sent_events()
    args = dict(cfg["reference"]["args"],
                queries=[{"a_price_gt": 40.0 + 10 * q} for q in range(4)])
    rows = ref.run(cols, ts, args)
    low = ref.run(cols, ts, args, dtype=ml_dtypes.bfloat16)
    low = dict(low, sym=tr.key_columns["sym"][low["sym"]])
    checks = compare_rows(low, rows, cfg["compare"], tr.key_columns)
    assert checks["rows_reference"]["value"] > 100
    assert not verdict(checks), checks


# ------------------------------------------- a tiny copy through the harness

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """tiny.make_root's copy (new files only), plus a tiny copy of this
    configuration and its cell beside the two it knows."""
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("absent"))
    bdir = os.path.join(root, "benchmark")
    cfg = _config()
    cfg["name"] = TINY
    cfg["app"] = cfg["app"].replace(f"@app:name('{CONFIG}')",
                                    f"@app:name('{TINY}')")
    for q in range(4):      # thresholds that leave rows at this size
        cfg["app"] = cfg["app"].replace(f"price > {90.0 + 0.25 * q}]",
                                        f"price > {40.0 + 10 * q}]")
    cfg["reference"]["args"]["queries"] = [
        {"a_price_gt": 40.0 + 10 * q} for q in range(4)]
    cfg["keys"] = cfg["kernel"]["shape"]["keys"] = KEYS
    tiny._dump(cfg, os.path.join(bdir, "configs", f"{TINY}.json"))
    tiny._dump({"name": CELL, "config": TINY, "mode": "saturate",
                "send_events": 512, "rate": None, "event_time_rate": 512,
                "pool_sends": 50,
                "warmup": {"ladder": [1, 2], "seconds": 0.2},
                "why": "tiny", "users": "tests"},
               os.path.join(bdir, "workloads", f"{CELL}.json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": TINY, "source": cfg["source"][:200],
        "file": f"benchmark/configs/{TINY}.json", "reduced": ["keys"],
        "why": "tiny copy for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": TINY,
                               "traffic": "saturate", "chips": 1,
                               "why": "tiny"})
    for m in bench["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        if m["name"] in ("timer_share.sat", "deadline_inblock_share.sat"):
            m["workloads"].append(CELL)
    tiny._dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    # the cell's path on one chip: the gang step, not a mesh
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


def _execute(root, trace=0, system_factory=None):
    run = tiny.load_run(root)
    return run, run.execute(tiny.opts(CELL, seed=5, seconds=1.0,
                                      trace=trace),
                            require_tpu=False, system_factory=system_factory)


def test_tiny_cell_is_correct(root):
    _run, out = _execute(root)
    assert out["correct"], out["compared"]
    c = out["compared"]
    assert c["rows_reference"]["value"] >= 20
    assert c["rows_unmatched"]["value"] == 0
    assert c["rows_out_of_order"]["value"] == 0
    assert c["queries_off_device"]["value"] == 0
    assert c["events_lost"]["value"] == 0
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer",
                                   "state_forgotten"])
def test_tiny_cell_under_a_fault_is_not_correct(root, fault):
    """test_run.py's three broken timed paths: half of every batch left
    out, an answer altered where the callback receives it, the app's
    state forgotten at the window's opening barrier."""
    from test_run import _broken
    run = tiny.load_run(root)
    out = run.execute(tiny.opts(CELL, seed=5, seconds=1.0),
                      require_tpu=False, system_factory=_broken(run, fault))
    assert not out["correct"], (fault, out["compared"])
    assert out["failed"] > 0


def test_tiny_cell_under_the_control_is_not_correct(root):
    import ml_dtypes
    run = tiny.load_run(root)
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import control
    control.run = run
    cell = run.Cell(CELL)
    traffic, win, rows = run.serve_window(cell, 7, 1.0)
    prog, _ = run.judge(cell, rows, traffic, win, win["guards"])
    low = control.control_rows(cell, traffic, win, ml_dtypes.bfloat16)
    ctrl, _ = run.judge(cell, low, traffic, win, {})
    assert verdict(prog), prog
    assert prog["rows_reference"]["value"] >= 20
    assert not verdict(ctrl), ctrl


# ------------------------------------------------- metric files and reader

def test_traced_tiny_cell_reports_the_two_metrics(root):
    _run, out = _execute(root, trace=1)
    assert out["correct"], out["compared"]
    m = out["metrics"]
    assert m["timer_share.sat"]["value"] == 0.0
    assert m["deadline_inblock_share.sat"]["value"] == 100.0
    assert "step_issue_share.sat" in m and "key_pack_share.sat" in m


def test_metric_files_name_declared_spans_and_counters():
    from siddhi_tpu.core.ledger import (ABSENT_COUNTERS, SPAN_NAMES, STAGES,
                                        WAITS)
    with open(os.path.join(BENCH, "metrics", "timer_share.sat.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "ledger"
    assert set(spec["args"]["stages"]) <= \
        set(STAGES) | set(SPAN_NAMES) | set(WAITS)
    with open(os.path.join(BENCH, "metrics",
                           "deadline_inblock_share.sat.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counters"
    assert {spec["args"]["num"], spec["args"]["den"]} <= set(ABSENT_COUNTERS)


def test_counters_reader_reports_nothing_without_the_counters():
    from siddhi_tpu.core.ledger import ledger
    reader = load_module("readers", "counters")
    ctx = {"config": {"app": "@app:name('no_such_app') define stream S (a int);"}}
    args = {"op": "ratio", "num": "absent_fired_inblock_total",
            "den": "absent_fired_total"}
    assert reader.read(ctx, **args) is None         # an app without them
    assert reader.read({"config": {"app": "define stream S (a int);"}},
                       **args) is None              # an app without a name
    ledger().note_absent("counted_app", [4, 0, 0, 0, 0])
    ctx = {"config": {"app": "@app:name('counted_app')"}}
    assert reader.read(ctx, **args) is None         # nothing fired yet
    assert reader.read(ctx, op="ratio", num="no_such_total",
                       den="absent_armed_total") is None
    ledger().note_absent("counted_app", [0, 4, 3, 0, 0])
    assert reader.read(ctx, **args) == 75.0
    with pytest.raises(ValueError):
        reader.read(ctx, op="sum", num="a", den="b")

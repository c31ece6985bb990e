"""`kleene_100k` on the CPU: its plain reference against its own
event-by-event loop and the program's host engine, a tiny copy of its cell
through the harness (correct; and not correct under the lower-precision
control and under three broken timed paths), its metric files, and the
block depths its warm-up has to meet.

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

CONFIG = "kleene_100k"
TINY = "tiny_kleene_100k"
CELL = f"{TINY}.saturate"
KEYS, RATE = 125, 64    # 0.512 events per key and event-second, as the cell


def _config(keys=KEYS):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    cfg["input"]["columns"]["sym"]["count"] = keys
    return cfg


def _events(cfg, seed, sends=12):
    tr = Traffic(cfg, {"send_events": 512, "event_time_rate": RATE,
                       "rate": None, "pool_sends": 12}, seed)
    tr.next_send = sends
    return tr


REF = load_module("references", "every_kleene_then_b_within")
ARGS = _config()["reference"]["args"]


@pytest.mark.parametrize("seed", [1, 2, 2147483999])
@pytest.mark.parametrize("a_price_gt", [50.0, 85.0])
def test_reference_equals_its_loop(seed, a_price_gt):
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    args = dict(ARGS, queries=[{"a_price_gt": a_price_gt}])
    stats = {}
    fast = REF.run(cols, ts, args)
    slow = REF.run_loop(cols, ts, args, stats)
    checks = compare_rows(fast, slow, cfg["compare"])
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 30
    assert stats["opened"][0] >= stats["reached_min"][0] > 0
    assert stats["absorbed"][0] > 3 * stats["reached_min"][0]
    assert (stats["reached_max"][0] > 0) == (a_price_gt == 50.0)


def test_reference_wants_ordered_timestamps_and_the_loop_does_not():
    cols = {"sym": np.zeros(4, np.int64), "kind": np.array([0, 0, 0, 1]),
            "price": np.array([90.0, 91.0, 92.0, 95.0], np.float32)}
    ts = np.array([1000, 1100, 1300, 1200])
    with pytest.raises(ValueError):
        REF.run(cols, ts, ARGS)
    assert len(REF.run_loop(cols, ts, ARGS)["__ts"]) == 1


# Upstream's count and within cases and the reference's own numbered rules
# are planted in tests/test_kleene_partitioned.py (tier-1), which holds
# `run`, `run_loop` and both engines of the program to each expected row.


def test_reference_equals_host_engine():
    from test_references import _host_rows
    cfg = _config()
    tr = _events(cfg, 7)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    host = _host_rows(cfg, tr, tr.next_send)
    checks = compare_rows(host, rows, cfg["compare"], tr.key_columns)
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 30


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(seed):
    import ml_dtypes
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    low = REF.run(cols, ts, ARGS, dtype=ml_dtypes.bfloat16)
    low = dict(low, sym=tr.key_columns["sym"][low["sym"]])
    checks = compare_rows(low, rows, cfg["compare"], tr.key_columns)
    assert checks["rows_reference"]["value"] > 30
    assert not verdict(checks), checks


# ------------------------------------------- a tiny copy through the harness

NEW_METRICS = ("block_fill_share.sat", "kleene_forward_share.sat")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """tiny.make_root's copy (new files only), plus a tiny copy of this
    configuration and its cell beside the two it knows."""
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("kleene"))
    bdir = os.path.join(root, "benchmark")
    cfg = _config()
    cfg["name"] = TINY
    cfg["app"] = cfg["app"].replace(f"@app:name('{CONFIG}')",
                                    f"@app:name('{TINY}')") \
        .replace("@app:lanes('100000')", f"@app:lanes('{KEYS}')")
    cfg["keys"] = cfg["kernel"]["shape"]["keys"] = KEYS
    tiny._dump(cfg, os.path.join(bdir, "configs", f"{TINY}.json"))
    tiny._dump({"name": CELL, "config": TINY, "mode": "saturate",
                "send_events": 512, "rate": None, "event_time_rate": RATE,
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
        if m["name"] in NEW_METRICS:
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


# ------------------------------------------------- metric files and counters

def test_traced_tiny_cell_reports_the_two_metrics(root):
    _run, out = _execute(root, trace=1)
    assert out["correct"], out["compared"]
    m = out["metrics"]
    # 512-event sends over 128 lanes: a block is at least a quarter full
    assert 25.0 <= m["block_fill_share.sat"]["value"] <= 100.0
    # every chain reaches `min` but the ones still filling at the end
    assert 50.0 < m["kleene_forward_share.sat"]["value"] <= 100.0
    assert "step_issue_share.sat" in m and "key_pack_share.sat" in m


def test_metric_files_name_declared_counters():
    from siddhi_tpu.core.ledger import COUNT_COUNTERS, PACK_COUNTERS
    for name, declared in (("block_fill_share.sat", PACK_COUNTERS),
                           ("kleene_forward_share.sat", COUNT_COUNTERS)):
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "counters"
        assert {spec["args"]["num"], spec["args"]["den"]} <= set(declared)


# ------------------------------------------------------------ block depths

def _depth(ids, count):
    """`pack_blocks(pad_t_pow2=True)`'s T of one block of key ids."""
    return 1 << int(np.bincount(ids, minlength=count).max() - 1).bit_length()


@pytest.mark.parametrize("seed", [1, 36, 2**31 + 5])
def test_the_warm_up_meets_every_depth_a_window_can(seed):
    """The junction's worker coalesces queued sends into one block until
    it holds `batch.size.max` events, so a window's block is 1 to 8
    consecutive sends of the pool, from any send on; a batch of the
    ladder is one block whatever its size.  A depth the ladder never
    made compiles in the window (ROADMAP A3: tens of seconds at 131,072
    lanes): about 0.3% of 65,536-event blocks over 100,000 keys hold a key
    with 9 or more events (T = 16), which no 8-send batch is sure to;
    the 32-send batch of the ladder is."""
    with open(os.path.join(BENCH, "workloads",
                           f"{CONFIG}.saturate.json")) as f:
        workload = json.load(f)
    cfg = _config(keys=100000)
    traffic = Traffic(cfg, workload, seed)
    ids = traffic.ids["sym"].reshape(traffic.pool_sends, traffic.send_events)
    ids = np.concatenate([ids, ids[:8]])            # the pool wraps around
    count = cfg["input"]["columns"]["sym"]["count"]
    warm, at = set(), 0
    for k in workload["warmup"]["ladder"]:
        for _ in range(2):                          # run.LADDER_REPEATS
            warm.add(_depth(ids[at:at + k].ravel(), count))
            at += k
    assert at <= traffic.pool_sends
    window = {_depth(ids[j:j + k].ravel(), count)
              for k in range(1, 9) for j in range(0, traffic.pool_sends, 3)}
    assert window <= warm, (sorted(window), sorted(warm))
    assert {4, 8, 16} <= warm

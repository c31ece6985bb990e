"""`nexmark_q17_100k` on the CPU: its plain reference against its own
event-by-event loop and the program's host engine, a tiny copy of its cell
through the harness (correct; and not correct under the lower-precision
control and under three broken timed paths), its metric files, its kernel
family, and the block depths and row counts its warm-up has to meet.

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

CONFIG = "nexmark_q17_100k"
TINY = "tiny_nexmark_q17"
CELL = f"{TINY}.saturate"
# 0.512 events per key and event-second, as the cell
KEYS, RATE = 125, 64
NEW_METRICS = ("gagg_lane_share.sat", "gagg_fill_share.sat")


def _config(keys=KEYS):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    cfg["input"]["columns"]["sym"]["count"] = keys
    return cfg


def _events(cfg, seed, sends=24):
    tr = Traffic(cfg, {"send_events": 512, "event_time_rate": RATE,
                       "rate": None, "pool_sends": 24}, seed)
    tr.next_send = sends
    return tr


REF = load_module("references", "grouped_running_agg")
ARGS = _config()["reference"]["args"]


def test_files_load_and_agree():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = _config()
    assert conf["reduced"] == list(cfg["reduced"]) == \
        ["auction.id_distribution", "string_pools", "price.distribution"]
    assert all(len(why) > 10 for why in cfg["reduced"].values())
    assert conf["source"] == cfg["source"] and len(conf["source"]) <= 200
    cell = {w["name"]: w for w in bench["workloads"]}[f"{CONFIG}.saturate"]
    assert len(conf["why"]) <= 200 and len(cell["why"]) <= 200
    assert cell["chips"] == 1 and cell["traffic"] == "saturate"
    with open(os.path.join(BENCH, "workloads",
                           f"{CONFIG}.saturate.json")) as f:
        workload = json.load(f)
    assert workload["config"] == CONFIG and workload["rate"] is None
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert f"{CONFIG}.saturate" in e2e["events_per_s"]["workloads"]
    for name in NEW_METRICS:
        m = {m["name"]: m for m in bench["per_layer"]}[name]
        assert m["workloads"] == [f"{CONFIG}.saturate"]
        assert m["moves"] == "events_per_s"
    # q20's sixteen columns, with q17's `price long`
    with open(os.path.join(BENCH, "configs", "nexmark_q20_100k.json")) as f:
        q20 = json.load(f)
    assert list(cfg["input"]["columns"]) == \
        list(q20["input"]["columns"])
    assert cfg["input"]["columns"]["price"]["dtype"] == "int64"
    declared = cfg["app"].split("define stream S (")[1].split(")")[0]
    assert [d.split()[0] for d in declared.split(", ")] == \
        list(cfg["input"]["columns"])
    assert "price long" in declared
    # the app states what the reference's arguments state
    assert "from S[kind >= 4]" in cfg["app"] and \
        "dateTime / 86400000L as day" in cfg["app"]
    assert ARGS["where"] == [["kind", ">=", 4]]
    assert ARGS["group"] == {"sym": ["sym", 1],
                             "day": ["dateTime", 86400000]}
    for name, (lo, hi) in ARGS["ranges"].items():
        assert f"as {name}" in cfg["app"]
    assert "group by sym, day" in cfg["app"]
    # every column of a row is compared
    row = {"sym", "day", "total_bids", "rank1_bids", "rank2_bids",
           "rank3_bids", "min_price", "max_price", "avg_price",
           "sum_price"}
    assert row == (set(cfg["compare"]["exact"]) |
                   set(cfg["compare"]["float"])) - {"__q", "__ts"}
    kind = cfg["input"]["columns"]["kind"]
    assert (kind["low"], kind["high"]) == (0, 50)     # 1 : 3 : 46
    cost = load_module("kernels", cfg["kernel"]["family"]).cost(
        cfg["kernel"]["shape"], 10, 655360, 600000)
    assert cost["bytes"] > 0 and cost["flops"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2147483999])
def test_reference_equals_its_loop(seed):
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    fast = REF.run(cols, ts, ARGS)
    slow = REF.run_loop(cols, ts, ARGS)
    checks = compare_rows(fast, slow, cfg["compare"])
    assert verdict(checks), checks
    for k in fast:      # and row for row, in order
        assert (fast[k] == slow[k]).all(), k
    assert checks["rows_reference"]["value"] > 10000
    assert slow["total_bids"].max() > 50
    # all three ranks are met
    for name in ARGS["ranges"]:
        assert slow[name].max() > 0, name


def test_reference_equals_host_engine():
    from test_references import _host_rows
    cfg = _config()
    tr = _events(cfg, 7, sends=6)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    host = _host_rows(cfg, tr, tr.next_send)
    checks = compare_rows(host, rows, cfg["compare"], tr.key_columns)
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 2000


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(seed):
    import ml_dtypes
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    low = REF.run(cols, ts, ARGS, dtype=ml_dtypes.bfloat16)
    low = dict(low, **{k: t[low[k]] for k, t in tr.key_columns.items()
                       if k in low})
    checks = compare_rows(low, rows, cfg["compare"], tr.key_columns)
    assert checks["rows_reference"]["value"] > 10000
    assert not verdict(checks), checks
    # the integers the prices make are not exact at 8 bits of mantissa:
    # nearly every row fails an exact column, and the comparison reads a
    # float column only over the rows that pass them all
    assert checks["rows_unmatched"]["value"] > \
        0.9 * checks["rows_reference"]["value"]
    # row for row (both tables are in arrival order) the average is off
    # by far more than the limit this configuration brings
    err = np.abs(low["avg_price"] - rows["avg_price"]) / \
        np.maximum(np.abs(rows["avg_price"]), 1.0)
    assert err.max() > 100 * cfg["compare"]["float"]["avg_price"]


# ------------------------------------------- a tiny copy through the harness

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """tiny.make_root's copy (new files only), plus a tiny copy of this
    configuration and its cell beside the two it knows."""
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("q17"))
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
    assert c["rows_reference"]["value"] >= 100
    assert c["rows_unmatched"]["value"] == 0
    assert c["rows_out_of_order"]["value"] == 0
    assert c["queries_off_device"]["value"] == 0
    assert c["events_lost"]["value"] == 0
    assert c["relerr_avg_price"]["value"] == 0.0
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
    assert out["failed"] > 0 or fault == "altered_answer"


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
    assert prog["rows_reference"]["value"] >= 100
    assert not verdict(ctrl), ctrl


# ------------------------------------------------- metric files and counters

def test_traced_tiny_cell_reports_the_two_metrics(root):
    _run, out = _execute(root, trace=1)
    assert out["correct"], out["compared"]
    m = out["metrics"]
    # every event the report steps is on a group lane
    assert m["gagg_lane_share.sat"]["value"] == 100.0
    assert 0 < m["gagg_fill_share.sat"]["value"] <= 100.0
    # the accepted span and counter metrics read the report's stages
    for name in ("step_issue_share.sat", "key_pack_share.sat",
                 "retire_wait_share.sat", "key_intern_hit_share.sat",
                 "pack_reuse_share.sat"):
        assert name in m, name
    assert m["key_pack_share.sat"]["value"] > 0
    assert m["step_issue_share.sat"]["value"] > 0
    assert m["key_intern_hit_share.sat"]["value"] > 90.0


def test_metric_files_name_declared_counters():
    from siddhi_tpu.core.ledger import GAGG_COUNTERS
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "counters"
        assert {spec["args"]["num"], spec["args"]["den"]} <= \
            set(GAGG_COUNTERS)


# ------------------------------------------------- block depths and rows

@pytest.mark.parametrize("seed", [1, 43, 2**31 + 5])
def test_the_warm_up_meets_every_shape_a_window_can(seed):
    """The junction's worker coalesces queued sends into one block until
    it holds `batch.size.max` events, so a window's block is 1 to 8
    consecutive sends of the pool, from any send on; a batch of the
    ladder is one block whatever its size; only the bids reach the
    report, and every bid of a run falls on one day, so a block's depth
    is the most bids one auction has in it.  The step's shape is its
    depth and its rows (gagg_compiler.rows_pad: at least P x T / 8), and
    a shape the ladder never made compiles in the window."""
    from siddhi_tpu.plan.gagg_compiler import rows_pad
    with open(os.path.join(BENCH, "workloads",
                           f"{CONFIG}.saturate.json")) as f:
        workload = json.load(f)
    assert workload["warmup"]["ladder"] == [1, 2, 4, 8, 32]
    cfg = _config(keys=100000)
    traffic = Traffic(cfg, workload, seed)
    assert traffic.ids["dateTime"].max() < 86400000
    bids = traffic.ids["kind"] >= 4
    assert 0.915 < bids.mean() < 0.925
    n, m = traffic.pool_sends, traffic.send_events
    ids = np.where(bids, traffic.ids["sym"], -1).reshape(n, m)
    ids = np.concatenate([ids, ids[:8]])            # the pool wraps around
    count = cfg["input"]["columns"]["sym"]["count"]
    lanes = 1 << (count - 1).bit_length()           # @app:lanes

    def shape(block):
        block = block.ravel()
        block = block[block >= 0]
        depth = 1 << int(np.bincount(block, minlength=count).max()
                         - 1).bit_length()
        return depth, rows_pad(len(block), lanes, depth)
    warm, at = set(), 0
    for k in workload["warmup"]["ladder"]:
        for _ in range(2):                          # run.LADDER_REPEATS
            warm.add(shape(ids[at:at + k]))
            at += k
    assert at <= traffic.pool_sends
    window = {shape(ids[j:j + k]) for k in range(1, 9)
              for j in range(0, traffic.pool_sends, 3)}
    assert window <= warm, (sorted(window), sorted(warm))
    assert {d for d, _r in warm} == {4, 8, 16}

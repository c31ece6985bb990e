"""`nexmark_q20_100k` on the CPU: its plain reference against its own
event-by-event loop and the program's host engine, a tiny copy of its cell
through the harness (correct; and not correct under the lower-precision
control and under three broken timed paths), its metric files, its kernel
family, and the block depths its warm-up has to meet.

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

CONFIG = "nexmark_q20_100k"
TINY = "tiny_nexmark_q20"
CELL = f"{TINY}.saturate"
# 0.512 events per key and event-second, as the cell: 10 s hold five
# events a key, 0.06 of them a category-10 auction
KEYS, RATE = 125, 64
NEW_METRICS = ("join_probe_hit_share.sat", "join_device_share.sat")


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


REF = load_module("references", "keyed_window_join")
ARGS = _config()["reference"]["args"]


def test_files_load_and_agree():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = {c["name"]: c for c in bench["configs"]}[CONFIG]
    cfg = _config()
    # the ids' correlation and the freshness of the strings are cut, and
    # both files say so under the same names, each with its reason
    assert conf["reduced"] == list(cfg["reduced"]) == \
        ["auction.id_distribution", "string_pools"]
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
    # the app states what the reference's arguments state
    assert "category == 10" in cfg["app"] and "window.time(10 sec)" in \
        cfg["app"]
    assert ["category", "==", 10] in ARGS["right"]["where"]
    # every column the app declares is drawn, and every column of a row
    # is compared
    declared = cfg["app"].split("define stream S (")[1].split(")")[0]
    assert [d.split()[0] for d in declared.split(", ")] == \
        list(cfg["input"]["columns"])
    assert set(ARGS["out"]) == (set(cfg["compare"]["exact"]) |
                                set(cfg["compare"]["float"])) - \
        {"__q", "__ts"}
    assert len(ARGS["out"]) == 16               # q20's select, whole
    # a string column of a row bears its input column's name: the
    # harness finds its table by it
    pools = {k for k, v in cfg["input"]["columns"].items()
             if v["gen"] == "key"}
    assert len(pools) == 7 and pools <= set(ARGS["out"])
    assert all(ARGS["out"][k][1] == k for k in pools)
    assert ARGS["right"]["window_ms"] == cfg["window_ms"] == 10000
    kind = cfg["input"]["columns"]["kind"]
    assert (kind["low"], kind["high"]) == (0, 50)     # 1 : 3 : 46
    cost = load_module("kernels", cfg["kernel"]["family"]).cost(
        cfg["kernel"]["shape"], 10, 655360, 37000)
    assert cost["bytes"] > 0 and cost["flops"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2147483999])
def test_reference_equals_its_loop(seed):
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    tally = {}
    fast = REF.run(cols, ts, ARGS)
    slow = REF.run_loop(cols, ts, ARGS, tally)
    checks = compare_rows(fast, slow, cfg["compare"])
    assert verdict(checks), checks
    for k in fast:      # and row for row, in order
        assert (fast[k] == slow[k]).all(), k
    assert checks["rows_reference"]["value"] > 300
    assert tally["rows"] == len(slow["__ts"])
    assert tally["probes"] > 10 * tally["probe_hits"] > 0
    assert tally["inserted"] > tally["expired"] > 0


def test_reference_wants_ordered_timestamps():
    cols = {"sym": np.zeros(3, np.int64), "kind": np.array([1, 5, 5]),
            "category": np.array([10, 10, 10]),
            "price": np.array([90.0, 1.0, 2.0], np.float32)}
    with pytest.raises(ValueError):
        REF.run(cols, np.array([1000, 1200, 1100]), ARGS)


def test_reference_equals_host_engine():
    from test_references import _host_rows
    cfg = _config()
    tr = _events(cfg, 7)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    host = _host_rows(cfg, tr, tr.next_send)
    checks = compare_rows(host, rows, cfg["compare"], tr.key_columns)
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 300


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(seed):
    import ml_dtypes
    cfg = _config()
    tr = _events(cfg, seed)
    cols, ts = tr.sent_events()
    rows = REF.run(cols, ts, ARGS)
    low = REF.run(cols, ts, ARGS, dtype=ml_dtypes.bfloat16)
    low = dict(low, **{k: t[low[k]] for k, t in tr.key_columns.items()})
    checks = compare_rows(low, rows, cfg["compare"], tr.key_columns)
    assert checks["rows_reference"]["value"] > 300
    assert not verdict(checks), checks
    # by the one limit this configuration brings, three orders above
    # it: a lower precision does not touch the 64-bit columns
    assert checks["relerr_bid"]["value"] > \
        1000 * cfg["compare"]["float"]["bid"]
    assert checks["rows_unmatched"]["value"] == 0


# ------------------------------------------- a tiny copy through the harness

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """tiny.make_root's copy (new files only), plus a tiny copy of this
    configuration and its cell beside the two it knows."""
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("q20"))
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
    assert c["relerr_bid"]["value"] == 0.0
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
    # (an altered price fails by its relative error, not by a row)
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
    # the cell's own rates per key: 0.061 live category-10 auctions a
    # key, so 5.96% of the bids find one
    assert 4.0 < m["join_probe_hit_share.sat"]["value"] < 9.0
    assert m["join_device_share.sat"]["value"] == 100.0
    # the accepted span and counter metrics read the join's stages
    for name in ("step_issue_share.sat", "key_pack_share.sat",
                 "retire_wait_share.sat", "key_intern_hit_share.sat",
                 "pack_reuse_share.sat"):
        assert name in m, name
    assert m["key_pack_share.sat"]["value"] > 0
    assert m["step_issue_share.sat"]["value"] > 0
    assert m["key_intern_hit_share.sat"]["value"] > 90.0


def test_the_mask_probe_is_not_launched(root):
    run, out = _execute(root)
    assert out["correct"]
    from siddhi_tpu.plan.shapes import shape_registry
    kernels = shape_registry().kernels()
    assert kernels["join.keyed_step"]["calls"] > 0
    assert kernels.get("join.probe", {}).get("calls", 0) == 0


def test_metric_files_name_declared_counters():
    from siddhi_tpu.core.ledger import JOIN_COUNTERS
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "counters"
        assert {spec["args"]["num"], spec["args"]["den"]} <= \
            set(JOIN_COUNTERS)


# ------------------------------------------------------------ block depths

def _depth(ids, count):
    """`pack_blocks(pad_t_pow2=True)`'s T of one block of key ids."""
    return 1 << int(np.bincount(ids, minlength=count).max() - 1).bit_length()


@pytest.mark.parametrize("seed", [1, 38, 2**31 + 5])
def test_the_warm_up_meets_every_depth_a_window_can(seed):
    """The junction's worker coalesces queued sends into one block until
    it holds `batch.size.max` events, so a window's block is 1 to 8
    consecutive sends of the pool, from any send on; a batch of the
    ladder is one block whatever its size; and only the events that pass
    a side's filter are placed.  The step's shape is its depth (the
    egress buffer's size goes with T alone), and a depth the ladder
    never made compiles in the window."""
    with open(os.path.join(BENCH, "workloads",
                           f"{CONFIG}.saturate.json")) as f:
        workload = json.load(f)
    assert workload["warmup"]["ladder"] == [1, 2, 4, 8, 32]
    cfg = _config(keys=100000)
    traffic = Traffic(cfg, workload, seed)
    kind, category = traffic.ids["kind"], traffic.ids["category"]
    placed = (kind >= 4) | ((kind >= 1) & (kind <= 3) & (category == 10))
    assert 0.92 < placed.mean() < 0.945
    n, m = traffic.pool_sends, traffic.send_events
    ids = np.where(placed, traffic.ids["sym"], -1).reshape(n, m)
    ids = np.concatenate([ids, ids[:8]])            # the pool wraps around
    count = cfg["input"]["columns"]["sym"]["count"]

    def depth(block):
        block = block.ravel()
        return _depth(block[block >= 0], count)
    warm, at = set(), 0
    for k in workload["warmup"]["ladder"]:
        for _ in range(2):                          # run.LADDER_REPEATS
            warm.add(depth(ids[at:at + k]))
            at += k
    assert at <= traffic.pool_sends
    window = {depth(ids[j:j + k]) for k in range(1, 9)
              for j in range(0, traffic.pool_sends, 3)}
    assert window <= warm, (sorted(window), sorted(warm))
    assert warm == {4, 8, 16}

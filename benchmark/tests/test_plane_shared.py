"""`plane_shared_share.sat` on the CPU: the metric file names counters
the program declares, its entry lists the cells whose program keeps them
on the pattern path, the accepted `counters` reader reports nothing where
the program keeps neither counter (the parent of PR 39), and a tiny traced
saturate cell of 4 pattern queries over one partition reads 75: of a
chunk's 24 planes the first query makes 6 and the other three find theirs
made.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import tiny  # noqa: E402
from run import load_module  # noqa: E402

METRIC = "plane_shared_share.sat"
ARGS = {"op": "ratio", "num": "pack_planes_shared_total",
        "den": "pack_planes_total"}
CELLS = ["pattern_10k.saturate", "pattern_absent_10k.saturate",
         "kleene_100k.saturate"]


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_metric_file_names_declared_counters():
    from siddhi_tpu.core.ledger import PLANE_COUNTERS
    with open(os.path.join(BENCH, "metrics", f"{METRIC}.json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "counters", "args": ARGS}
    assert (ARGS["den"], ARGS["num"]) == PLANE_COUNTERS


def test_entry_lists_the_cells_of_the_pattern_path():
    bench = _bench()
    mine = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert mine == [{
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "host packing and query processing",
        "moves": "events_per_s", "workloads": CELLS}]
    shaped_as = [m for m in bench["per_layer"]
                 if m["name"] == "key_intern_hit_share.sat"]
    assert [dict(m, name=METRIC, workloads=CELLS) for m in shaped_as] == mine
    e2e = [m for m in bench["end_to_end"] if m["name"] == "events_per_s"]
    assert set(CELLS) <= set(e2e[0]["workloads"])
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}


def test_reader_reports_nothing_without_the_counters():
    """What the parent commit gives: its ledger has no such row, so the
    app's entry lacks both names and the line leaves the metric out."""
    from siddhi_tpu.core.ledger import ledger
    reader = load_module("readers", "counters")
    ctx = {"config": {"app": "@app:name('planes_no_such_app')"}}
    assert reader.read(ctx, **ARGS) is None
    # an app that keeps the parent's packing counters only
    ledger().note_pack("planes_other_counters", 600, 4096)
    ctx = {"config": {"app": "@app:name('planes_other_counters')"}}
    assert reader.read(ctx, **ARGS) is None
    ledger().note_planes("planes_counted", 6, 0)
    ctx = {"config": {"app": "@app:name('planes_counted')"}}
    assert reader.read(ctx, **ARGS) == 0.0      # one query: all its own
    for _ in range(3):
        ledger().note_planes("planes_counted", 6, 6)
    assert reader.read(ctx, **ARGS) == 75.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("plane_shared"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] == METRIC:     # the tiny copies of the listed cells
            m["workloads"] += ["tiny_pattern_10k.saturate",
                               "tiny_agg_keyed_1k.saturate"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


@pytest.mark.parametrize("cell,share", [
    ("tiny_pattern_10k.saturate", 75.0),
    # 8 aggregation queries pack a block each: nothing is shared there
    ("tiny_agg_keyed_1k.saturate", 0.0)])
def test_traced_tiny_cell(root, cell, share):
    run = tiny.load_run(root)
    out = run.execute(tiny.opts(cell, seed=2147483659, seconds=0.6, trace=1),
                      require_tpu=False)
    assert out["correct"], out["compared"]
    got = out["metrics"][METRIC]
    assert got["unit"] == "%" and got["value"] == share
    assert "pack_reuse_share.sat" in out["metrics"]


def test_untraced_tiny_cell_leaves_it_out(root):
    run = tiny.load_run(root)
    out = run.execute(tiny.opts("tiny_pattern_10k.saturate", seed=39),
                      require_tpu=False)
    assert out["correct"] and METRIC not in out["metrics"]

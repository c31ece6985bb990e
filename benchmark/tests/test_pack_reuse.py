"""`pack_reuse_share.sat` on the CPU: the metric file names counters the
program declares, the accepted `counters` reader reports nothing where
the program keeps none of them (the parent of PR 33), and a tiny traced
saturate cell of each pattern configuration's kind reads 3 ingests of 4
(4 queries of one partition) and 7 of 8.

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

METRIC = "pack_reuse_share.sat"
ARGS = {"op": "ratio", "num": "key_factor_reused_total",
        "den": "key_factor_total"}


def _entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench, [m for m in bench["per_layer"] if m["name"] == METRIC]


def test_metric_file_names_declared_counters():
    from siddhi_tpu.core.ledger import KEY_FACTOR_COUNTERS
    with open(os.path.join(BENCH, "metrics", f"{METRIC}.json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "counters", "args": ARGS}
    assert {ARGS["num"], ARGS["den"]} <= set(KEY_FACTOR_COUNTERS)


def test_entry_is_last_and_reported_by_every_saturate_cell():
    bench, mine = _entry()
    assert bench["per_layer"][-1]["name"] == METRIC and len(mine) == 1
    assert mine[0] == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "host packing and query processing",
        "moves": "events_per_s"}
    assert mine[0]["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}


def test_reader_reports_nothing_without_the_counters():
    """What the parent commit gives: its ledger has no such row, so the
    app's entry lacks both names and the line leaves the metric out."""
    from siddhi_tpu.core.ledger import ledger
    reader = load_module("readers", "counters")
    ctx = {"config": {"app": "@app:name('pack_no_such_app')"}}
    assert reader.read(ctx, **ARGS) is None
    # an app that keeps other counters only (a parent with absent units)
    ledger().note_absent("pack_other_counters", [4, 4, 4, 0, 0])
    ctx = {"config": {"app": "@app:name('pack_other_counters')"}}
    assert reader.read(ctx, **ARGS) is None
    ledger().note_key_factor("pack_counted", False)
    ctx = {"config": {"app": "@app:name('pack_counted')"}}
    assert reader.read(ctx, **ARGS) == 0.0      # one query: all misses
    for _ in range(3):
        ledger().note_key_factor("pack_counted", True)
    assert reader.read(ctx, **ARGS) == 75.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("pack_reuse"))
    return root


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


@pytest.mark.parametrize("cell,share", [
    ("tiny_pattern_10k.saturate", 75.0),        # 4 queries of a partition
    ("tiny_agg_keyed_1k.saturate", 87.5),       # 8
])
def test_traced_tiny_cell_reads_the_share(root, cell, share):
    run = tiny.load_run(root)
    out = run.execute(tiny.opts(cell, seed=33, seconds=0.6, trace=1),
                      require_tpu=False)
    assert out["correct"], out["compared"]
    assert out["metrics"][METRIC] == {"value": share, "unit": "%"}
    assert "key_pack_share.sat" in out["metrics"]


def test_untraced_tiny_cell_leaves_it_out(root):
    run = tiny.load_run(root)
    out = run.execute(tiny.opts("tiny_pattern_10k.saturate", seed=34),
                      require_tpu=False)
    assert out["correct"] and METRIC not in out["metrics"]

"""`key_intern_hit_share.sat` on the CPU: the metric file names counters
the program declares, every saturate cell reports the entry, the accepted
`counters` reader reports nothing where the program keeps neither counter
(the parent of PR 37), and a tiny traced saturate cell, whose warm-up has
met every key, reads 100: every event's key id came from the one dict
probe per event.

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

METRIC = "key_intern_hit_share.sat"
ARGS = {"op": "ratio", "num": "key_intern_hits_total",
        "den": "key_intern_events_total"}


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_metric_file_names_declared_counters():
    from siddhi_tpu.core.ledger import KEY_INTERN_COUNTERS
    with open(os.path.join(BENCH, "metrics", f"{METRIC}.json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "counters", "args": ARGS}
    assert (ARGS["den"], ARGS["num"]) == KEY_INTERN_COUNTERS


def test_entry_is_reported_by_every_saturate_cell():
    """No `workloads` key: every cell that reports `events_per_s` reports
    it, those that later PRs add too."""
    bench = _bench()
    mine = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert mine == [{
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "host packing and query processing",
        "moves": "events_per_s"}]
    shaped_as = [m for m in bench["per_layer"]
                 if m["name"] == "pack_reuse_share.sat"]
    assert [dict(m, name=METRIC) for m in shaped_as] == mine
    saturate = {w["name"] for w in bench["workloads"]
                if w["traffic"] == "saturate"}
    e2e = [m for m in bench["end_to_end"] if m["name"] == "events_per_s"]
    assert set(e2e[0]["workloads"]) == saturate and len(saturate) >= 3


def test_reader_reports_nothing_without_the_counters():
    """What the parent commit gives: its ledger has no such row, so the
    app's entry lacks both names and the line leaves the metric out."""
    from siddhi_tpu.core.ledger import ledger
    reader = load_module("readers", "counters")
    ctx = {"config": {"app": "@app:name('intern_no_such_app')"}}
    assert reader.read(ctx, **ARGS) is None
    # an app that keeps the parent's counters only
    ledger().note_key_factor("intern_other_counters", True)
    ctx = {"config": {"app": "@app:name('intern_other_counters')"}}
    assert reader.read(ctx, **ARGS) is None
    ledger().note_key_intern("intern_counted", 600, 0)
    ctx = {"config": {"app": "@app:name('intern_counted')"}}
    assert reader.read(ctx, **ARGS) == 0.0      # every key was new
    ledger().note_key_intern("intern_counted", 1800, 1800)
    assert reader.read(ctx, **ARGS) == 75.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root, _cells = tiny.make_root(tmp_path_factory.mktemp("key_intern"))
    return root


@pytest.fixture(autouse=True)
def _one_device(monkeypatch):
    monkeypatch.setenv("SIDDHI_TPU_MESH", "off")


@pytest.mark.parametrize("cell", ["tiny_pattern_10k.saturate",
                                  "tiny_agg_keyed_1k.saturate"])
def test_traced_tiny_cell_reads_100(root, cell):
    """The metrics are taken over the process (warm-up included), so the
    first sends' new keys count against the share: 40 and 16 keys against
    the thousands of events of even a tiny run round to 100 only after
    the warm-up has met them all, which is what the cell is for."""
    run = tiny.load_run(root)
    out = run.execute(tiny.opts(cell, seed=2147483659, seconds=0.6, trace=1),
                      require_tpu=False)
    assert out["correct"], out["compared"]
    share = out["metrics"][METRIC]
    assert share["unit"] == "%" and 99.0 < share["value"] <= 100.0
    assert "pack_reuse_share.sat" in out["metrics"]


def test_untraced_tiny_cell_leaves_it_out(root):
    run = tiny.load_run(root)
    out = run.execute(tiny.opts("tiny_pattern_10k.saturate", seed=35),
                      require_tpu=False)
    assert out["correct"] and METRIC not in out["metrics"]

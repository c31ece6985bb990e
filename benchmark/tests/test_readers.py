"""The readers and the kernels' arithmetic as plain functions, and the
trace reduction on one small trace recorded on a TPU v5e
(`recorded.xplane.pb`: agg_keyed_1k.paced, a 0.1 s window, PR 25).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from readers import generator, ledger, registry, trace  # noqa: E402
from run import load_module  # noqa: E402

RECORDED = os.path.join(HERE, "recorded.xplane.pb")


def test_union_and_short_names():
    assert trace._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [[0, 4], [5, 10]]
    assert trace._short(
        "%fusion.4 = s32[1024]{0:T(1024)S(1)} fusion(s32[4194304]{0} %b)") \
        == ("%fusion.4 fusion s32[1024]", "fusion")
    assert trace._short(
        "%while.2 = (s32[]{:T(128)}, f32[8,2]{0,1}) while((s32[]) %t)")[1] \
        == "while"
    assert trace._base("jit_gang(123)") == "jit_gang"


def test_idle_gaps_go_to_the_shortest_covering_host_span():
    busy = [[0, 10], [110, 120], [130, 140]]
    host = [(0, 200, "main:outer"), (12, 108, "main:inner"),
            (121, 122, "main:blip")]
    gaps = dict(trace._idle_gaps(busy, host))
    assert gaps["main:inner"] == pytest.approx(100e-9)
    assert gaps["main:outer"] == pytest.approx(10e-9)
    assert "main:blip" not in gaps
    assert trace._idle_gaps(busy, []) == \
        [[trace.UNNAMED, pytest.approx(110e-9)]]


def test_idle_gap_lookback_is_by_time_not_by_event_count():
    """One gang call writes ~27,000 runtime events for its upload: a span
    of the program that covered the gap before them still names it."""
    ms = 1_000_000
    busy = [[0, ms], [121 * ms, 122 * ms]]
    host = [(2 * ms, 118 * ms, "python3:siddhi/dispatch")]
    host += [(119 * ms + 50 * k, 119 * ms + 50 * k + 40,
              "pjrt-tpu-tasks:Transpose") for k in range(30_000)]
    assert trace._idle_gaps(busy, host) == \
        [["python3:siddhi/dispatch", pytest.approx(0.120)]]
    # a span that started more than LOOKBACK_NS before the gap's end is
    # not searched
    late = trace.LOOKBACK_NS + 200 * ms
    far = [[late, late + ms], [late + 101 * ms, late + 102 * ms]]
    assert trace._idle_gaps(far, [(0, late + 101 * ms, "main:stale")]) == \
        [[trace.UNNAMED, pytest.approx(0.100)]]


def test_idle_gap_that_no_span_covers_by_half_is_named_by_its_halves():
    """A paced cell's gap: the worker waits for the next send, then packs
    it; neither covers half, each covers most of one half."""
    busy = [[0, 10], [110, 120]]
    host = [(10, 52, "python3:siddhi/queue.idle"),
            (56, 104, "python3:siddhi/deliver")]
    gaps = dict(trace._idle_gaps(busy, host))
    assert gaps == {"python3:siddhi/queue.idle": pytest.approx(50e-9),
                    "python3:siddhi/deliver": pytest.approx(50e-9)}
    # cut down to an eighth of the gap and no further
    thin = dict(trace._idle_gaps([[0, 10], [810, 820]],
                                 [(10, 70, "main:a")]))
    assert thin == {trace.UNNAMED: pytest.approx(700e-9),
                    "main:a": pytest.approx(100e-9)}


def test_recorded_trace_reduces():
    red = trace.reduce_xplane(RECORDED, "^jit_full_step$")
    assert red["device_planes"] == 1
    assert red["step_modules"] == ["jit_full_step"]
    assert red["step_runs"] >= 1
    assert 0 < red["step_s"] <= red["busy_s"] * 1.001
    assert red["busy_s"] < 0.5
    ops = red["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and all(len(n) <= 80 for n, _ in ops)
    assert ops == sorted(ops, key=lambda r: -r[1])
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    none = trace.reduce_xplane(RECORDED, "^no_such_module$")
    assert none["step_s"] == 0 and none["step_runs"] == 0


def _ctx(**kw):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    with open(os.path.join(BENCH, "configs", "agg_keyed_1k.json")) as f:
        config = json.load(f)
    ctx = {"config": config, "peaks": peaks, "rows": 950_000,
           "device": {"kind": "TPU v5 lite"}, "load_module": load_module,
           "window": {"events": 1_000_000, "window_s": 2.0,
                      "ledger": {"stages_ms": {"dispatch": {"count": 16}}}},
           "trace": None}
    ctx.update(kw)
    return ctx


def test_trace_reader_returns_nothing_without_a_device_plane():
    assert trace.read(_ctx(), "step_roofline") is None
    empty = {"device_planes": 0, "step_s": 0.0, "step_runs": 0}
    assert trace.read(_ctx(trace=empty), "step_ms_per_mev") is None
    no_step = {"device_planes": 1, "step_s": 0.0, "step_runs": 0}
    assert trace.read(_ctx(trace=no_step), "step_roofline") is None


def test_roofline_counts_from_the_deployments_shapes():
    tr = {"device_planes": 1, "step_s": 0.5, "step_runs": 3}
    ctx = _ctx(trace=tr)
    assert trace.read(ctx, "step_ms_per_mev") == pytest.approx(500.0)
    shape = ctx["config"]["kernel"]["shape"]
    carry = 1000 * (1000 * 4 + 12)
    want_bytes = 2 * carry * 16 + shape["event_bytes"] * 1_000_000 + \
        shape["row_bytes"] * 950_000
    cost = load_module("kernels", "wagg_ring").cost(shape, 16, 1_000_000,
                                                    950_000)
    assert cost["bytes"] == want_bytes
    share = trace.read(ctx, "step_roofline")
    assert share == pytest.approx(100 * (want_bytes / 819e9) / 0.5)
    assert 0 < share < 100
    unknown = _ctx(trace=tr, device={"kind": "TPU v9"})
    assert trace.read(unknown, "step_roofline") is None
    nfa = load_module("kernels", "nfa_keyed").cost(
        {"queries": 2, "keys": 10, "slots": 8, "carry_bytes_per_slot": 12,
         "event_bytes": 16, "row_bytes": 12, "flops_per_event_slot": 4},
        2, 100, 5)
    assert nfa == {"bytes": 2 * 960 * 2 + 1600 + 60, "flops": 6400}


def test_ledger_generator_and_registry_readers():
    win = {"window_s": 2.0,
           "ledger": {"stage_ns": {"ingress": 1e8, "decode": 2e8,
                                   "egress_d2h": 1e8},
                      "stages_ms": {"decode": {"count": 3, "p50": 1.5,
                                               "p95": 2.0, "p99": 2.0},
                                    "egress_d2h": {"count": 3, "p50": 0.5,
                                                   "p95": 1.0, "p99": 1.0}}},
           "registry": {"compiles": 2},
           "gen": {"due": np.arange(10) * 0.01,
                   "starts": np.arange(10) * 0.01 + 0.001}}
    ctx = {"window": win}
    assert ledger.read(ctx, "share", ["ingress"]) == pytest.approx(5.0)
    assert ledger.read(ctx, "share", ["egress_d2h", "decode"]) == \
        pytest.approx(15.0)
    assert ledger.read(ctx, "percentile", ["egress_d2h", "decode"], 50) == \
        pytest.approx(2.0)
    assert ledger.read(ctx, "percentile", ["queue"], 95) is None
    assert registry.read(ctx, "delta", "compiles") == 2.0
    assert generator.read(ctx, "late_percentile", 95) == pytest.approx(1.0)
    win["gen"]["due"] = None
    assert generator.read(ctx, "late_percentile", 95) is None

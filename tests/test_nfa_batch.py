"""Fatter scan ticks (round 6): batched-vs-legacy NFA equivalence.

The ops/nfa restructuring (condition hoisting + B-event micro-batching,
gated by SIDDHI_TPU_NFA_BATCH) must be BIT-IDENTICAL in match semantics:
for every B in {1, 2, 4, 8} and every pattern family the kernel supports
(every/sequence, kleene counts, within expiry, absent deadlines, leading
min-0 kleene), randomized feeds produce identical matches, payloads and
`dropped` counters vs the B=1 legacy one-event-tick path — the same way
liveness pruning was proven in tests/test_plan_verify.py.

Plus the structural claims: the jaxpr scan length genuinely drops
T -> ceil(T/B), and the step's shape entry records scan_ticks beside B.
Runs on the conftest-forced virtual 8-device CPU mesh.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu.ops.nfa import (BATCH_ENV, DEFAULT_BATCH_B,  # noqa: E402
                                build_block_step, resolve_batch_b)
from siddhi_tpu.plan.nfa_compiler import CompiledPatternNFA  # noqa: E402

STREAM = "define stream S (price float, kind int);\n"

#: the B x shape parity grid — one app per supported pattern family
SHAPES = {
    "every_within":
        "from every e1=S[kind == 0] -> "
        "e2=S[kind == 1 and price > e1.price] within 3 sec "
        "select e1.price as p1, e2.price as p2 insert into Out;",
    "count":
        # self e[last] ref: a capture-READING condition that must stay
        # in-scan while the other conditions hoist (mixed mode); the
        # not() keeps the EMPTY chain appendable (null compares false)
        "from every e1=S[kind == 0] -> "
        "e2=S[kind == 1 and not (price < e2[last].price)]<1:3> -> "
        "e3=S[kind == 0] "
        "select e1.price as p1, e3.price as p3 insert into Out;",
    "kleene0_within":
        "from e1=S[kind == 0] -> e2=S[kind == 2]<0:3> -> "
        "e3=S[kind == 1] within 4 sec "
        "select e1.price as p1, e2.price as p2, e3.price as p3 "
        "insert into Out;",
    "absent":
        "from every e1=S[kind == 0 and price > 60.0] -> "
        "not S[kind == 1 and price > e1.price] for 2 sec "
        "select e1.price as p1 insert into Out;",
    "sequence":
        "from every e1=S[kind == 0], e2=S[kind == 1] "
        "select e1.price as p1, e2.price as p2 insert into Out;",
}


def _feed(n=220, seed=0, parts=2):
    rng = np.random.default_rng(seed)
    pids = rng.integers(0, parts, n).astype(np.int64)
    cols = {"price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 3, n).astype(np.float32)}
    ts = 1_000_000 + np.cumsum(rng.integers(0, 900, n)).astype(np.int64)
    return pids, cols, ts


def _run(nfa, feed, timer_to=None):
    pids, cols, ts = feed
    out = list(nfa.process_events(pids, cols, ts))
    dropped = [int(nfa.last_dropped_total)]
    if timer_to is not None:
        out += list(nfa.process_timer(timer_to))
        dropped.append(int(nfa.last_dropped_total))
    return out, dropped


_LEGACY_CACHE = {}


def _legacy(shape):
    """One B=1 compile per shape, shared across the B parametrization."""
    if shape not in _LEGACY_CACHE:
        _LEGACY_CACHE[shape] = CompiledPatternNFA(
            STREAM + SHAPES[shape], n_partitions=2, n_slots=4,
            mesh=None, batch_b=1)
    return _LEGACY_CACHE[shape]


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("B", [1, 2, 4, 8])
def test_batched_matches_legacy(shape, B):
    batched = CompiledPatternNFA(STREAM + SHAPES[shape], n_partitions=2,
                                 n_slots=4, mesh=None, batch_b=B)
    legacy = _legacy(shape)
    assert batched.spec.batch_b == B and legacy.spec.batch_b == 1
    timer_to = 1_000_000 + 600_000 if shape == "absent" else None
    total = 0
    for seed in (0, 1, 2):
        feed = _feed(seed=seed)
        got, gdrop = _run(batched, feed, timer_to)
        want, wdrop = _run(legacy, feed, timer_to)
        assert got == want, \
            f"{shape} B={B} seed={seed}: batched diverged " \
            f"({len(got)} vs {len(want)} matches)"
        assert gdrop == wdrop, \
            f"{shape} B={B} seed={seed}: dropped counters diverged"
        total += len(want)
        # fresh state per seed: both kernels rebuild their carries
        from siddhi_tpu.ops.nfa import make_carry
        batched.carry = batched._place_carry(
            make_carry(batched.spec, batched.n_partitions))
        batched.base_ts = None
        legacy.carry = legacy._place_carry(
            make_carry(legacy.spec, legacy.n_partitions))
        legacy.base_ts = None
    assert total > 0, f"{shape}: degenerate grid cell (0 matches)"


def test_batched_matches_legacy_on_mesh():
    """Default auto mesh = the virtual 8-device CPU mesh: the sharded
    engine step runs the same restructured kernel."""
    app = STREAM + SHAPES["every_within"]
    a = CompiledPatternNFA(app, n_partitions=8, batch_b=4)
    b = CompiledPatternNFA(app, n_partitions=8, batch_b=1)
    assert a.mesh is not None and a.mesh.devices.size == 8
    feed = _feed(n=300, parts=8)
    got, _ = _run(a, feed)
    want, _ = _run(b, feed)
    assert got == want and len(want) > 0


def _scan_lengths(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            acc.add(int(eqn.params.get("length", -1)))
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", None)
            if sub is not None:
                _scan_lengths(sub, acc)
            elif isinstance(p, (list, tuple)):
                for x in p:
                    sub = getattr(x, "jaxpr", None)
                    if sub is not None:
                        _scan_lengths(sub, acc)
    return acc


def test_jaxpr_tick_count_drops():
    """The sequential chain REALLY shrinks: with B=4 and T=10 events the
    outer scan runs ceil(10/4)=3 ticks (a fully-unrolled length-4 inner
    scan per tick); the legacy jaxpr scans all 10."""
    import jax
    nfa = CompiledPatternNFA(STREAM + SHAPES["every_within"],
                             n_partitions=2, mesh=None, batch_b=4)
    T = 10
    block = {a: np.zeros((2, T), np.float32)
             for a in nfa.spec.attr_names}
    block["__ts"] = np.arange(T, dtype=np.int32)[None].repeat(2, 0)
    block["__stream"] = np.zeros((2, T), np.int32)
    block["__valid"] = np.ones((2, T), bool)
    batched = jax.make_jaxpr(build_block_step(nfa.spec))(nfa.carry, block)
    lens = _scan_lengths(batched.jaxpr, set())
    assert 3 in lens, f"expected a ceil(T/B)=3-tick scan, got {lens}"
    assert T not in lens, f"a T={T}-tick chain survived batching: {lens}"
    legacy = jax.make_jaxpr(
        build_block_step(nfa.spec, batch_b=1))(nfa.carry, block)
    lens1 = _scan_lengths(legacy.jaxpr, set())
    assert T in lens1


def test_registry_records_scan_ticks_and_batch_b():
    from siddhi_tpu.plan.shapes import shape_registry
    reg = shape_registry()
    nfa = CompiledPatternNFA(STREAM + SHAPES["every_within"],
                             n_partitions=2, mesh=None, batch_b=4)
    entry = nfa._step.entry
    ticks0, total0 = entry.scan_ticks, reg.scan_ticks
    pids = np.zeros(10, np.int64)      # one lane -> T = 10
    cols = {"price": np.linspace(1, 99, 10).astype(np.float32),
            "kind": np.tile([0.0, 1.0], 5).astype(np.float32)}
    ts = 1_000_000 + np.arange(10, dtype=np.int64) * 100
    nfa.process_events(pids, cols, ts)
    assert entry.dims["B"] == 4
    assert entry.scan_ticks - ticks0 == -(-10 // 4)      # ceil(T/B) = 3
    assert reg.scan_ticks - total0 == 3
    assert reg.kernels()["nfa.step"]["scan_ticks"] >= 3


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv(BATCH_ENV, "1")
    assert resolve_batch_b() == 1
    nfa = CompiledPatternNFA(STREAM + SHAPES["sequence"],
                             n_partitions=2, mesh=None)
    assert nfa.batch_b == 1 and nfa.spec.batch_b == 1
    monkeypatch.delenv(BATCH_ENV)
    assert resolve_batch_b() == DEFAULT_BATCH_B
    assert resolve_batch_b(8) == 8
    monkeypatch.setenv(BATCH_ENV, "garbage")
    assert resolve_batch_b() == DEFAULT_BATCH_B


def test_cond_free_classification():
    """Capture-free conditions hoist; capture-reading ones must not."""
    nfa = CompiledPatternNFA(STREAM + SHAPES["every_within"],
                             n_partitions=2, mesh=None, batch_b=4)
    # e1: event-only -> free; e2 reads e1.price -> pinned in-scan
    assert nfa.spec.cond_free == (True, False)
    k = CompiledPatternNFA(STREAM + SHAPES["count"], n_partitions=2,
                           mesh=None, batch_b=4)
    # e2's self e[last] ref reads its own capture bank -> not free
    free = dict(zip(("e1", "e2", "e3"), k.spec.cond_free))
    assert free["e1"] and not free["e2"] and free["e3"]

"""Dispatch consolidation (round 7): stacked-vs-sequential equivalence.

The CompiledPatternBank restructuring (chunk stacking into one vmapped
super-dispatch, gated by SIDDHI_TPU_NFA_STACK; carry donation; fused
per-app egress, gated by SIDDHI_TPU_EGRESS_FUSE) must be BIT-IDENTICAL
in match semantics: randomized feeds produce identical counts, decoded
ring payloads and `dropped` counters vs the chunk-sequential legacy
path, for B in {1, 4} and through a forced grow-and-replay — the same
proof style as tests/test_nfa_batch.py.

Plus the structural claims: a C-chunk bank REALLY pays one device
dispatch per block (the shape registry's calls) from ONE compiled
executable (compile_count), the donated input carry is REALLY deleted
after the step, the stacked [C, N, ...] carry is byte-identical to C
separate chunk carries (asserted against cost_model), the default chunk
sizing matches cost_model.default_pattern_chunk, and an app with two
device query runtimes performs exactly ONE egress D2H per ingest block.
Runs on the conftest-forced virtual 8-device CPU mesh.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from siddhi_tpu.ops.nfa import (STACK_ENV, pack_blocks,  # noqa: E402
                                resolve_stack)
from siddhi_tpu.plan.nfa_compiler import CompiledPatternBank  # noqa: E402
from siddhi_tpu.plan.shapes import shape_registry  # noqa: E402

STREAM = "define stream S (partition int, price float, kind int);\n"
P = 16          # partitions
T = 12          # events per lane per block
BASE = 1_000_000
GAP = 1_000     # per-lane inter-arrival ms


def _apps(n, within_ms=9_000):
    """n structurally-identical alert patterns, thresholds as the only
    difference (parameter lanes → homogeneous chunks by construction)."""
    thrs = np.linspace(5.0, 95.0, n)
    return [STREAM +
            f"from every e1=S[kind == 0 and price > {thr}] -> "
            f"e2=S[kind == 1 and price > e1.price] "
            f"within {within_ms} milliseconds "
            "select e1.price as p1, e2.price as p2 insert into Out;"
            for thr in thrs]


def _bank(n_apps, chunk, stack, ring=8, n_slots=4, batch_b=None,
          replayable=False):
    bank = CompiledPatternBank(_apps(n_apps), n_partitions=P,
                               n_slots=n_slots, pattern_chunk=chunk,
                               ring=ring, batch_b=batch_b, stack=stack,
                               replayable=replayable)
    bank.base_ts = BASE
    return bank


def _block(rng, t0):
    """One dense [P, T] block, every lane active, globally time-ordered."""
    n = P * T
    pids = np.tile(np.arange(P, dtype=np.int64), T)
    j = np.repeat(np.arange(T, dtype=np.int64), P)
    ts = t0 + j * GAP + pids * (GAP // P)
    cols = {"partition": pids.astype(np.float32),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 2, n).astype(np.float32)}
    return pack_blocks(pids, cols, ts, np.zeros(n, np.int32), P,
                       base_ts=BASE)


def _feed(bank, seed, n_blocks=3, replayed=False):
    """Run n_blocks through the bank; → (counts [N], sorted payload rows,
    dropped)."""
    rng = np.random.default_rng(seed)
    counts = np.zeros(bank.n_patterns, np.int64)
    rows = []
    t0 = BASE
    for _ in range(n_blocks):
        block = _block(rng, t0)
        t0 += T * GAP
        out = (bank.process_block_replayed(block) if replayed
               else bank.process_block(block))
        counts += np.asarray(out[0], np.int64)
        dec = bank.decode_ring(*out[1:])
        rows.append(sorted(zip(*(np.asarray(v).tolist()
                                 for v in dec.values()))))
    return counts, rows, bank.total_dropped()


@pytest.mark.parametrize("B", [1, 4])
def test_stacked_matches_sequential(B):
    """4 patterns x chunk 2 = C=2: the one-super-dispatch bank and the
    legacy chunk loop must agree exactly on counts, decoded ring
    payloads and dropped, across randomized feeds."""
    total = 0
    for seed in (0, 1, 2):
        seq = _bank(4, 2, stack=False, batch_b=B)
        stk = _bank(4, 2, stack=True, batch_b=B)
        assert not seq.stacked and stk.stacked and stk.n_chunks == 2
        c_seq, r_seq, d_seq = _feed(seq, seed)
        c_stk, r_stk, d_stk = _feed(stk, seed)
        assert (c_seq == c_stk).all(), \
            f"B={B} seed={seed}: counts diverged {c_seq} vs {c_stk}"
        assert r_seq == r_stk, f"B={B} seed={seed}: payloads diverged"
        assert d_seq == d_stk
        total += int(c_seq.sum())
    assert total > 0, "degenerate parity grid (0 matches)"


def test_grow_and_replay_parity():
    """Forced slot overflow (K=1 ring): both paths rewind, double K and
    replay at their own granularity, and still agree exactly."""
    seq = _bank(4, 2, stack=False, n_slots=1, replayable=True)
    stk = _bank(4, 2, stack=True, n_slots=1, replayable=True)
    c_seq, r_seq, d_seq = _feed(seq, 5, replayed=True)
    c_stk, r_stk, d_stk = _feed(stk, 5, replayed=True)
    assert d_seq == 0 and d_stk == 0, "replay left evicted partials"
    assert seq.nfa.spec.n_slots > 1 and stk.nfa.spec.n_slots > 1, \
        "feed never overflowed K=1 — the replay path was not exercised"
    assert (c_seq == c_stk).all() and c_seq.sum() > 0
    assert r_seq == r_stk


def test_dispatch_count_drops_c_to_1():
    """The registry's launch count sees C device executions per block
    on the sequential path and exactly ONE on the stacked path, and the
    stacked bank compiles ONE executable for any number of blocks."""
    reg = shape_registry()
    rng = np.random.default_rng(0)
    seq = _bank(8, 2, stack=False)
    stk = _bank(8, 2, stack=True)
    assert seq.n_chunks == 4 and stk.n_chunks == 4

    def dispatches(bank, block):
        d0, k0 = reg.calls, bank._step.entry.calls
        np.asarray(bank.process_block(block)[0])
        # every launch made is a launch of the bank's own step
        assert reg.calls - d0 == bank._step.entry.calls - k0
        return reg.calls - d0

    b1, b2 = _block(rng, BASE), _block(rng, BASE + T * GAP)
    assert dispatches(seq, b1) == 4
    assert dispatches(seq, b2) == 4
    c0 = stk._step.entry.compiles
    t0 = stk._step.entry.scan_ticks
    assert dispatches(stk, b1) == 1
    assert dispatches(stk, b2) == 1
    # each launch scans its block in ceil(T/B) ticks
    assert stk._step.entry.scan_ticks - t0 == \
        2 * -(-T // stk._step.entry.dims["B"]) > 0
    # one executable covers every block of this shape: the only
    # compile is the first stacked step's
    assert stk._step.entry.compiles - c0 == 1


def test_donated_carry_is_deleted():
    """Default (non-replayable) banks donate the carry: after one step
    the INPUT buffers are deleted (XLA aliased them in place).  A
    replayable bank must NOT donate — the rewind snapshot survives."""
    rng = np.random.default_rng(1)
    stk = _bank(4, 2, stack=True)
    leaf = stk._stack_carry["slot_state"]
    stk.process_block(_block(rng, BASE))
    assert leaf.is_deleted(), \
        "stacked step did not donate its input carry"
    rep = _bank(4, 2, stack=True, replayable=True)
    leaf = rep._stack_carry["slot_state"]
    rep.process_block(_block(rng, BASE))
    assert not leaf.is_deleted(), \
        "replayable step donated the carry its rewind depends on"


def test_env_kill_switch(monkeypatch):
    monkeypatch.setenv(STACK_ENV, "0")
    assert resolve_stack() is False
    legacy = _bank(4, 2, stack=None)
    assert not legacy.stacked and legacy._carries is not None
    monkeypatch.delenv(STACK_ENV)
    assert resolve_stack() is True
    assert resolve_stack(False) is False
    on = _bank(4, 2, stack=None)
    assert on.stacked


def test_stacked_carry_bytes_identical_to_sequential():
    """[C, N, ...] holds exactly the elements of C separate [N, ...]
    carries — stacking changes dispatch count, never bytes — and the
    cost model's stacked_bank_state_bytes prices it identically."""
    from siddhi_tpu.analysis.cost_model import (bank_state_bytes,
                                                stacked_bank_state_bytes)
    from siddhi_tpu.analysis.plan_ir import automaton_ir_from_nfa
    seq = _bank(4, 2, stack=False)
    stk = _bank(4, 2, stack=True)
    seq_bytes = sum(int(v.nbytes) for c in seq._carries
                    for v in c.values())
    stk_bytes = sum(int(v.nbytes) for v in stk._stack_carry.values())
    assert stk_bytes == seq_bytes
    a = automaton_ir_from_nfa(stk.nfa, "q")
    assert stacked_bank_state_bytes(a, stk.n_chunks, stk.chunk, P) == \
        stk.n_chunks * bank_state_bytes(a, stk.chunk, P)


def test_default_chunk_matches_cost_model():
    """The bank's auto chunk sizing IS the cost model's formula — with
    the round-6 B-batching fusion growth (~3.2x per B-doubling) priced
    in, so defaults don't spill at SIDDHI_TPU_NFA_BATCH=4."""
    from siddhi_tpu.analysis import cost_model as cm
    bank = CompiledPatternBank(_apps(4), n_partitions=P, n_slots=4,
                               ring=8)        # pattern_chunk=None → auto
    spec = bank.nfa.spec
    want = cm.default_pattern_chunk(
        4, P, spec.n_slots, spec.n_rows, spec.n_caps,
        batch_b=max(bank.nfa.batch_b, 1), ring=True)
    assert bank.chunk == want
    # the growth factor really bites: at B=4 (two doublings) the modeled
    # per-pattern step footprint grows ~3.2^2 over B=1
    b1 = cm.bank_chunk_bytes_per_pattern(10_000, 8, 2, 1, batch_b=1)
    b4 = cm.bank_chunk_bytes_per_pattern(10_000, 8, 2, 1, batch_b=4)
    assert b4 == int(b1 * cm.BATCH_FUSION_GROWTH ** 2)
    # and a budget that only fits the B=1 footprint must pick a smaller
    # divisor chunk at B=4
    budget = cm.bank_chunk_bytes_per_pattern(10_000, 8, 2, 1,
                                             batch_b=1) * 200
    c1 = cm.default_pattern_chunk(1000, 10_000, 8, 2, 1, batch_b=1,
                                  budget=budget)
    c4 = cm.default_pattern_chunk(1000, 10_000, 8, 2, 1, batch_b=4,
                                  budget=budget)
    assert c4 < c1


def test_plan_ir_surfaces_stacking():
    from siddhi_tpu.analysis.plan_ir import automaton_ir_from_nfa
    stk = _bank(4, 2, stack=True)
    a = automaton_ir_from_nfa(stk.nfa, "q")
    assert a.stacked and a.dispatches_per_block == 1
    assert a.as_dict()["stacked"] is True
    seq = _bank(4, 2, stack=False)
    a2 = automaton_ir_from_nfa(seq.nfa, "q")
    assert not a2.stacked and a2.dispatches_per_block == 2


# ---------------------------------------------------------------- egress fuse

FUSE_APP = """
    @app:playback @app:pipeline('2')
    define stream S (k int, v float);
    @info(name='q1')
    from every e1=S[k == 0] -> e2=S[k == 1 and v > e1.v]
    select e1.v as a, e2.v as b insert into Out1;
    @info(name='q2')
    from every e1=S[k == 1] -> e2=S[k == 0 and v > e1.v]
    select e1.v as c, e2.v as d insert into Out2;
"""


def _run_fuse_app(n_blocks=4, block_n=48):
    from siddhi_tpu import SiddhiManager, StreamCallback
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(FUSE_APP)
    out = {"Out1": [], "Out2": []}
    for sid in out:
        rt.add_callback(sid, StreamCallback(
            lambda evs, _s=sid: out[_s].extend(
                tuple(e.data) for e in evs)))
    rt.start()
    h = rt.get_input_handler("S")
    rng = np.random.default_rng(9)
    t0 = 1_000_000
    for _ in range(n_blocks):
        h.send_batch(
            {"k": rng.integers(0, 2, block_n).astype(np.int64),
             "v": rng.uniform(0, 100, block_n).astype(np.float32)},
            timestamps=t0 + np.arange(block_n, dtype=np.int64) * 7)
        t0 += block_n * 7
    rt.flush()
    fusers = {qr.device_runtime.nfa.egress_fuser
              for qr in rt.query_runtimes.values()}
    rt.shutdown()
    return out, fusers


def test_fused_egress_one_d2h_per_block(monkeypatch):
    """An app with TWO device pattern runtimes pays exactly ONE egress
    D2H per ingest block (both runtimes' compacted buffers ride one
    slab), and decodes to the same matches as the unfused legacy path
    (SIDDHI_TPU_EGRESS_FUSE=0)."""
    n_blocks = 4
    monkeypatch.delenv("SIDDHI_TPU_EGRESS_FUSE", raising=False)
    fused_out, fusers = _run_fuse_app(n_blocks)
    assert len(fusers) == 1, "runtimes did not share the app fuser"
    fuser = fusers.pop()
    assert fuser is not None
    # every ingest block formed one group, read back with one D2H
    assert fuser.d2h_count == n_blocks, \
        f"expected {n_blocks} fused D2H reads, got {fuser.d2h_count}"

    monkeypatch.setenv("SIDDHI_TPU_EGRESS_FUSE", "0")
    legacy_out, legacy_fusers = _run_fuse_app(n_blocks)
    assert legacy_fusers == {None}
    assert sum(len(v) for v in fused_out.values()) > 0, \
        "degenerate fuse feed (0 matches)"
    for sid in fused_out:
        assert fused_out[sid] == legacy_out[sid], \
            f"{sid}: fused egress decoded different matches"


def test_app_dispatches_per_block_gauge():
    """The per-app dispatches/block gauge ticks from real ingest deltas
    and exports on /metrics."""
    from siddhi_tpu.core.ledger import ledger
    ledger().reset()
    _run_fuse_app(2)
    per_block = ledger().dispatches_per_block()
    assert per_block, "no app recorded ingest-block dispatch deltas"
    assert any(v > 0 for v in per_block.values())
    assert "siddhi_app_dispatches_per_block" in \
        "\n".join(ledger().prometheus_lines())
    assert "siddhi_kernel_dispatches_total" in \
        "\n".join(shape_registry().prometheus_lines())

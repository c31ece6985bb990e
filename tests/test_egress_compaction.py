"""The match compaction of the NFA egress (`_egress_pack_fn`'s `pack`,
which the gang step and the per-NFA egress jit both trace): the j-th
match is found by a search over prefix counts (`ops/compact.py`), where
`jnp.nonzero(size=)` scattered every mask element into `cap` bins.

Each case holds the index column to `numpy.flatnonzero` padded with -1
and the tail's count to the true count (so an overflow still re-packs at
a doubled cap); the lowering guard holds the scatter out of `pack` and
of a one-tenant gang."""
import json
import os

import jax
import numpy as np
import pytest

from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.ops.nfa import ABSENT_CTR, build_block_step, make_carry
from siddhi_tpu.plan.nfa_compiler import CompiledPatternNFA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPES = [(16, 8, 8), (100, 4, 3), (7, 1, 1), (16384, 8, 8)]
MASKS = ["empty", "first", "last", "sparse", "half", "full"]
CAPS = ["below", "equal", "above"]


def _nfa(config, lanes=16, slots=8):
    with open(os.path.join(REPO, "benchmark", "configs", config)) as f:
        app = SiddhiCompiler.parse(json.load(f)["app"])
    part = [e for e in app.execution_elements if hasattr(e, "queries")][0]
    return CompiledPatternNFA(app, n_partitions=lanes, n_slots=slots,
                              query=part.queries[0], mesh=None)


@pytest.fixture(scope="module")
def plain():
    return _nfa("pattern_10k.json")


@pytest.fixture(scope="module")
def absent():
    return _nfa("pattern_absent_10k.json")


@pytest.fixture(scope="module")
def pack(plain):
    return jax.jit(plain._egress_pack_fn(), static_argnums=8)


def _mask(shape, kind, rng):
    n = int(np.prod(shape))
    flat = np.zeros(n, bool)
    if kind == "first":
        flat[0] = True
    elif kind == "last":
        flat[-1] = True
    elif kind == "sparse":
        flat[rng.choice(n, max(2, n // 1000), replace=False)] = True
    elif kind == "half":
        flat = rng.random(n) < 0.5
    elif kind == "full":
        flat[:] = True
    return flat.reshape(shape)


def _outs(nfa, mask, rng):
    """A step's match outputs for `mask`: every cell a value of its own,
    so a row gathered from the wrong place shows."""
    P, T, K = mask.shape
    R, C = max(nfa.spec.n_rows, 1), max(nfa.spec.n_caps, 1)
    n = P * T * K
    ts = np.arange(n, dtype=np.int32).reshape(P, T, K) * 3 + 1
    enter = np.arange(n, dtype=np.int32).reshape(P, T, K) * 5 + 2
    seq = np.arange(n, dtype=np.int32).reshape(P, T, K) * 7 + 3
    caps = rng.random((P, T, K, R, C)).astype(np.float32)
    dropped = np.zeros(P, np.int32)
    dropped[::3] = 2
    return caps, ts, enter, seq, dropped


@pytest.mark.parametrize("cap_kind", CAPS)
@pytest.mark.parametrize("mask_kind", MASKS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_pack_rows_are_flatnonzero_and_the_tail_counts_truly(
        plain, pack, shape, mask_kind, cap_kind):
    rng = np.random.default_rng(len(mask_kind) * 1000 + shape[0])
    mask = _mask(shape, mask_kind, rng)
    want = np.flatnonzero(mask)
    n = len(want)
    cap = {"below": max(1, n // 2), "equal": max(1, n),
           "above": n + 5}[cap_kind]
    caps, ts, enter, seq, dropped = _outs(plain, mask, rng)
    buf = np.asarray(pack(mask, caps, ts, enter, seq, dropped, None, None,
                          cap))
    R, C = max(plain.spec.n_rows, 1), max(plain.spec.n_caps, 1)
    assert buf.shape == (cap + 1, 4 + R * C) and buf.dtype == np.int32
    idx = np.full(cap, -1, np.int64)
    idx[:min(n, cap)] = want[:cap]
    assert (buf[:cap, 0] == idx).all()
    # the tail: the true count (beyond cap too), dropped summed
    assert buf[-1, 0] == n and buf[-1, 1] == dropped.sum()
    # the rows' other columns are gathered where the index points
    safe = np.maximum(idx, 0)
    assert (buf[:cap, 1] == ts.reshape(-1)[safe]).all()
    assert (buf[:cap, 2] == enter.reshape(-1)[safe]).all()
    assert (buf[:cap, 3] == seq.reshape(-1)[safe]).all()
    assert (buf[:cap, 4:] ==
            caps.reshape(-1, R * C)[safe].view(np.int32)).all()


def test_the_counter_row_and_the_deadline_column_are_where_they_were(
        absent):
    """`has_absent` with `ctr`: [cap rows, the ABSENT_CTR row, the tail],
    the earliest waiting deadline in the tail's third column."""
    assert absent.has_absent
    rng = np.random.default_rng(35)
    P, T, K = 16, 8, 8
    mask = _mask((P, T, K), "sparse", rng)
    mask[3, 2, 1] = mask[9, 7, 0] = True
    caps, ts, enter, seq, dropped = _outs(absent, mask, rng)
    units = absent.spec.units
    a = [i for i, u in enumerate(units) if u.kind == "absent"][0]
    other = [i for i, u in enumerate(units) if u.kind != "absent"][0]
    dl_st = np.full((P, K), -1, np.int32)
    dl = np.full((P, K), 5, np.int32)
    dl_st[2, 1], dl[2, 1] = a, 7000          # waiting: counts
    dl_st[5, 0], dl[5, 0] = a, 6400          # waiting, the earliest
    dl_st[6, 3], dl[6, 3] = other, 100       # not in the absent unit
    ctr = rng.integers(0, 1000, (P, len(ABSENT_CTR))).astype(np.int32)
    cap = 64
    buf = np.asarray(jax.jit(absent._egress_pack_fn(), static_argnums=8)(
        mask, caps, ts, enter, seq, dropped, dl_st, dl, cap, ctr))
    R, C = max(absent.spec.n_rows, 1), max(absent.spec.n_caps, 1)
    want = np.flatnonzero(mask)
    assert buf.shape == (cap + 2, 4 + R * C)
    assert (buf[:len(want), 0] == want).all()
    assert (buf[len(want):cap, 0] == -1).all()
    assert (buf[-2, :len(ABSENT_CTR)] == ctr.sum(axis=0)).all()
    assert (buf[-2, len(ABSENT_CTR):] == 0).all()
    assert buf[-1, 0] == len(want) and buf[-1, 1] == dropped.sum()
    assert buf[-1, 2] == 6400


# ------------------------------- the helper's two row layouts, directly

@pytest.mark.parametrize("cap", [1, 4, 64, 4096])
@pytest.mark.parametrize("shape", [(1, 5000), (3, 7), (4096,), (2000, 3),
                                   (5000, 2, 2)],
                         ids=lambda s: "x".join(map(str, s)))
def test_compact_indices_on_few_long_rows_and_many_short_ones(shape, cap):
    """The join's probe compacts an [n, m] mask (n may be 1), a device
    window's egress [P, M]: where the mask has fewer rows than `cap` the
    helper works over the flat mask in lane-tile rows, else over the
    mask's own: the same indices either way."""
    from siddhi_tpu.ops.compact import compact_indices
    rng = np.random.default_rng(cap + len(shape))
    compact = jax.jit(compact_indices, static_argnums=1)
    for density in (0.0, 0.01, 0.6, 1.0):
        mask = rng.random(shape) < density
        want = np.flatnonzero(mask)
        idx, count = compact(mask, cap)
        full = np.full(cap, -1, np.int64)
        full[:min(len(want), cap)] = want[:cap]
        assert idx.dtype == np.int32 and idx.shape == (cap,)
        assert (np.asarray(idx) == full).all()
        assert int(count) == len(want)


# ----------------------------------------------------- the lowering guard

def _block(nfa, lanes, depth):
    block = {a: np.zeros((lanes, depth), np.float32)
             for a in nfa.attr_names}
    block.update(__ts=np.zeros((lanes, depth), np.int32),
                 __stream=np.zeros((lanes, depth), np.int32),
                 __valid=np.zeros((lanes, depth), bool))
    return block


def test_pack_lowers_without_a_scatter(plain):
    """4M mask elements scattered one after another were 78% of the gang
    step (PERF.md, PR 35): neither a jax upgrade nor a refactor may
    bring the scatter back."""
    P, T, K = 16384, 32, 8
    R, C = max(plain.spec.n_rows, 1), max(plain.spec.n_caps, 1)
    s = jax.ShapeDtypeStruct
    text = jax.jit(plain._egress_pack_fn(), static_argnums=8).lower(
        s((P, T, K), bool), s((P, T, K, R, C), np.float32),
        s((P, T, K), np.int32), s((P, T, K), np.int32),
        s((P, T, K), np.int32), s((P,), np.int32), None, None,
        1024).as_text()
    assert "gather" in text          # the rows are gathered, as before
    assert "scatter" not in text


@pytest.mark.parametrize("config", ["pattern_10k.json",
                                    "pattern_absent_10k.json"])
def test_a_one_tenant_gang_lowers_without_a_scatter(config):
    """The gang as `plan/xtenant._build_gang` builds it (the registry's
    jit of `gang` over one tenant's step and pack)."""
    from siddhi_tpu.plan.xtenant import _build_gang, _distinct_planes
    nfa = _nfa(config)
    planes, reads = _distinct_planes([_block(nfa, 16, 8)])
    gang, caps = _build_gang([nfa], reads, trigger="test")
    assert caps == [1024]
    carry = make_carry(nfa.spec, 16)
    text = gang.lower([carry], planes).as_text()
    assert "scatter" not in text


def test_the_step_alone_keeps_its_lowering(plain):
    """The guard reads the gang's text for a word: hold that the step
    itself never had it, so the guard cannot pass by the step's luck."""
    carry = make_carry(plain.spec, 16)
    text = jax.jit(build_block_step(plain.spec)).lower(
        carry, _block(plain, 16, 8)).as_text()
    assert "scatter" not in text

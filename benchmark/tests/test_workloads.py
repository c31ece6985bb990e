"""What a cell's traffic rests on, held on the CPU from its data files.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from traffic import Traffic  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def _depths(config, workload, seed):
    """Per send of the pool: the count of its fullest key's events,
    rounded up to a power of two as `pack_blocks(pad_t_pow2=True)` rounds
    a block's depth T."""
    traffic = Traffic(config, workload, seed)
    (key,) = traffic.key_columns
    ids = traffic.ids[key].reshape(traffic.pool_sends, traffic.send_events)
    count = config["input"]["columns"][key]["count"]
    fullest = np.array([np.bincount(s, minlength=count).max() for s in ids])
    return 1 << np.ceil(np.log2(fullest)).astype(int)


def test_pattern_10k_paced_sends_are_all_of_one_depth():
    """The gang step takes one of a few durations by the block's depth T
    (22.5 ms at 4, 44.3 ms at 8) and every send of this cell is a block
    of its own: with the sends split between two depths the rows leave in
    two groups and the median latency falls in one or the other by the
    seed (PERF.md 4, PR 30).  A later edit of `send_events` or of the key
    count must not put the cell back on such a boundary unseen."""
    workload = _load("workloads", "pattern_10k.paced")
    config = _load("configs", workload["config"])
    depths = np.concatenate([_depths(config, workload, seed)
                             for seed in (1, 30, 2**31 + 5)])
    values, counts = np.unique(depths, return_counts=True)
    assert counts.max() >= 0.99 * len(depths), dict(zip(values, counts))


@pytest.mark.parametrize("send_events,split", [(4096, True), (8192, False)])
def test_depth_draw_sees_a_boundary(send_events, split):
    """The draw itself, on the send size the cell left (about half of the
    sends at each of T = 4 and T = 8) and the one it took."""
    workload = dict(_load("workloads", "pattern_10k.paced"),
                    send_events=send_events, pool_sends=250)
    config = _load("configs", workload["config"])
    _values, counts = np.unique(_depths(config, workload, 7),
                                return_counts=True)
    assert (counts.max() < 0.7 * counts.sum()) == split

"""Worker for tests/test_multihost.py: one OS process of a 2-process
jax.distributed cluster (localhost DCN, 4 virtual CPU devices per
process) driving DistributedPatternBank.step_local on its own partition
range.  Writes its local match rows + global stats as JSON.

Usage: multihost_worker.py <coordinator> <num_procs> <pid> <out.json>
"""
import json
import os
import sys

# 4 virtual CPU devices: XLA_FLAGS must be set before backend init; the
# platform is forced via jax.config.update below (the parent scrubs
# JAX_PLATFORMS from this worker's environment)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from siddhi_tpu.parallel import distributed as dist  # noqa: E402

APP = """
define stream S (partition int, price float, kind int);
@info(name='q')
from every e1=S[kind == 0 and price > 50.0]
    -> e2=S[kind == 1 and price > e1.price] within 10 sec
select e1.price as p1, e2.price as p2
insert into Out;
"""

N_PARTITIONS = 16
T_PER_BLOCK = 8
N_BLOCKS = 4


def global_events(block: int):
    """Deterministic global event set — every process generates the same
    stream and keeps only the partitions it owns."""
    rng = np.random.default_rng(1234 + block)
    P, T = N_PARTITIONS, T_PER_BLOCK
    base = 1_000_000 + block * T * 1000
    cols = {"partition": np.repeat(np.arange(P), T).astype(np.float32),
            "price": rng.uniform(0, 100, P * T).astype(np.float32),
            "kind": rng.integers(0, 2, P * T).astype(np.float32)}
    ts = base + np.tile(np.arange(T, dtype=np.int64) * 500, P)
    return cols, ts


def pack_local(cols, ts, lo, hi):
    from siddhi_tpu.ops.nfa import pack_blocks
    pids = cols["partition"].astype(np.int64)
    keep = (pids >= lo) & (pids < hi)
    block = pack_blocks(
        pids[keep] - lo,
        {k: v[keep] for k, v in cols.items()},
        ts[keep], np.zeros(int(keep.sum()), np.int32),
        hi - lo, base_ts=1_000_000)
    return block


def main():
    coord, nproc, pid, out_path = sys.argv[1:5]
    ok = dist.init_distributed(coord, int(nproc), int(pid))
    assert ok and jax.process_count() == int(nproc), \
        f"distributed init failed: {jax.process_count()}"
    assert len(jax.devices()) == 4 * int(nproc), len(jax.devices())

    bank = dist.DistributedPatternBank(APP, n_partitions=N_PARTITIONS,
                                       n_slots=8)
    lo, hi = bank.local_range
    results = {"pid": int(pid), "range": [lo, hi], "blocks": []}
    for b in range(N_BLOCKS):
        cols, ts = global_events(b)
        mask, mts, stats = bank.step_local(pack_local(cols, ts, lo, hi))
        # host-local egress: only this host's partitions appear
        assert mask.shape[0] == hi - lo
        per_p = mask.sum(axis=(1, 2)).astype(int).tolist()
        results["blocks"].append({
            "local_matches": int(mask.sum()),
            "per_partition": per_p,
            "stats": stats,
        })
    with open(out_path, "w") as f:
        json.dump(results, f)


if __name__ == "__main__":
    main()

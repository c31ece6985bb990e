"""TPU pattern-bank harness (the BASELINE north-star config at reduced
default size; the measured configurations are benchmark/run.py's cells)."""
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(n_patterns=100, n_partitions=1000):
    import time

    import numpy as np

    from siddhi_tpu.ops.nfa import pack_blocks
    from siddhi_tpu.plan.nfa_compiler import CompiledPatternBank
    apps = [f"""
        define stream S (partition int, price float, kind int);
        @info(name='q')
        from every e1=S[kind == 0 and price > {thr}] -> e2=S[kind == 1 and price > e1.price]
            within 40 sec
        select e1.price as p1, e2.price as p2 insert into Out;
    """ for thr in np.linspace(5, 95, n_patterns)]
    bank = CompiledPatternBank(apps, n_partitions=n_partitions, n_slots=8,
                               pattern_chunk=n_patterns)
    rng = np.random.default_rng(0)
    t_per = 16
    n = n_partitions * t_per
    pids = np.repeat(np.arange(n_partitions), t_per)
    cols = {"partition": pids.astype(np.float32),
            "price": rng.uniform(0, 100, n).astype(np.float32),
            "kind": rng.integers(0, 2, n).astype(np.float32)}
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    block = pack_blocks(pids, cols, ts, np.zeros(n, np.int32), n_partitions,
                        base_ts=1_000_000)
    import jax
    jax.block_until_ready(bank.process_block(block))   # compile
    start = time.perf_counter()
    counts = bank.process_block(block)
    jax.block_until_ready(counts)
    elapsed = time.perf_counter() - start
    print(f"{n_patterns} NFAs x {n_partitions} partitions: "
          f"{n / elapsed:,.0f} events/sec, "
          f"matches={int(np.asarray(counts).sum())}")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main(*(int(a) for a in sys.argv[1:3]))

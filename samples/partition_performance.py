"""Partition throughput harness (reference model: performance-samples
PartitionPerformance.java — per-key partitioned sum over a value
partition)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402


def main(total=200_000, batch=10_000, n_keys=1000):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream TradeStream (symbol string, price double, volume long);
        partition with (symbol of TradeStream)
        begin
            from TradeStream select symbol, sum(volume) as total
            insert into OutputStream;
        end;
    """)
    count = [0]
    rt.add_callback("OutputStream", StreamCallback(
        lambda evs: count.__setitem__(0, count[0] + len(evs))))
    rt.start()
    h = rt.get_input_handler("TradeStream")
    rng = np.random.default_rng(0)
    keys = np.asarray([f"k{i}" for i in range(n_keys)], object)
    sent = 0
    start = time.perf_counter()
    while sent < total:
        h.send_batch({
            "symbol": keys[rng.integers(0, n_keys, batch)],
            "price": rng.uniform(0.0, 100.0, batch),
            "volume": rng.integers(1, 10, batch)})
        sent += batch
    elapsed = time.perf_counter() - start
    rt.shutdown()
    print(f"partitioned ({n_keys} keys): {sent / elapsed:,.0f} events/sec "
          f"({count[0]:,} outputs, {elapsed:.2f}s)")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main()

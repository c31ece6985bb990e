"""@Async-junction filter throughput harness (reference model:
performance-samples SimpleFilterSingleQueryWithDisruptorPerformance.java —
the disruptor ring becomes the @Async queue+worker re-batching junction,
stream/StreamJunction.java:280-316)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402


def main(total=1_000_000, batch=10_000):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @Async(buffer.size='1024', workers='2', batch.size.max='4096')
        define stream cseEventStream (symbol string, price float, volume long);
        from cseEventStream[volume < 150]
        select symbol, price insert into outputStream;
    """)
    count = [0]
    rt.add_callback("outputStream", StreamCallback(
        lambda evs: count.__setitem__(0, count[0] + len(evs))))
    rt.start()
    h = rt.get_input_handler("cseEventStream")
    rng = np.random.default_rng(0)
    sent = 0
    start = time.perf_counter()
    while sent < total:
        h.send_batch({
            "symbol": np.full(batch, "WSO2", object),
            "price": rng.uniform(0.0, 100.0, batch).astype(np.float32),
            "volume": rng.integers(0, 300, batch)})
        sent += batch
    rt.shutdown()      # drains the async queue
    elapsed = time.perf_counter() - start
    print(f"@Async: {sent / elapsed:,.0f} events/sec "
          f"({count[0]:,} matches, {elapsed:.2f}s)")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main()

"""Filter-throughput harness (reference model:
siddhi-samples/performance-samples SimpleFilterSingleQueryPerformance.java —
prints events/sec + avg latency per 1M events, host path)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402


def main(total=1_000_000, batch=10_000):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream cseEventStream (symbol string, price float, volume long);
        from cseEventStream[volume < 150]
        select symbol, price insert into outputStream;
    """)
    count = [0]
    rt.add_callback("outputStream",
                    StreamCallback(lambda evs: count.__setitem__(
                        0, count[0] + len(evs))))
    rt.start()
    h = rt.get_input_handler("cseEventStream")
    rng = np.random.default_rng(0)
    sent = 0
    start = time.perf_counter()
    while sent < total:
        n = min(batch, total - sent)
        h.send_batch({
            "symbol": np.asarray(["WSO2"] * n, object),
            "price": rng.uniform(40, 80, n).astype(np.float32),
            "volume": rng.integers(50, 250, n).astype(np.int64)})
        sent += n
    elapsed = time.perf_counter() - start
    rt.shutdown()
    print(f"sent={sent} matched={count[0]} "
          f"throughput={sent / elapsed:,.0f} events/sec "
          f"avg_batch_latency={elapsed / (sent / batch) * 1000:.2f} ms")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000)

"""Incremental-aggregation ingest harness: host bucket cascade vs the
device slab segment-reduction path (ops/incremental_agg.py; reference
model: aggregation/IncrementalExecutor.java ingest)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from siddhi_tpu import SiddhiManager  # noqa: E402

APP = """
define stream TradeStream (symbol string, price double, volume long, ts long);
define aggregation TradeAgg
from TradeStream
select symbol, avg(price) as avgPrice, sum(price) as total, count() as n
group by symbol
aggregate by ts every sec ... hour;
"""


def run(engine, total=200_000, batch=20_000, n_keys=50):
    prefix = f"@app:engine('{engine}') " if engine else ""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(prefix + APP)
    rt.start()
    h = rt.get_input_handler("TradeStream")
    rng = np.random.default_rng(0)
    keys = np.asarray([f"k{i}" for i in range(n_keys)], object)
    base = 1_496_289_950_000
    sent = 0
    start = time.perf_counter()
    while sent < total:
        h.send_batch({
            "symbol": keys[rng.integers(0, n_keys, batch)],
            "price": rng.uniform(1.0, 100.0, batch),
            "volume": rng.integers(1, 10, batch),
            "ts": base + rng.integers(0, 3_600_000, batch)})
        sent += batch
    # materialise one query so lazy device sync is inside the clock
    rt.query("from TradeAgg within 1496289000000, 1496296000000 "
             "per 'seconds' select AGG_TIMESTAMP, symbol, total")
    elapsed = time.perf_counter() - start
    rt.shutdown()
    label = engine or "device(auto)"
    print(f"{label:12s}: {sent / elapsed:,.0f} events/sec ({elapsed:.2f}s)")
    return sent / elapsed


def main():
    host = run("host")
    dev = run(None)
    print(f"device speedup: {dev / host:.1f}x")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main()

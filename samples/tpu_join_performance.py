"""Device join-probe throughput: a range-condition stream-table join
(10k-row table — no hash path exists for `>` conditions) through the
public API, device probe vs forced-host numpy mask.

The probe is the reference JoinProcessor's per-event find() hot loop
(JoinProcessor.java:36-122); here each arriving chunk evaluates the
on-condition as one [chunk, table] broadcast program on the device
(core/join.py JoinRuntime._device_mask).
"""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


APP = """
define stream L (id int, price float);
define table T (tid int, threshold float, band int);
define stream Fill (tid int, threshold float, band int);
from Fill insert into T;
@info(name='q')
from L join T on L.price > T.threshold and T.band == 3
select L.id as lid, T.tid as tid
insert into Out;
"""

N_TABLE = 10_000
CHUNK = 16_384
CHUNKS = 4


def run(engine):
    from siddhi_tpu import SiddhiManager, StreamCallback
    m = SiddhiManager()
    prefix = f"@app:engine('{engine}') " if engine else ""
    rt = m.create_siddhi_app_runtime("@app:playback " + prefix + APP)
    matched = [0]
    rt.add_callback("Out", StreamCallback(
        lambda evs: matched.__setitem__(0, matched[0] + len(evs))))
    rt.start()
    rng = np.random.default_rng(0)
    rt.get_input_handler("Fill").send_batch(
        {"tid": np.arange(N_TABLE, dtype=np.int64),
         # high thresholds keep the match count (and host emission cost)
         # small so the measured difference is the PROBE, not the emit
         "threshold": rng.uniform(99, 100, N_TABLE).astype(np.float32),
         "band": rng.integers(0, 8, N_TABLE).astype(np.int64)},
        timestamps=np.full(N_TABLE, 1_000_000, np.int64))
    h = rt.get_input_handler("L")
    qr = rt.query_runtimes["q"]
    backend = qr.backend
    # warmup at the MEASURED chunk shape (device: jit compile at
    # [CHUNK, N_TABLE] + the compaction-cap growth retrace)
    for _ in range(2):
        h.send_batch(
            {"id": np.arange(CHUNK, dtype=np.int64),
             "price": rng.uniform(0, 100, CHUNK).astype(np.float32)},
            timestamps=np.full(CHUNK, 1_001_000, np.int64))
    matched[0] = 0
    t0 = time.perf_counter()
    total = 0
    for ci in range(CHUNKS):
        n = CHUNK
        h.send_batch(
            {"id": np.arange(n, dtype=np.int64),
             "price": rng.uniform(0, 100, n).astype(np.float32)},
            timestamps=np.full(n, 1_002_000 + ci, np.int64))
        total += n
    dt = time.perf_counter() - t0
    rt.shutdown()
    return backend, total / dt, matched[0]


def main():
    b_dev, rate_dev, m_dev = run(None)
    b_host, rate_host, m_host = run("host")
    assert b_dev == "device" and b_host == "host", (b_dev, b_host)
    assert m_dev == m_host, (m_dev, m_host)
    print(f"table rows:        {N_TABLE}")
    print(f"probe pairs/chunk: {CHUNK * N_TABLE:,}")
    print(f"device probe:      {rate_dev:,.0f} events/s "
          f"({rate_dev * N_TABLE / 1e9:.2f}B pairs/s)")
    print(f"host numpy mask:   {rate_host:,.0f} events/s "
          f"({rate_host * N_TABLE / 1e9:.2f}B pairs/s)")
    print(f"speedup:           {rate_dev / rate_host:.2f}x "
          f"(matches identical: {m_dev})")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main()

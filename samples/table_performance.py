"""Table-probe throughput harness (reference model: performance-samples
NoIndexingTablePerformance.java:80-180 — stream-table join probes), run
twice: full-scan table vs @Index'd table to show the index-plan speedup
(util/parser/CollectionExpressionParser.java role)."""
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402


def run(indexed: bool, table_rows=20_000, probes=2_000):
    ann = "@Index('symbol')" if indexed else ""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(f"""
        define stream FillStream (symbol string, volume long);
        define stream ProbeStream (symbol string);
        {ann}
        define table StockTable (symbol string, volume long);
        from FillStream insert into StockTable;
        from ProbeStream join StockTable
            on StockTable.symbol == ProbeStream.symbol
        select StockTable.symbol, StockTable.volume
        insert into OutputStream;
    """)
    count = [0]
    rt.add_callback("OutputStream", StreamCallback(
        lambda evs: count.__setitem__(0, count[0] + len(evs))))
    rt.start()
    rng = np.random.default_rng(0)
    syms = np.asarray([f"s{i}" for i in range(table_rows)], object)
    rt.get_input_handler("FillStream").send_batch(
        {"symbol": syms, "volume": rng.integers(1, 100, table_rows)})
    probe = rt.get_input_handler("ProbeStream")
    start = time.perf_counter()
    probe.send_batch({"symbol": syms[rng.integers(0, table_rows, probes)]})
    elapsed = time.perf_counter() - start
    rt.shutdown()
    label = "indexed" if indexed else "full-scan"
    print(f"{label:9s}: {probes / elapsed:,.0f} probes/sec over "
          f"{table_rows:,} rows ({count[0]:,} hits)")
    return probes / elapsed


def main():
    scan = run(indexed=False)
    idx = run(indexed=True)
    print(f"index speedup: {idx / scan:.1f}x")


if __name__ == "__main__":
    from siddhi_tpu.core.profiling import device_info
    print(f"device: {device_info()}")   # no number without it
    main()

"""Worker for tests/test_shapes.py::test_compile_cache_survives_process_
restart: one process that builds a one-filter app, sends one block and
prints, as one JSON line, its matches' digest and what the shape registry
saw (signatures, compiles, persistent-cache hits and misses).  The parent
prepares the cache through the environment (JAX_COMPILATION_CACHE_DIR,
JAX_ENABLE_COMPILATION_CACHE).

Usage: coldstart_worker.py
"""
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

APP = ("@app:name('cstiny') "
       "define stream S (sym string, price float, vol int); "
       "@info(name='q') from S[price > 1 and vol > 0] "
       "select sym, price insert into Out;")
N = 64


def main() -> dict:
    # Cache config must precede the first jax computation of the process
    # (jax latches the cache decision at first compile) — configure from
    # the lightweight shapes module before the heavy engine import.
    from siddhi_tpu.plan.shapes import (configure_compile_cache,
                                        shape_registry)
    configure_compile_cache()
    from siddhi_tpu import SiddhiManager, StreamCallback
    rt = SiddhiManager().create_siddhi_app_runtime(APP)
    got: list = []
    rt.add_callback("Out", StreamCallback(
        lambda evs: got.extend(tuple(getattr(e, "data", e)) for e in evs)))
    rt.start()
    # the exact same event stream in every worker: the digest is compared
    # across cache-on / cache-off processes
    rt.get_input_handler("S").send_batch(
        {"sym": np.asarray(["A", "B"] * (N // 2), object),
         "price": 11.0 + np.arange(N, dtype=np.float64),
         "vol": np.ones(N, np.int64)},
        timestamps=1_000_000 + np.arange(N, dtype=np.int64))
    rt.flush()
    rt.shutdown()
    snap = shape_registry().snapshot()
    tot = snap["totals"]
    return {"matches": len(got),
            "digest": hashlib.sha1(repr(got).encode()).hexdigest()[:16],
            "signatures": [e["signature"] for e in snap["entries"]
                           if e["kind"] != "other"],
            "compiles": tot["compiles"],
            "cache_hits": tot["cache_hits"],
            "cache_misses": tot["cache_misses"],
            "cache": snap["cache"]}


if __name__ == "__main__":
    print(json.dumps(main()))

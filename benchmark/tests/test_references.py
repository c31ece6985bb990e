"""The plain references against their own event-by-event loops and
against the program's host engine (`@app:engine('host')`), a few thousand
seeded events per configuration; and the lower-precision control.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from compare import compare_rows, verdict  # noqa: E402
from run import load_module  # noqa: E402
from traffic import Traffic  # noqa: E402


def _config(name, keys):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["input"]["columns"]["sym"]["count"] = keys
    return cfg


def _events(cfg, seed, sends=8, send_events=512, rate=5120):
    tr = Traffic(cfg, {"send_events": send_events, "event_time_rate": rate,
                       "rate": None, "pool_sends": 10}, seed)
    tr.next_send = sends
    return tr


def _host_rows(cfg, tr, sends):
    """The same events through the program's host engine, synchronously
    (under @Async the sender runs the playback clock ahead of the
    worker)."""
    from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
    app = re.sub(r"@Async\([^)]*\)", "", cfg["app"])
    rt = SiddhiManager().create_siddhi_app_runtime(
        f"@app:engine('host') {app}")
    got = []
    for q, stream in enumerate(cfg["output"]["streams"]):
        rt.add_callback(stream, ColumnarStreamCallback(
            lambda ch, q=q: got.append((np.array(ch.timestamps),
                                        {k: np.array(v)
                                         for k, v in ch.columns.items()},
                                        q))))
    rt.start()
    h = rt.get_input_handler("S")
    for j in range(sends):
        cols, ts = tr.batch(j)
        h.send_batch(cols, timestamps=ts)
    rt.flush()
    rt.shutdown()
    out = {"__ts": np.concatenate([g[0] for g in got]),
           "__q": np.concatenate([np.full(len(g[0]), g[2], np.int64)
                                  for g in got])}
    for k in got[0][1]:
        out[k] = np.concatenate([g[1][k] for g in got])
    return out


CASES = [("pattern_10k", 40), ("agg_keyed_1k", 12)]


@pytest.mark.parametrize("name,keys", CASES)
@pytest.mark.parametrize("seed", [1, 2147483999])
def test_reference_equals_its_loop(name, keys, seed):
    cfg = _config(name, keys)
    tr = _events(cfg, seed)
    ref = load_module("references", cfg["reference"]["name"])
    cols, ts = tr.sent_events()
    fast = ref.run(cols, ts, cfg["reference"]["args"])
    slow = ref.run_loop(cols, ts, cfg["reference"]["args"])
    spec = dict(cfg["compare"], float={c: 1e-9
                                       for c in cfg["compare"]["float"]})
    checks = compare_rows(fast, slow, spec)
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 100


@pytest.mark.parametrize("name,keys", CASES)
def test_reference_equals_host_engine(name, keys):
    cfg = _config(name, keys)
    tr = _events(cfg, 7)
    ref = load_module("references", cfg["reference"]["name"])
    cols, ts = tr.sent_events()
    rows = ref.run(cols, ts, cfg["reference"]["args"])
    host = _host_rows(cfg, tr, tr.next_send)
    checks = compare_rows(host, rows, cfg["compare"], tr.key_columns)
    assert verdict(checks), checks
    assert checks["rows_reference"]["value"] > 100


@pytest.mark.parametrize("name,keys", CASES)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_is_not_correct(name, keys, seed):
    """The reference in bfloat16, put in the program's place, has to fail
    the comparison (run at the cells' own sizes by control.py on the
    chip; here at a size a test run can hold)."""
    import ml_dtypes
    cfg = _config(name, keys)
    tr = _events(cfg, seed, sends=16)
    ref = load_module("references", cfg["reference"]["name"])
    cols, ts = tr.sent_events()
    rows = ref.run(cols, ts, cfg["reference"]["args"])
    low = ref.run(cols, ts, cfg["reference"]["args"],
                  dtype=ml_dtypes.bfloat16)
    if "sym" in low:        # the program's place: key strings, as served
        low = dict(low, sym=tr.key_columns["sym"][low["sym"]])
    checks = compare_rows(low, rows, cfg["compare"], tr.key_columns)
    assert not verdict(checks), checks

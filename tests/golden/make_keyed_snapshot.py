"""Writes tests/golden/keyed_snapshot_pr32.pkl.gz, the golden of
tests/test_key_factor.py: a snapshot written by the tree BEFORE PR 33
(`ab77623`), with the input it was given and what that tree made of it
(lane maps, dictionaries, rows before and after the snapshot).

    git archive ab77623 | tar -x -C /tmp/parent
    JAX_PLATFORMS=cpu python tests/golden/make_keyed_snapshot.py \
        /tmp/parent tests/golden/keyed_snapshot_pr32.pkl.gz

Not a test and not collected; kept so the golden can be read for what it
is.  Only this repository's own trees write the pickle."""
import functools
import gzip
import os
import pickle
import sys

os.environ["SIDDHI_TPU_MESH"] = "off"
sys.path.insert(0, sys.argv[1])
import numpy as np
from siddhi_tpu import ColumnarStreamCallback, SiddhiManager

APP = """@app:name('kf_golden') @app:playback
define stream S (sym string, price float, kind int);
partition with (sym of S) begin
@info(name='q0')
from every e1=S[kind == 0 and price > 40.0] -> e2=S[kind == 1 and price > e1.price] within 1 sec
select e1.sym as sym, e1.price as p1, e2.price as p2 insert into Out0;
@info(name='q1')
from every e1=S[kind == 0 and price > 45.0] -> e2=S[kind == 1 and price > e1.price] within 1 sec
select e1.sym as sym, e1.price as p1, e2.price as p2 insert into Out1;
@info(name='q2')
from S[price > 50.0]#window.length(5)
select sym, sum(price) as p1, count() as p2 group by sym insert into Out2;
@info(name='q3')
from S[price > 55.0]#window.length(5)
select sym, sum(price) as p1, count() as p2 group by sym insert into Out3;
end;
"""
N, CUT, SNAP_AT = 2400, 600, 1200
rng = np.random.default_rng(3301)
names = np.asarray([f"k{i}" for i in range(48)], object)
# the later half meets keys the first never saw
ids = np.concatenate([rng.integers(0, 30, SNAP_AT), rng.integers(0, 48, N - SNAP_AT)])
cols = {"sym": names[ids], "price": rng.uniform(0, 100, N).astype(np.float32),
        "kind": rng.integers(0, 2, N)}
ts = 1_000_000 + (np.arange(N) * 1000) // 400

rt = SiddhiManager().create_siddhi_app_runtime(APP)
rows = []
def recv(q, chunk):
    c = chunk.columns
    for j, t in enumerate(chunk.timestamps):
        rows.append((q, str(c["sym"][j]), int(t), float(c["p1"][j]), float(c["p2"][j])))
for q in range(4):
    rt.add_callback(f"Out{q}", ColumnarStreamCallback(functools.partial(recv, q)))
rt.start()
h = rt.get_input_handler("S")
def lanes():
    out = {}
    for pr in rt.partition_runtimes:
        assert pr.device_mode
        for name, qr in pr.device_query_runtimes.items():
            out[name] = dict(qr.device_runtime.key_lanes)
    return out
snap = None
for i in range(0, N, CUT):
    if i == SNAP_AT:
        rt.flush()
        snap = rt.snapshot()
        lanes_at_snap = lanes()
        n_rows_at_snap = len(rows)
    sl = slice(i, i + CUT)
    h.send_batch({k: v[sl] for k, v in cols.items()}, timestamps=ts[sl])
rt.flush()
decoders = {name: list(qr.device_runtime.nfa.str_decoder)
            for pr in rt.partition_runtimes
            for name, qr in pr.device_query_runtimes.items()
            if hasattr(qr.device_runtime, "nfa")}
gold = {"app": APP, "cols": cols, "ts": ts, "cut": CUT, "snap_at": SNAP_AT,
        "snapshot": snap, "lanes_at_snap": lanes_at_snap, "lanes": lanes(),
        "decoders": decoders,
        "rows_before": sorted(rows[:n_rows_at_snap]),
        "rows_after": sorted(rows[n_rows_at_snap:])}
rt.shutdown()
with gzip.open(sys.argv[2], "wb") as f:
    pickle.dump(gold, f, protocol=4)
print(len(snap), len(rows), n_rows_at_snap, os.path.getsize(sys.argv[2]),
      decoders["q0"][:6])

"""The system under test, as the benchmark sees it: everything here goes
through the entry points a user calls.  SiddhiManager builds the app,
InputHandler.send_batch feeds it, a ColumnarStreamCallback receives its
rows.  Besides that the benchmark reads the program's spans and counters
(readers/) and asks each query where it runs.
"""
import time

import numpy as np


class Served:
    """One running app with a collecting callback on each of its output
    streams (one per query: a row's `__q` is its stream's index)."""

    def __init__(self, config):
        from siddhi_tpu import ColumnarStreamCallback, SiddhiManager
        self.config = config
        # (receipt time, stream index, timestamps, {column: array})
        self.chunks = []
        self.rt = SiddhiManager().create_siddhi_app_runtime(config["app"])
        for q, stream in enumerate(config["output"]["streams"]):
            self.rt.add_callback(stream, ColumnarStreamCallback(
                lambda chunk, q=q: self._receive(q, chunk)))
        self.rt.start()
        self.handler = self.rt.get_input_handler(config["input"]["stream"])
        # the benchmark's own two calls, on the profiler's clock in a
        # traced run (a no-op costing well under a microsecond otherwise)
        from jax.profiler import TraceAnnotation
        self._span = TraceAnnotation

    def _receive(self, q, chunk):
        # the engine hands over fresh arrays per delivery; copying them
        # here keeps the rows whatever it does with its buffers later
        with self._span("bench.callback"):
            self.chunks.append((time.perf_counter(), q,
                                np.array(chunk.timestamps),
                                {k: np.array(v)
                                 for k, v in chunk.columns.items()}))

    def send(self, cols, ts):
        with self._span("bench.send_batch"):
            self.handler.send_batch(cols, timestamps=ts)

    def flush(self):
        """Every row for events already sent has been delivered."""
        self.rt.flush()

    def shutdown(self):
        self.rt.shutdown()

    # ----------------------------------------------------------- guards

    def backends(self):
        """{query: (backend, reason, selection backend)}; a partition that
        fell back to host clones reports host."""
        rt = self.rt
        qrs = dict(rt.query_runtimes)
        out = {}
        for pr in rt.partition_runtimes:
            if pr.device_mode:
                qrs.update(pr.device_query_runtimes)
            else:
                out[pr.name] = ("host", pr.fallback_reason, None)
        for name, qr in qrs.items():
            sel = qr.selection_route
            out[name] = (qr.backend, qr.backend_reason,
                         sel["backend"] if sel else None)
        return out

    def not_on_device(self):
        return {q: v for q, v in self.backends().items()
                if v[0] != "device" or v[2] == "host"}

    def lost_events(self):
        """Events shed, overflowed or dropped, by counter."""
        lost = {}
        m = self.rt.ingest_metrics
        for name in ("ingest_shed_total", "ingest_overflow_total"):
            total = sum(getattr(m, name).series().values())
            if total:
                lost[name] = total
        qrs = dict(self.rt.query_runtimes)
        for pr in self.rt.partition_runtimes:
            if pr.device_mode:
                qrs.update(pr.device_query_runtimes)
        for name, qr in qrs.items():
            dr = getattr(qr, "device_runtime", None)
            nfa = getattr(dr, "nfa", None)
            dropped = int(getattr(nfa, "last_dropped_total", 0) or 0)
            if dropped:
                lost[f"{name}.dropped"] = dropped
        return lost


def table(chunks):
    """Delivered chunks -> one column table, in delivery order, with
    __ts, the index of each row's output stream (__q) and the receipt
    time of its chunk (__t_recv)."""
    if not chunks:
        return {"__ts": np.empty(0, np.int64), "__q": np.empty(0, np.int64),
                "__t_recv": np.empty(0, np.float64)}
    out = {"__ts": np.concatenate([c[2] for c in chunks]),
           "__q": np.concatenate(
               [np.full(len(c[2]), c[1], np.int64) for c in chunks]),
           "__t_recv": np.concatenate(
               [np.full(len(c[2]), c[0]) for c in chunks])}
    for name in chunks[0][3]:
        out[name] = np.concatenate([c[3][name] for c in chunks])
    return out

"""The one traffic generator: data from the seed, pacing from a data file.

A configuration's `input` says what a stream's columns hold; a workload
file says how they are sent: `send_events` per send, `event_time_rate`
(events per second of synthetic event time), `rate` (events per second of
wall time offered open-loop, or null for back-to-back sends held only by
the system's back-pressure), and the warm-up.  Every send's arrays exist
before the window opens: the generator thread does no RNG work and no key
gather inside it, only an integer add on a timestamp column when the pool
of sends wraps around.
"""
import threading
import time

import numpy as np


def _column(spec, rng, n):
    gen = spec["gen"]
    if gen == "key":
        if spec.get("skew", "uniform") != "uniform":
            raise ValueError(f"key skew {spec['skew']!r} is not implemented")
        return rng.integers(0, spec["count"], n)
    if gen == "uniform":
        return rng.uniform(spec["low"], spec["high"], n).astype(spec["dtype"])
    if gen == "integers":
        return rng.integers(spec["low"], spec["high"], n).astype(
            spec["dtype"])
    raise ValueError(f"unknown column generator {gen!r}")


class Traffic:
    """A pool of `pool_sends` pre-built sends, cycled; event i of the run
    carries the timestamp t0_ms + floor(i * 1000 / event_time_rate)."""

    def __init__(self, config, workload, seed):
        inp = config["input"]
        self.t0_ms = int(inp["t0_ms"])
        self.send_events = int(workload["send_events"])
        self.event_time_rate = int(workload["event_time_rate"])
        self.rate = workload.get("rate")
        self.pool_sends = int(workload.get("pool_sends", 256))
        span = self.pool_sends * self.send_events * 1000
        if span % self.event_time_rate:
            raise ValueError("pool_sends * send_events * 1000 must be a "
                             "multiple of event_time_rate")
        self.pool_span_ms = span // self.event_time_rate
        if (self.send_events * 1000) % self.event_time_rate:
            raise ValueError("a send must span a whole number of ms of "
                             "event time, so a row's timestamp names it")
        self.send_span_ms = self.send_events * 1000 // self.event_time_rate

        rng = np.random.default_rng(int(seed))
        n = self.pool_sends * self.send_events
        self.key_columns = {}          # column -> ndarray of key strings
        self.ids = {}                  # column -> integer ids / raw values
        for name, spec in inp["columns"].items():
            self.ids[name] = _column(spec, rng, n)
            if spec["gen"] == "key":
                self.key_columns[name] = np.asarray(
                    [f"{spec['prefix']}{i}" for i in range(spec["count"])],
                    object)
        self.pool_ts = self.t0_ms + (
            np.arange(n, dtype=np.int64) * 1000) // self.event_time_rate
        # what the system is sent: key strings, not ids
        self.sent_cols = {
            name: (self.key_columns[name][v] if name in self.key_columns
                   else v) for name, v in self.ids.items()}
        self.next_send = 0             # sends made so far, warm-up included

    # ------------------------------------------------------------ sends

    def batch(self, first_send, sends=1):
        """(columns, timestamps) of `sends` consecutive sends as one
        batch, starting at run-wide send index `first_send`."""
        parts_c, parts_t = [], []
        for j in range(first_send, first_send + sends):
            cyc, k = divmod(j, self.pool_sends)
            sl = slice(k * self.send_events, (k + 1) * self.send_events)
            parts_c.append({c: v[sl] for c, v in self.sent_cols.items()})
            t = self.pool_ts[sl]
            parts_t.append(t + cyc * self.pool_span_ms if cyc else t)
        if sends == 1:
            return parts_c[0], parts_t[0]
        return ({c: np.concatenate([p[c] for p in parts_c])
                 for c in parts_c[0]}, np.concatenate(parts_t))

    def take(self, sends=1):
        """The next `sends` sends as one batch; advances the run."""
        out = self.batch(self.next_send, sends)
        self.next_send += sends
        return out

    def sent_events(self):
        """Everything sent so far, for the reference: key columns as
        integer ids."""
        n = self.next_send * self.send_events
        pool_n = self.pool_sends * self.send_events
        idx = np.arange(n) % pool_n
        cols = {c: v[idx] for c, v in self.ids.items()}
        ts = self.t0_ms + (np.arange(n, dtype=np.int64) * 1000) \
            // self.event_time_rate
        return cols, ts

    def first_ts_of_send(self, j):
        """Timestamp of the first event of run-wide send j."""
        return self.t0_ms + j * self.send_span_ms

    def send_of_ts(self, ts):
        """Run-wide send index that carried the event stamped `ts`."""
        return (np.asarray(ts, np.int64) - self.t0_ms) // self.send_span_ms

    # ----------------------------------------------------------- window

    def run_window(self, send, seconds):
        """Drive `send(cols, ts)` from a thread of the generator's own for
        `seconds`; -> dict with t_open, first_send, n_sends, and per send
        the due and actual start times (paced) or the start times alone."""
        res = {}
        clock = time.perf_counter

        def loop():
            first = self.next_send
            period = (self.send_events / float(self.rate)
                      if self.rate else None)
            starts = []
            t_open = clock()
            t_end = t_open + seconds
            j = 0
            while True:
                if period is not None:
                    due = t_open + j * period
                    if due >= t_end:
                        break
                    wait = due - clock()
                    if wait > 0:
                        time.sleep(wait)
                elif clock() >= t_end:
                    break
                cols, ts = self.batch(first + j)
                starts.append(clock())
                send(cols, ts)
                j += 1
            self.next_send = first + j
            starts = np.asarray(starts, np.float64)
            res.update(t_open=t_open, first_send=first, n_sends=j,
                       starts=starts, t_last_send_done=clock(),
                       due=(t_open + np.arange(j) * period
                            if period is not None else None))

        th = threading.Thread(target=loop, name="bench-generator")
        th.start()
        th.join()
        if "n_sends" not in res:
            raise RuntimeError("the generator thread died")
        return res

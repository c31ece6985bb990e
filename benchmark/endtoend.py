"""The end-to-end quantities, as the benchmark itself takes them by the
host's clock; a cell reports those that BENCHMARK.json lists for it.  Each
is over all the work and all the time of the window."""
import numpy as np


def latencies_ms(win, rows, traffic):
    """Per delivered row of an open-loop window: (latency in ms, index of
    the window's send it belongs to).  A row's timestamp names the send
    that carried its last contributing event; its latency runs from that
    send's due time to the callback's receipt of the row."""
    send = traffic.send_of_ts(rows["__ts"]) - win["gen"]["first_send"]
    return (rows["__t_recv"] - win["gen"]["due"][send]) * 1e3, send


def values(win, rows, traffic, setup_s):
    """win: what run.measure returned; rows: the rows delivered for the
    window's own events, with each row's receipt time."""
    vals = {"setup_s": setup_s,
            "events_per_s": win["events"] / win["window_s"]}
    if win["gen"]["due"] is not None and len(rows["__ts"]):
        lat_ms, _ = latencies_ms(win, rows, traffic)
        vals["match_latency_p50_ms"] = float(np.percentile(lat_ms, 50))
        vals["match_latency_p95_ms"] = float(np.percentile(lat_ms, 95))
        vals["latency_samples"] = int(len(lat_ms))
    return vals

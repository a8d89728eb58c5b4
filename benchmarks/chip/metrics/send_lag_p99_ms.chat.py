"""Load generator: how late the open-loop client sent, as the 99th
percentile of (actual send - due time) over the window's requests, in ms.
A late generator would be read as a slow server."""

import harness


def read(run, cell, peaks):
    lag = run.counters.get("send_lag_s")
    if not lag:
        return None
    return harness.percentile(lag, 99) * 1e3

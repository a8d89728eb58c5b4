"""Prefill farm: how long a request waited for a prefill worker, as the
95th percentile (nearest rank) of ``queued_us`` (worker pick-up minus
submit) over the window's ``serve.prefill`` spans, in ms."""

import harness
import spans


def read(run, cell, peaks):
    waits = spans.stat_ms(spans.of_run(run, cell), "serve.prefill",
                          "queued_us")
    return harness.percentile(waits, 95) if waits else None

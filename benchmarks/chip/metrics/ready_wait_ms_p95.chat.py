"""Cache manager: how long a prefilled request sat in the ready queue
before its slot insert, as the 95th percentile (nearest rank) of
``ready_us`` (insert minus prefill done) over the window's
``serve.insert`` spans, in ms."""

import harness
import spans


def read(run, cell, peaks):
    waits = spans.stat_ms(spans.of_run(run, cell), "serve.insert",
                          "ready_us")
    return harness.percentile(waits, 95) if waits else None

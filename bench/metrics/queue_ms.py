"""Front end: median wait from admission to batch dispatch.

``FrontendResponse.queue_ms`` (queued -> dispatched) of every request that
finished; the time a request spends before ``run_batch`` takes it.
"""

import statistics


def read(run):
    vals = [r["queue_ms"] for r in run.requests if r["queue_ms"] is not None]
    return statistics.median(vals) if vals else None

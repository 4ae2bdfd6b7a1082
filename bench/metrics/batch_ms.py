"""Service: median time of one dispatched batch inside ``run_batch``.

From the front end's timestamps, ``done - launched`` once per dispatched
micro-batch (requests of one batch share both).
"""

import statistics


def read(run):
    vals = [(b["t_done"] - b["t_launch"]) * 1e3 for b in run.batches
            if b.get("t_done") is not None]
    return statistics.median(vals) if vals else None

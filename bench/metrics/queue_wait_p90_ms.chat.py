"""Scheduler: 90th percentile of the wait from a request's scheduled
arrival to the start of the step in which the scheduler gave it a
seat, over the requests seated in the window."""
import numpy as np


def read(run):
    vals = [r.admitted - r.due for r in run.records
            if r.admitted is not None and run.t0 <= r.admitted <= run.t1]
    if not vals:
        return None
    return float(np.percentile(vals, 90)) * 1e3

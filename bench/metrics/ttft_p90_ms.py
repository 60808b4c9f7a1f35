"""90th percentile of time to first token over every request due in
the window, from its scheduled arrival (not from submit) to the end of
the step that handed its first token to the host.  A request with no
first token by the end of the wait counts at that wait."""
import numpy as np


def read(run):
    vals = [((r.times[0] if r.times else run.resolved_at) - r.due)
            for r in run.records
            if run.t0 <= r.due < run.t1 and not r.refused]
    if not vals:
        return None
    return float(np.percentile(vals, 90)) * 1e3

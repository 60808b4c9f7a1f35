"""95th percentile of the gaps between consecutive tokens of a request,
as the host received them, over every gap that ends in the window."""
import numpy as np


def read(run):
    gaps = [b - a for r in run.records
            for a, b in zip(r.times, r.times[1:]) if run.t0 < b <= run.t1]
    if not gaps:
        return None
    return float(np.percentile(gaps, 95)) * 1e3

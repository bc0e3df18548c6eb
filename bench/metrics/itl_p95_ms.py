"""itl_p95_ms: 95th percentile, in ms, of every gap between two
consecutive output tokens of one request, both inside the window."""
import numpy as np


def read(rec):
    gaps = [b - a for ts in rec.token_times.values()
            for a, b in zip(ts, ts[1:]) if rec.t0 <= a and b <= rec.t1]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None

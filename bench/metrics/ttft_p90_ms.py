"""ttft_p90_ms: 90th percentile, in ms, of the time from each request's
due time to its first token, over every request whose first token came
inside the window (arrivals during warm-up included)."""
import numpy as np


def read(rec):
    ttft = [ts[0] - rec.due[uid] for uid, ts in rec.token_times.items()
            if ts and rec.t0 <= ts[0] <= rec.t1]
    return float(np.percentile(ttft, 90)) * 1e3 if ttft else None

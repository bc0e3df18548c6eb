"""tokens_per_s: output tokens generated inside the window over the
window's length (host clock)."""


def read(rec):
    n = sum(rec.t0 < t <= rec.t1 for ts in rec.token_times.values()
            for t in ts)
    return n / (rec.t1 - rec.t0)

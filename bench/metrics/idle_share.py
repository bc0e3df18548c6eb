"""idle_share: per cent of the traced window in which no op ran on the
device (averaged over the chips)."""
from bench.xplane import busy_s


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - busy_s(rec.trace) / rec.trace.window_s)

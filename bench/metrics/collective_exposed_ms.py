"""collective_exposed_ms: per engine tick in the traced window, the time
in ms in which a collective op ran on device 0 and no other op did."""
from bench.xplane import exposed_collective_s


def read(rec):
    if rec.trace is None or rec.chips == 1:
        return None
    ticks = rec.trace.spans_named("bench.tick")
    if not ticks:
        return None
    return exposed_collective_s(rec.trace, 0) / len(ticks) * 1e3

"""engine_host_ms: per engine tick in the traced window, the tick's host
span (``bench.tick``) less the device-0 busy time inside it, in ms: the
host work of PagedDecodeEngine.step that the device waits on."""
from bench.xplane import busy, length


def read(rec):
    if rec.trace is None:
        return None
    ticks = rec.trace.spans_named("bench.tick")
    if not ticks:
        return None
    host = [t.dur - length(busy(rec.trace, 0, t.start, t.end))
            for t in ticks]
    return sum(host) / len(host) * 1e-6

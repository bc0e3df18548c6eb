"""serve_step_roofline: per cent of the step programs' device time that
the least time of their work takes.  For every step call in the traced
window, the least time is the weights once plus the K/V of the live
contexts over HBM, or the FLOPs, whichever binds, split over the chips
(bench/work.py); their sum is divided by the summed device time of the
step programs (both chunk widths) on device 0."""
from bench.work import step_least_seconds
from bench.xplane import module_events


def read(rec):
    if rec.trace is None:
        return None
    progs = module_events(rec.trace, "serve_step_c")
    spans = rec.trace.spans_named("bench.step_c")
    if not progs or not spans:
        return None
    least = sum(step_least_seconds(rec.shape, rec.calls[s.stats["call"]][1],
                                   rec.chips, rec.peaks)[0] for s in spans)
    device = sum(p.dur for p in progs) * 1e-9
    # the window may cut a call's span and its program differently
    return 100.0 * (least / len(spans)) / (device / len(progs))

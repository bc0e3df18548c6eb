"""serve_step_roofline: per cent of the step programs' device time that
the least time of their work takes.  For every step call in the traced
window, the least time is the weights once plus the K/V of the live
contexts over HBM, or the FLOPs, whichever binds, split over the chips
(the family's ``step_flops`` and ``step_bytes``, bench/work.py); their
sum is divided by the summed device time of the step programs (both
chunk widths) on device 0."""
from bench.work import step_least_seconds
from bench.xplane import module_events


def read(rec):
    if rec.trace is None:
        return None
    progs = module_events(rec.trace, "serve_step_c")
    spans = rec.trace.spans_named("bench.step_c")
    if not progs or not spans:
        return None
    fam = rec.family
    least = 0.0
    for s in spans:
        slots = rec.calls[s.stats["call"]][1]
        least += step_least_seconds(fam.step_flops(rec.shape, slots),
                                    fam.step_bytes(rec.shape, slots),
                                    rec.chips, rec.peaks)[0]
    device = sum(p.dur for p in progs) * 1e-9
    # the window may cut a call's span and its program differently
    return 100.0 * (least / len(spans)) / (device / len(progs))

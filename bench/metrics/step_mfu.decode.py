"""step_mfu.decode: the whole step's share of the chips' bf16 peak in a
decode cell: model FLOPs of every token the step programs processed in
the traced window (the family's ``step_flops``) over the window's length
times the chips' peak."""


def read(rec):
    if rec.trace is None:
        return None
    spans = rec.trace.spans_named("bench.step_c")
    if not spans:
        return None
    flops = sum(rec.family.step_flops(rec.shape,
                                      rec.calls[s.stats["call"]][1])
                for s in spans)
    return 100.0 * flops / (rec.trace.window_s * rec.chips
                            * rec.peaks.flops_bf16)

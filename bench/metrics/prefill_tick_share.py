"""prefill_tick_share: per cent of the window's engine ticks that ran
the C=chunk program (some slot was prefilling), counted on the host."""


def read(rec):
    cs = [c for _, end, c in rec.ticks[rec.window_first_tick:] if c]
    return 100.0 * sum(c == rec.chunk for c in cs) / len(cs) if cs else None

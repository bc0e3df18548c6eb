"""paged_attn_share: per cent of the step programs' device time on device
0 in the traced window that ops under the ``attn.paged`` named scope take
(``paged_attention``: on a TPU the Pallas kernel ``paged_attention``,
which reads each slot's visible KV blocks in place, with the per-rank
block list and the merge of partial results around it).  Each op is
found by its HLO instruction in the step program it ran in
(bench/scopes.py); a program without the scope reads nothing."""
from bench.scopes import scope_map, scope_seconds, step_programs_hlo


def read(rec):
    if rec.trace is None:
        return None
    maps = {c: scope_map(text)
            for c, text in step_programs_hlo(rec.cell).items()}
    secs = scope_seconds(rec.trace, maps)
    paged = sum(s for k, s in secs if k.split("/")[0] == "attn.paged")
    if not paged:
        return None
    return 100.0 * paged / sum(s for _, s in secs)

"""gemv_ar_roofline: per cent of the fused GEMV+AllReduce kernel's
device time that the least time of its work takes.  The kernel is the
step programs' only Pallas call (``custom_call_target="tpu_custom_call"``
in the op's name on the trace); each call's rows are the step's slots
times its chunk width, read from the program it runs in, and its least
time is the weight slice over HBM, the all-reduce over ICI, or the
FLOPs, whichever binds (bench/work.py)."""
import re

from bench.work import gemv_allreduce_least_seconds
from bench.xplane import ops_in

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(rec):
    if rec.trace is None or rec.chips == 1:
        return None
    calls = [(op, prog) for op, prog in ops_in(rec.trace, KERNEL)
             if prog is not None and "serve_step_c" in prog.name]
    if not calls:
        return None
    batch = rec.cell.config["harness"]["batch"]
    s = rec.shape
    least = 0.0
    for op, prog in calls:
        c = int(re.search(r"serve_step_c(\d+)", prog.name).group(1))
        least += gemv_allreduce_least_seconds(batch * c, s.d_ff, s.d_model,
                                              rec.chips, rec.peaks)[0]
    device = sum(op.dur for op, _ in calls) * 1e-9
    return 100.0 * least / device

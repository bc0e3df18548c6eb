"""Readings that a cell's correctness limit is set from.

  python3 bench/calibrate.py --workload glm6b.decode --seconds 30 \
      --seeds 101,102,103

For each seed, in one process: the cell's set-up and window exactly as a
benchmark run makes them, then the same sample of finished requests
compared with the float32 reference, both as served by the program and
as the float8 control would have chosen its tokens at the same
positions.  One JSON line per seed: the widest gap of each, the numbers
of requests and tokens compared, and the end-to-end metrics of the
window.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, harness, reference
    from bench.peaks import peaks_for
    from bench.run import enable_cache, read_metric, require_chips
    from bench.work import Shape

    cell = harness.load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_cache()
    shape = Shape.from_config(cell.config)
    peaks = peaks_for(devices[0].device_kind)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        rec, served = harness.run(cell, seed, args.seconds, False,
                                  t_start=t, shape=shape, peaks=peaks)
        metrics = {n: read_metric(n, rec)
                   for n in harness.cell_metrics(cell, False)}
        harness.release(served)
        del served
        picked = check.sample(rec.finished, seed)
        g, gl = reference.gaps(seed, shape,
                               [(r.prompt, r.tokens) for r in picked],
                               control=True)
        print(json.dumps({
            "seed": seed,
            "program_max_gap": max(float(x.max()) for x in g),
            "control_max_gap": max(float(x.max()) for x in gl),
            "requests": len(picked),
            "tokens": sum(len(r.tokens) for r in picked),
            "longest": max(len(r.tokens) for r in picked),
            "numbers": check.numbers(rec, g),
            "metrics": metrics}), flush=True)


if __name__ == "__main__":
    main()

"""Find the highest open-loop rate a configuration sustains under a
traffic mix: one process, one window per rate, at the cell's own sizes.

  python3 bench/sweep.py --config chatglm3-6b --traffic longprompt \
      --seconds 30 --seed 7 --rates 1.5,2,2.5,3

Prints one JSON line per rate: requests due and finished in the window,
the queue at the window's start and end, tokens per second and the
time-to-first-token tail.  The knee is the highest rate whose queue does
not grow through the window.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.peaks import peaks_for
    from bench.run import enable_cache, read_metric, require_chips
    from bench.work import Shape

    cell = harness.make_cell("sweep", args.config, args.traffic, 0, {})
    cell.chips = cell.config["harness"]["chips"]
    devices = require_chips(cell.chips)
    enable_cache()
    shape = Shape.from_config(cell.config)
    peaks = peaks_for(devices[0].device_kind)
    for rate in [float(r) for r in args.rates.split(",")]:
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate=rate))
        rec, served = harness.run(c, args.seed, args.seconds, False,
                                  t_start=time.perf_counter(), shape=shape,
                                  peaks=peaks)
        harness.release(served)
        del served
        due = sum(rec.t0 <= d <= rec.t1 for d in rec.due.values())
        done = sum(rec.t0 <= r.t_done <= rec.t1 for r in rec.finished)
        print(json.dumps({
            "rate": rate, "due_in_window": due, "finished_in_window": done,
            "queued_at_start": rec.queued[0], "queued_at_end": rec.queued[1],
            "tokens_per_s": read_metric("tokens_per_s", rec),
            "ttft_p90_ms": read_metric("ttft_p90_ms", rec),
            "itl_p95_ms": read_metric("itl_p95_ms", rec)}), flush=True)


if __name__ == "__main__":
    main()

"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload glm6b.decode --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); each metric is read by
``bench/metrics/<name>.py``.  Set-up draws the weights on the device from
``--seed``, loads or compiles the two step programs, and warms up the
traffic; then the window serves for ``--seconds``.  ``--trace 1`` profiles
a few seconds of the window and prints the per-layer metrics instead of
the end-to-end ones.  After the window the served tokens are checked
against the float32 reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number checked beside its
limit); the compared numbers are also the last lines of standard error.
Without a TPU, or with another number of chips than the cell asks for,
or without the program's sources beside it, it exits nonzero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# the script's own directory is no place to look for modules: the
# benchmark is imported as the package ``bench`` from the checkout root
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def read_metric(name: str, rec):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int):
    """The devices of this machine, which must be ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {devices[0].platform})")
    if len(devices) != chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def enable_cache():
    """JAX's persistent compilation cache, where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the
    checkout), for every program however fast it compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(cell, seed: int, seconds: float, trace: bool, *, devices,
            limits: dict, peaks=None, make_ctx=None, wrap_steps=None):
    """Everything after the look for chips: set-up, window, metrics,
    check.  Returns the result object."""
    import jax

    from bench import check, harness, reference
    from bench.peaks import peaks_for
    from bench.work import Shape
    from bench.xplane import busy_s, idle_gaps, op_seconds

    shape = Shape.from_config(cell.config)
    peaks = peaks or peaks_for(devices[0].device_kind)
    rec, served = harness.run(
        cell, seed, seconds, trace, t_start=T_START, shape=shape,
        peaks=peaks, make_ctx=make_ctx, wrap_steps=wrap_steps)
    late = rec.lateness or [0.0]
    harness.log(f"window {rec.t1 - rec.t0:.3f} s, {len(rec.ticks)} ticks "
                f"({len(rec.ticks) - rec.window_first_tick} in the window), "
                f"{len(rec.finished)} requests finished, generator late by "
                f"{1e3 * sum(late) / len(late):.3f} ms on average, "
                f"{1e3 * max(late):.3f} ms at most")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    metrics = {}
    for name in harness.cell_metrics(cell, trace):
        unit = next(m["unit"] for key in ("end_to_end", "per_layer")
                    for m in cell.bench[key] if m["name"] == name)
        value = read_metric(name, rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": False, "attempted": rec.attempted,
           "failed": rec.truncated, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = busy_s(rec.trace)
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": op_seconds(rec.trace)[:10],
                            "idle_gaps": idle_gaps(rec.trace)[:10]}
    harness.release(served)
    del served
    t = time.perf_counter()
    ok, rows, n_req, n_tok = check.check(
        rec, limits, lambda seqs: reference.gaps(seed, shape, seqs)[0])
    harness.log(f"reference: {n_req} requests, {n_tok} served tokens "
                f"compared in {time.perf_counter() - t:.1f} s")
    out["correct"] = ok
    out["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                       for r in rows}
    for r in rows:
        harness.log(f"compared {r['name']}: {r['value']!r} "
                    f"(limit {r['limit']!r})")
    jax.clear_caches()
    return out


def main(argv=None):
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, harness

    cell = harness.load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_cache()
    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  devices=devices, limits=check.load_limits(cell.name))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""A cell at a size the CPU holds: the benchmark's own configuration
files with every width and the depth cut, and a short closed-loop mix."""
import copy
import json
from pathlib import Path

import numpy as np

from bench import harness

BENCH = Path(__file__).resolve().parents[1]
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab=512, max_seq=128)
MIX = {"name": "tiny", "loop": "closed", "clients": "slots",
       "prompt": {"median": 14, "sigma": 0.5, "min": 4, "max": 40},
       "output": {"median": 10, "sigma": 0.5, "min": 4, "max": 30}}


def tiny_cell(config: str, *, chips=1, dtype="bfloat16", **extra):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    over = dict(TINY, param_dtype=dtype, compute_dtype=dtype, **extra)
    cfg["shape"].update({k: v for k, v in over.items()
                         if k in cfg["shape"]}, dtype=dtype)
    cfg["harness"]["overrides"] = over
    cfg["harness"].update(batch=4, chips=chips)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = copy.deepcopy(bench)
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    bench["per_layer"] = []
    return harness.Cell(f"tiny.{config}", cfg, dict(MIX), chips, bench)


def make_ctx(chips):
    import jax
    from jax.sharding import Mesh

    from repro.parallel.sharding import ParallelContext

    devs = np.array(jax.devices()[:chips]).reshape(1, chips)
    return lambda fusion: ParallelContext.from_mesh(
        Mesh(devs, ("data", "model")), fusion=fusion)

"""Model families of the benchmark, found by name.

A configuration file (``bench/configs/<name>.json``) names its family
with the top-level key ``family``; the family's code is the file
``bench/families/<family>.py``, found by that name as the metric readers
are.  A family module gives:

- ``Shape``, with ``Shape.from_config(cfg)``: the sizes the benchmark
  reads, at least ``n_layers``, ``d_model``, ``vocab`` and ``dtype``;
- ``step_flops(shape, slots)`` and ``step_bytes(shape, slots)``: the
  model FLOPs and the least HBM bytes of one serve step, where ``slots``
  lists (pos, n_new) of every slot that works in it; the yardstick of the
  step's roofline and utilisation;
- ``gaps(seed, shape, seqs, *, control=False)``: the plain float32
  reference, with the weights drawn from ``seed`` by :mod:`bench.weights`,
  teacher-forced over each (prompt, served tokens) pair: per served
  token, the reference's best logit minus its logit of that token; with
  ``control``, the same for the token the lower precision puts first.

A new architecture comes as a new file here and a configuration that
names it; no other file of the benchmark names a family.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

DIR = Path(__file__).resolve().parent


def names() -> list:
    """The families there are: the modules of ``DIR``."""
    return sorted(p.stem for p in DIR.glob("*.py")
                  if not p.stem.startswith("_"))


def module(name: str):
    """The family module ``DIR/<name>.py``, loaded once a process."""
    if name not in names():
        raise SystemExit(f"bench: no model family {name!r} in {DIR}; "
                         f"families: {', '.join(names())}")
    path = (DIR / f"{name}.py").resolve()
    key = f"bench_family:{path}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        # registered before it runs, as an import would: a dataclass looks
        # its module up while it is made
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]


def load(cfg: dict) -> tuple:
    """(family module, its Shape of the configuration) for a
    configuration, which must name its family."""
    if "family" not in cfg:
        raise SystemExit(f"bench: configuration {cfg.get('name')!r} names "
                         f"no model family (key \"family\"); families: "
                         f"{', '.join(names())}")
    fam = module(cfg["family"])
    return fam, fam.Shape.from_config(cfg)

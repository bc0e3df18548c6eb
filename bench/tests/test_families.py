"""Model families found by name: the gqa family gives, to the bit, what
the benchmark's one dense GQA reference gave before families existed; a
family added as a file, with a configuration naming it, is the one a run
uses for its shape, its work counts and its reference; a configuration
must name a family that exists."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import check, families
from bench import work as W
from bench.peaks import PEAKS
from bench.run import execute, read_metric
from bench.tests.tiny import make_ctx, tiny_cell
from bench.xplane import Ev, Trace

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SEED = 2147483650

# Per request, the reference's gaps and the float8 control's, as
# float.hex, computed by ``bench/reference.py`` before it became the gqa
# family, for the requests ``requests`` draws.
BEFORE = {
    "chatglm3-6b": (
        [["0x1.5e6d800000000p+1", "0x1.08e44c0000000p+1",
          "0x1.6c1db40000000p+0", "0x1.da90560000000p+1",
          "0x1.3f5efc0000000p+1", "0x1.112af40000000p+1"],
         ["0x1.b2501a0000000p+1", "0x1.24be040000000p+2",
          "0x1.083c2c0000000p+2", "0x1.002fb00000000p+2",
          "0x1.21915c0000000p+2", "0x1.45eef60000000p+1"]],
        [["0x0.0p+0", "0x0.0p+0", "0x0.0p+0", "0x0.0p+0",
          "0x1.50bf000000000p-4", "0x0.0p+0"],
         ["0x0.0p+0", "0x0.0p+0", "0x1.a937800000000p-5", "0x0.0p+0",
          "0x0.0p+0", "0x0.0p+0"]]),
    "phi3-medium-14b": (
        [["0x1.689aee0000000p+1", "0x1.79fcda0000000p+1",
          "0x1.ba70340000000p+0", "0x1.009bce0000000p+2",
          "0x1.6d63680000000p+1", "0x1.cdd0940000000p+0"],
         ["0x1.b123060000000p+1", "0x1.d400440000000p+1",
          "0x1.126eb80000000p+2", "0x1.8459620000000p+1",
          "0x1.31c45a0000000p+2", "0x1.0bc5fa0000000p+1"]],
        [["0x0.0p+0", "0x1.2a1cf00000000p-2", "0x0.0p+0", "0x0.0p+0",
          "0x0.0p+0", "0x1.3b26000000000p-7"],
         ["0x0.0p+0"] * 6]),
}

TOY = '''
"""The gqa family under another name, counting the calls made of it."""
from bench.families import gqa

calls = []


class Shape(gqa.Shape):
    @classmethod
    def from_config(cls, cfg):
        calls.append("Shape")
        return cls(**cfg["shape"])


def step_flops(shape, slots):
    calls.append("step_flops")
    return gqa.step_flops(shape, slots)


def step_bytes(shape, slots):
    calls.append("step_bytes")
    return gqa.step_bytes(shape, slots)


def gaps(seed, shape, seqs, *, control=False):
    calls.append("gaps")
    return gqa.gaps(seed, shape, seqs, control=control)
'''


def requests(vocab):
    """Two requests of random prompt and served tokens: prompts of 5 and
    23 tokens, 6 served tokens each."""
    rng = np.random.default_rng(7)
    return [(rng.integers(0, vocab, n).tolist(),
             rng.integers(0, vocab, 6).tolist()) for n in (5, 23)]


def hexes(arrays):
    return [[float(x).hex() for x in a] for a in arrays]


@pytest.mark.parametrize("config,extra", [
    ("chatglm3-6b", {}),
    ("phi3-medium-14b", {"window": 16}),
])
def test_gqa_reference_gives_the_gaps_it_gave_before(config, extra):
    family, shape = families.load(tiny_cell(config, **extra).config)
    assert family is families.module("gqa")
    gaps, control = family.gaps(SEED, shape, requests(shape.vocab),
                                control=True)
    assert (hexes(gaps), hexes(control)) == (
        BEFORE[config][0], BEFORE[config][1])


def test_a_family_added_as_a_file_is_the_one_a_run_uses(tmp_path,
                                                        monkeypatch):
    (tmp_path / "toy.py").write_text(TOY)
    monkeypatch.setattr(families, "DIR", tmp_path)
    cell = tiny_cell("chatglm3-6b")
    cell.config["family"] = "toy"
    out = execute(cell, SEED, 2.0, False, devices=jax.devices()[:1],
                  limits=check.load_limits("glm6b.decode"),
                  peaks=PEAKS["TPU v5 lite"], make_ctx=make_ctx(1))
    toy = families.module("toy")
    assert toy.calls == ["Shape", "gaps"]
    assert out["correct"] is True

    # the step programs' yardstick is the family's work counts: a C=8
    # call and a C=1 call in a traced window of 1 us
    shape = toy.Shape.from_config(cell.config)
    calls = [(8, [(0, 8), (3, 5)]), (1, [(8, 1), (12, 1)])]
    trace = Trace(ops={}, modules={0: [Ev("serve_step_c8", 100, 600),
                                       Ev("serve_step_c1", 700, 900)]},
                  spans=[Ev("bench.trace_window", 0, 1000),
                         Ev("bench.step_c8", 50, 650, {"call": 0}),
                         Ev("bench.step_c1", 650, 950, {"call": 1})])
    peaks = PEAKS["TPU v5 lite"]
    rec = SimpleNamespace(trace=trace, calls=calls, family=toy, shape=shape,
                          chips=1, peaks=peaks)
    toy.calls.clear()
    mfu = read_metric("step_mfu.decode", rec)
    assert toy.calls == ["step_flops"] * 2
    roofline = read_metric("serve_step_roofline", rec)
    assert toy.calls[2:] == ["step_flops", "step_bytes"] * 2
    gqa = toy.gqa
    flops = sum(gqa.step_flops(shape, s) for _, s in calls)
    assert mfu == pytest.approx(100.0 * flops / (1e-6 * peaks.flops_bf16),
                                rel=1e-12)
    least = sum(W.step_least_seconds(gqa.step_flops(shape, s),
                                     gqa.step_bytes(shape, s), 1, peaks)[0]
                for _, s in calls)
    assert roofline == pytest.approx(100.0 * least / 700e-9, rel=1e-12)


@pytest.mark.parametrize("family", [None, "mla_moe"])
def test_a_configuration_must_name_a_family_that_exists(family):
    cfg = json.loads((CONFIGS / "chatglm3-6b.json").read_text())
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    with pytest.raises(SystemExit, match="families: .*gqa"):
        families.load(cfg)

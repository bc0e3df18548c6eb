"""The accepted trace reductions (bench/xplane.py), pinned: on the trace
recorded on a TPU v5e chip each reads what it read when its metrics were
accepted, and idle time is named by the innermost of nested host
spans."""
import gzip
from pathlib import Path

import pytest

from bench import xplane as X

DATA = Path(__file__).parent / "data" / "glm6b_decode_4ticks.xplane.pb.gz"


def ev(name, start, end, **stats):
    return X.Ev(name, float(start), float(end), stats)


def test_recorded_chip_trace_reductions_pinned():
    from jax.profiler import ProfileData

    t = X.from_profile(ProfileData.from_serialized_xspace(
        gzip.open(DATA).read()))
    assert {s.name for s in t.spans} == {
        "bench.trace_window", "bench.tick", "bench.step_c8", "bench.client"}
    assert X.busy_s(t) == pytest.approx(0.309873027, rel=1e-12)
    assert dict(X.idle_gaps(t)) == pytest.approx(
        {"bench.tick": 0.015886777, "bench.client": 0.00023618,
         "no span": 0.00011157}, rel=1e-9)
    ops = X.op_seconds(t)
    assert len(ops) == 255
    assert sum(s for _, s in ops) == pytest.approx(0.309842722, rel=1e-9)
    assert ops[:3] == [
        ("fusion.322 bf16[8192,16,2,128] fusion", pytest.approx(0.055870339)),
        ("fusion.312 bf16[8192,16,2,128] fusion", pytest.approx(0.055870045)),
        ("fusion.345 bf16[32,8,4096] fusion", pytest.approx(0.019128341))]
    progs = X.module_events(t, "serve_step_c8")
    assert [(p.start, p.dur) for p in progs] == [
        (43958111.0, 77444543.0), (125202901.0, 77450784.0),
        (206613461.0, 77448928.0), (288339421.0, 77457046.0)]
    assert len(X.module_events(t, "argmax")) == 4
    assert X.exposed_collective_s(t) == 0.0


def test_idle_gaps_named_by_the_innermost_of_nested_spans():
    """A window [0, 200): bench.tick [0, 120) holding serve.tick [2, 118):
    schedule [2, 20), dispatch [20, 40) (holding the harness's step call
    [22, 38)), sample [40, 100), commit [104, 118).  Device 0 is busy on
    [25, 90)."""
    ops = {0: [ev("%fusion.1 = bf16[8] fusion(x)", 25, 90)]}
    spans = [ev("bench.trace_window", 0, 200), ev("bench.tick", 0, 120),
             ev("serve.tick", 2, 118, tick=0, width=1),
             ev("serve.schedule", 2, 20), ev("serve.dispatch", 20, 40),
             ev("bench.step_c1", 22, 38, call=0),
             ev("serve.sample", 40, 100), ev("serve.commit", 104, 118)]
    t = X.Trace(ops, {0: []}, spans)
    gaps = dict(X.idle_gaps(t))
    assert gaps == pytest.approx({
        "bench.tick": 4e-9,         # [0, 2) and [118, 120)
        "serve.schedule": 18e-9, "serve.dispatch": 2e-9,
        "bench.step_c1": 3e-9,      # [22, 25)
        "serve.sample": 10e-9,      # [90, 100)
        "serve.tick": 4e-9,         # [100, 104)
        "serve.commit": 14e-9, "no span": 80e-9})
    assert X.busy_s(t) + sum(gaps.values()) == pytest.approx(t.window_s)

"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e chip: four C=8 ticks of chatglm3-6b at batch 32."""
import gzip
from pathlib import Path

import pytest

from bench import xplane as X

DATA = Path(__file__).parent / "data" / "glm6b_decode_4ticks.xplane.pb.gz"


def ev(name, start, end, **stats):
    return X.Ev(name, float(start), float(end), stats)


def synthetic():
    """Device 0: a program [0, 100) holding a matmul [0, 40), an
    all-reduce [30, 60) (half hidden under the matmul) and a kernel
    [70, 90); a second program [150, 190).  Host: a window [0, 200), a
    tick [0, 120) holding a step call [0, 5), then a client span
    [120, 150)."""
    ops = {0: [ev("%fusion.1 = bf16[8] fusion(x)", 0, 40),
               ev("%all-reduce.2 = bf16[8] all-reduce(x)", 30, 60),
               ev('%custom-call.3 = bf16[8] custom-call(x), '
                  'custom_call_target="tpu_custom_call"', 70, 90),
               ev("%fusion.1 = bf16[8] fusion(x)", 150, 190)]}
    modules = {0: [ev("jit_serve_step_c1(1)", 0, 100),
                   ev("jit_serve_step_c8(2)", 150, 190)]}
    spans = [ev("bench.trace_window", 0, 200), ev("bench.tick", 0, 120),
             ev("bench.step_c1", 0, 5, call=0),
             ev("bench.client", 120, 150)]
    return X.Trace(ops, modules, spans)


def test_interval_arithmetic():
    assert X.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert X.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert X.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert X.length([(0, 3), (5, 8)]) == 6


def test_busy_idle_and_gaps_named_by_host_span():
    t = synthetic()
    assert t.window_s == pytest.approx(200e-9)
    # busy: [0, 60) + [70, 90) + [150, 190) = 120 ns
    assert X.busy_s(t) == pytest.approx(120e-9)
    gaps = dict(X.idle_gaps(t))
    # [60, 70) and [90, 120) lie in the tick, [120, 150) in the client
    # span, [190, 200) in no span
    assert gaps == pytest.approx({"bench.tick": 40e-9,
                                  "bench.client": 30e-9,
                                  "no span": 10e-9})
    assert X.busy_s(t) + sum(gaps.values()) == pytest.approx(t.window_s)


def test_programs_kernels_and_exposed_collectives():
    t = synthetic()
    assert [m.name for m in X.module_events(t, "serve_step_c1")] == [
        "jit_serve_step_c1(1)"]
    (k, prog), = X.ops_in(t, 'custom_call_target="tpu_custom_call"')
    assert (k.start, prog.name) == (70, "jit_serve_step_c1(1)")
    # the all-reduce runs alone on [40, 60)
    assert X.exposed_collective_s(t) == pytest.approx(20e-9)
    labels = dict(X.op_seconds(t))
    assert labels["fusion.1 bf16[8] fusion"] == pytest.approx(80e-9)
    assert "custom-call.3 bf16[8] custom-call tpu_custom_call" in labels


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    t = X.from_profile(ProfileData.from_serialized_xspace(
        gzip.open(DATA).read()))
    assert list(t.ops) == [0]
    assert t.window_s == pytest.approx(0.326107554)
    busy = X.busy_s(t)
    assert 0.9 < busy / t.window_s < 1.0
    gaps = X.idle_gaps(t)
    # the engine's host work inside the tick holds the device back most
    assert gaps[0][0] == "bench.tick"
    assert gaps[0][1] > 0.8 * sum(s for _, s in gaps)
    assert busy + sum(s for _, s in gaps) == pytest.approx(t.window_s)
    progs = X.module_events(t, "serve_step_c8")
    assert len(progs) == 4
    assert sum(p.dur for p in progs) / 4 == pytest.approx(77.45e6, rel=1e-3)
    ticks = t.spans_named("bench.tick")
    assert [s.stats["tick"] for s in ticks] == [137, 138, 139, 140]
    host = [s.dur - X.length(X.busy(t, 0, s.start, s.end)) for s in ticks]
    assert all(3.5e6 < h < 4.5e6 for h in host)
    assert X.exposed_collective_s(t) == 0.0
    # the paged gather of every table block leads the device time
    top, _ = X.op_seconds(t)[0]
    assert top.startswith("fusion.322 bf16[8192,16,2,128]")

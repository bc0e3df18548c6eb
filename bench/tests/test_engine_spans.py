"""The serving engine's own tracing at a size the CPU holds: its spans
under the profiler, and its counters against the harness's record."""
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.peaks import PEAKS
from bench.run import read_metric
from bench.tests.tiny import make_ctx, tiny_cell
from bench.work import Shape

CHILDREN = ["serve.schedule", "serve.dispatch", "serve.sample",
            "serve.commit"]


def engine_spans(directory):
    """(name, start ns, end ns, args) of every serve.* host event in the
    profile written under ``directory``, by start."""
    from jax.profiler import ProfileData

    path, = Path(directory).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def test_each_tick_has_its_four_spans_in_order(tmp_path):
    import jax

    from repro.serve.engine import Request

    served = harness.build(tiny_cell("chatglm3-6b"), 2147483651, make_ctx(1))
    harness.warm_up_shapes(served)
    eng = served.engine
    rng = np.random.default_rng(0)
    for uid in range(6):
        eng.submit(Request(uid=uid, prompt=rng.integers(1, 500, 11).tolist(),
                           max_new=3))
    ticks = 0
    with jax.profiler.trace(str(tmp_path)):
        while eng._pending():
            eng.step()
            ticks += 1
    spans = engine_spans(tmp_path)
    tick_spans = [s for s in spans if s[0] == "serve.tick"]
    assert len(tick_spans) == ticks > 4
    assert [s[3]["tick"] for s in tick_spans] == list(range(ticks))
    assert {s[3]["width"] for s in tick_spans} == {1, eng.chunk}
    assert all(s[3]["decode"] + s[3]["prefill_tokens"] > 0
               for s in tick_spans)
    for _, lo, hi, _ in tick_spans:
        kids = [s for s in spans if s[0] != "serve.tick"
                and lo <= s[1] and s[2] <= hi]
        assert [k[0] for k in kids] == CHILDREN
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    assert len(spans) == 5 * ticks


def test_counters_agree_with_the_harness():
    """ticks_wide/ticks is the harness's prefill_tick_share, counted the
    engine's way, over every tick of a short run; the tokens fed are
    those of the step calls the harness recorded."""
    cell = tiny_cell("chatglm3-6b")
    engines = []
    rec, served = harness.run(
        cell, 2147483652, 2.0, False, t_start=0.0,
        shape=Shape.from_config(cell.config), peaks=PEAKS["TPU v5 lite"],
        make_ctx=make_ctx(1), wrap_steps=lambda s: engines.append(s.engine))
    st = engines[0].stats
    harness.release(served)
    widths = [c for _, _, c in rec.ticks if c]
    assert st.ticks == len(widths) > 10
    assert st.ticks_wide == sum(c == rec.chunk for c in widths)
    assert st.prefill_tokens + st.decode_tokens == sum(
        n for _, slots in rec.calls for _, n in slots)
    # the accepted metric reads the same share from the window's ticks
    rec.window_first_tick = 0
    assert 100.0 * st.ticks_wide / st.ticks == pytest.approx(
        read_metric("prefill_tick_share", rec))
    assert st.admitted >= len(rec.finished)
    assert st.preempted == st.admit_deferred == st.truncated == 0

"""Serve one cell through the program's normal path and record the run.

The calls are those of ``repro.launch.serve.serve()``: a host mesh with
the configuration's ``--fusion`` mode, weights placed by
``init_params_on_mesh`` (drawn here from ``--seed`` by
:mod:`bench.weights`), the bundle's ``serve_step_fn`` jitted with the
weights as an argument, and a ``PagedDecodeEngine`` with the server's
default pool rule.  The harness drives ``engine.step()`` itself with the
mix's requests, and records what every metric reader needs: each tick,
each step-program call, each output token's time, and, in a traced run,
the profiler's trace of part of the window.

Host spans (``jax.profiler.TraceAnnotation``): ``bench.tick`` around each
``engine.step``; ``bench.step_c<C>`` around each call of the C-wide step
program; ``bench.client`` around the harness's own work between ticks;
``bench.trace_window`` around the traced part of the window.  The two
step programs are jitted under names of their own (``serve_step_c1``,
``serve_step_c<chunk>``), so their device events are told apart.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
TRACE_SECONDS = 4.0


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    bench: dict          # the whole BENCHMARK.json


def make_cell(name: str, config: str, traffic: str, chips: int,
              bench: dict) -> Cell:
    from bench.traffic import load_mix

    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    return Cell(name, cfg, load_mix(traffic), chips, bench)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            return make_cell(name, w["config"], w["traffic"], w["chips"],
                             bench)
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(cell: Cell, trace: bool) -> list:
    """Names of the metrics this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    key = "per_layer" if trace else "end_to_end"
    return [m["name"] for m in cell.bench[key]
            if cell.name in m.get("workloads", [cell.name])]


# -- the system under test -------------------------------------------------
class StepPrograms:
    """``serve_fn`` for the engine: the program's serve step, jitted with
    the weights as an argument, under one name per chunk width."""

    def __init__(self, step_fn, params, chunk: int):
        import jax

        def named(c):
            def f(p, *a):
                return step_fn(p, *a)
            f.__name__ = f.__qualname__ = f"serve_step_c{c}"
            return jax.jit(f)

        self.params = params
        self.jits = {1: named(1), chunk: named(chunk)}
        self.calls = []        # (C, pos, n_new) per call, device arrays

    def __call__(self, tokens, pool, tables, pos, n_new):
        from jax.profiler import TraceAnnotation

        c = tokens.shape[1]
        with TraceAnnotation(f"bench.step_c{c}", call=len(self.calls)):
            out = self.jits[c](self.params, tokens, pool, tables, pos, n_new)
        self.calls.append((c, pos, n_new))
        return out


def program_bundle(cell: Cell):
    """The registry bundle with the configuration's overrides, whose
    ``init_params`` draws the benchmark's seeded weights."""
    import jax

    from bench.weights import tree_init
    from repro.configs.registry import get_arch
    from repro.models.common import Param, split_params

    h = cell.config["harness"]
    base = get_arch(h["arch"])
    base = dataclasses.replace(base, config=dataclasses.replace(
        base.config, **h.get("overrides", {})))
    struct, specs = split_params(jax.eval_shape(base.init_params,
                                                jax.random.PRNGKey(0)))
    d_model = base.config.d_model

    class SeededBundle(type(base)):
        def init_params(self, key):
            vals = tree_init(key, struct, d_model)
            return jax.tree.map(Param, vals, specs,
                                is_leaf=lambda x: isinstance(x, tuple))

    return SeededBundle(**{f.name: getattr(base, f.name)
                           for f in dataclasses.fields(base)})


@dataclasses.dataclass
class Served:
    engine: object
    steps: StepPrograms
    params: object
    ctx: object


def build(cell: Cell, seed: int, make_ctx=None) -> Served:
    """Weights on the device from the seed, the two step programs, and the
    engine, as ``serve()`` builds them for ``--paged``."""
    from repro.launch.mesh import init_params_on_mesh, make_host_mesh
    from repro.parallel.sharding import FusionConfig
    from repro.serve.engine import PagedDecodeEngine

    h = cell.config["harness"]
    fusion = FusionConfig(mode=h["fusion"])
    ctx = (make_ctx or make_host_mesh)(fusion=fusion)
    bundle = program_bundle(cell)
    params, _ = init_params_on_mesh(bundle, ctx, seed)
    cfg = bundle.config
    batch, block = h["batch"], h["block_size"]
    # serve()'s --num-blocks, where the configuration gives it; else its
    # default: half the dense budget, tp-divisible
    num_blocks = h.get("num_blocks") or max(
        ctx.tp, (batch * cfg.max_seq // 2) // block // ctx.tp * ctx.tp)
    steps = StepPrograms(bundle.serve_step_fn(ctx), params, h["chunk"])
    engine = PagedDecodeEngine(
        steps, bundle.init_paged_pool, batch, num_blocks=num_blocks,
        block_size=block, max_seq=cfg.max_seq, chunk=h["chunk"],
        n_stripes=ctx.tp, time_fn=time.perf_counter)
    return Served(engine, steps, params, ctx)


def warm_up_shapes(served: Served):
    """Compile (or load from the cache) the step programs and the engine's
    argmax, at the cell's shapes, with every slot idle, in the variants
    the engine meets: the first tick (a prefill) runs the C=chunk program
    on the pool as the engine makes it, every later tick a program on a
    pool as a step returns it, which is placed differently.  The engine
    keeps one pool; each call replaces it, as a tick does."""
    import jax
    import jax.numpy as jnp

    eng = served.engine
    b = eng.batch
    idle = np.zeros(b, np.int32)
    tables = np.zeros((b, eng.kv.max_blocks), np.int32)
    chunk = max(served.steps.jits)
    for c in (chunk, chunk, 1):
        logits, eng.pool = served.steps.jits[c](
            served.params, jnp.asarray(np.zeros((b, c), np.int32)), eng.pool,
            jnp.asarray(tables), jnp.asarray(idle), jnp.asarray(idle))
        np.asarray(jnp.argmax(logits, axis=-1))
        del logits
    jax.block_until_ready(eng.pool)


# -- the run ----------------------------------------------------------------
@dataclasses.dataclass
class Record:
    """What a run leaves for the metric readers and the check."""
    cell: Cell
    seed: int
    shape: object
    peaks: object
    chips: int
    chunk: int
    setup_s: float = 0.0
    t0: float = 0.0                       # window start (host clock, s)
    t1: float = 0.0                       # window end
    ticks: list = dataclasses.field(default_factory=list)   # (start, end, C)
    token_times: dict = dataclasses.field(default_factory=dict)
    requests: dict = dataclasses.field(default_factory=dict)  # uid -> Item
    due: dict = dataclasses.field(default_factory=dict)       # uid -> s
    finished: list = dataclasses.field(default_factory=list)
    calls: list = dataclasses.field(default_factory=list)   # (C, slots)
    trace: object = None
    lateness: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    queued: list = dataclasses.field(default_factory=list)  # at t0, t1
    window_first_tick: int = 0
    attempted: int = 0
    truncated: int = 0


def profile_options():
    """Device ops and the harness's spans; no Python call tracing, whose
    cost would land in the host time the trace measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class Compiles:
    """Counts backend compiles and persistent-cache lookups."""

    def __init__(self):
        import jax

        self.n = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda ev, **_: self.n.update([ev]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, d, **_: self.n.update([ev]))

    def total(self) -> int:
        return self.n[COMPILE_EVENT] + sum(self.n[e] for e in CACHE_EVENTS)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start,
        shape, peaks, make_ctx=None, wrap_steps=None) -> tuple:
    """Set up, warm up, serve the window; returns (record, served)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.traffic import Generator
    from repro.serve.engine import Request

    compiles = Compiles()
    served = build(cell, seed, make_ctx)
    if wrap_steps is not None:
        wrap_steps(served)
    warm_up_shapes(served)
    eng = served.engine
    h = cell.config["harness"]
    rec = Record(cell, seed, shape, peaks, cell.chips, h["chunk"])
    mix = cell.mix
    gen = Generator(mix, seed, shape.vocab, eng.batch)
    clock = time.perf_counter
    closed = mix["loop"] == "closed"

    def submit(item, due):
        rec.requests[item.uid] = item
        rec.due[item.uid] = due
        rec.token_times[item.uid] = []
        eng.submit(Request(uid=item.uid, prompt=item.prompt,
                           max_new=item.max_new, t_submit=due))

    pending = collections.deque()

    def submit_due(now):
        while pending and t_sched + pending[0].due <= now:
            it = pending.popleft()
            submit(it, t_sched + it.due)
            rec.lateness.append(now - (t_sched + it.due))

    seen = {}

    def tick(i):
        t = clock()
        n_calls = len(served.steps.calls)
        with TraceAnnotation("bench.tick", tick=i):
            _, fin = eng.step()
        t_end = clock()
        with TraceAnnotation("bench.client"):
            c = (served.steps.calls[-1][0]
                 if len(served.steps.calls) > n_calls else 0)
            rec.ticks.append((t, t_end, c))
            for r in [s for s in eng.slots if s is not None] + fin:
                n = len(r.tokens)
                if n > seen.get(r.uid, 0):
                    rec.token_times[r.uid].append(t_end)
                    seen[r.uid] = n
            for r in fin:
                rec.finished.append(r)
                rec.truncated += bool(r.truncated)
                if closed:
                    submit(gen.next_request(rec.requests[r.uid].client),
                           t_end)
        return t_end

    # traffic warm-up: the first prefills (closed loop) or the schedule's
    # first warmup_s seconds (open loop); set-up ends at the first timed
    # step
    i = 0
    if closed:
        t_sched = clock()
        for item in gen.first_requests():
            submit(item, t_sched)
        firsts = set(rec.requests)
        while any(not rec.token_times[u] for u in firsts):
            tick(i)
            i += 1
    else:
        warm = float(mix["warmup_s"])
        pending.extend(gen.schedule(warm + seconds + 60.0))
        t_sched = clock()
        while clock() < t_sched + warm:
            submit_due(clock())
            if eng._pending():
                tick(i)
                i += 1
            else:
                time.sleep(0.0005)
    rec.t0 = clock()
    rec.setup_s = rec.t0 - t_start
    rec.queued = [len(eng.queue)]
    n_compiles = compiles.total()
    t_end_target = rec.t0 + seconds
    first_tick = len(rec.ticks)
    trace_dir = None
    trace_from = rec.t0 + max(0.0, (seconds - TRACE_SECONDS) / 2)
    tracing = False
    window_span = None
    while True:
        now = clock()
        if trace and trace_dir is None and now >= trace_from:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
            window_span = TraceAnnotation("bench.trace_window")
            window_span.__enter__()
            tracing = True
        if tracing and now >= trace_from + TRACE_SECONDS:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if now >= t_end_target:
            break
        submit_due(now)
        if eng._pending():
            rec.t1 = tick(i)
            i += 1
        else:
            time.sleep(0.0005)
    if tracing:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    rec.t1 = max(rec.t1, clock())
    rec.queued.append(len(eng.queue))
    rec.compiles_in_window = compiles.total() - n_compiles
    rec.attempted = len(rec.requests)
    if trace_dir is not None:
        from bench.xplane import load_trace

        rec.trace = load_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # work counts of every step call, read back after the window
    rec.calls = [(c, [(int(p), int(n)) for p, n in
                      zip(np.asarray(pos), np.asarray(nn)) if n > 0])
                 for c, pos, nn in served.steps.calls]
    rec.window_first_tick = first_tick
    return rec, served


def release(served: Served):
    """Drop the program's state so the reference has the device."""
    import jax

    served.engine.pool = None
    served.steps.params = None
    served.params = None
    served.engine = None
    gc.collect()
    jax.clear_caches()
    gc.collect()

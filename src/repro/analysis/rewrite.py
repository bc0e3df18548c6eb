"""Automatic fusion pass: score comm-graph matches, rewrite the winners.

Scoring mirrors the fused-op call sites exactly — same ``resolve_overlap``
/ ``tune_*`` invocations (so decisions land in, and are served from, the
same autotune cache the hand-fused path uses), same per-axis hardware
resolution, same degradation-quarantine keys.  A site is rewritten only
when every gate passes:

  * the family's ``FusionConfig.fuse_*`` flag is on,
  * the collective rings over the axis the fused op supports,
  * the chunked dimension divides the ring (indivisible shapes stay bulk),
  * the ``(op, shape)`` key is not quarantined by the degradation policy,
  * a pinned fp8 wire is only honored on fp8-capable links,
  * the alpha-beta model projects a win (fused < bulk).

The rewrite itself is an interpreter over the traced jaxpr.  Matched
``shard_map`` equations are replaced by calls to the *actual* fused-op
wrappers (``matmul_allreduce``/``allgather_matmul``/...) under a
mode="fused" context — bit-identity with the hand-fused path holds by
construction because it *is* the hand-fused path.  The MoE body (whose
routing config is not recoverable from the jaxpr) is instead rebuilt as a
shard_map interpreting the original body with the two all_to_alls
replaced by ``direct_all_to_all_compute``; the expert-FFN chain between
them is re-played per destination so each output block ships the moment
it is computed (the paper's GEMM+A2A fusion).  Containers on the path to
a rewritten site (``scan``/``remat2``/``jit``) are rebuilt around the
interpreted body; everything untouched binds verbatim.

The interpreter must run under ``jax.jit`` (shard_map bodies cannot be
evaluated eagerly) — both launchers and ``auto_fuse`` arrange that.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax import lax
from jax._src import core as jcore

from repro.analysis import commgraph as cg
from repro.compat import shard_map
from repro.core.autotune import (resolve_overlap, tune_all_to_all,
                                 tune_allgather_matmul, tune_matmul_allreduce)
from repro.core.collectives import direct_all_to_all_compute, wire_itemsize
from repro.core.degrade import is_quarantined
from repro.core.perfmodel import model_pair
from repro.parallel.sharding import ParallelContext


@dataclasses.dataclass
class SiteReport:
    """One line of the ``--explain-comm`` report."""

    family: str
    path: str
    axes: tuple[str, ...]
    shapes: tuple[tuple[int, ...], ...]
    fusible: bool
    rewritten: bool
    reason: str = ""
    bulk_us: float | None = None
    fused_us: float | None = None
    q: int | None = None
    wire: str | None = None
    kernel: str = ""

    @property
    def savings_pct(self) -> float | None:
        if self.bulk_us and self.fused_us is not None:
            return 100.0 * (self.bulk_us - self.fused_us) / self.bulk_us
        return None


@dataclasses.dataclass
class FusionPlan:
    """Rewrite actions keyed by equation identity, plus the per-site
    reports.  Holds the traced ``ClosedJaxpr`` so ``id(eqn)`` keys stay
    valid for the plan's lifetime."""

    closed: Any
    actions: dict[int, Any]
    rebuild: set[int]
    reports: list[SiteReport]

    @property
    def n_rewritten(self) -> int:
        return sum(1 for r in self.reports if r.rewritten)


# ---------------------------------------------------------------------------
# scoring (mirrors the wrapper call sites term for term)
# ---------------------------------------------------------------------------
def _itemsize(site, pos_key: str) -> int:
    return site.eqn.invars[site.detail[pos_key]].aval.dtype.itemsize


def _gate_common(site, ctx, *, flag: str, op: str, key_shape) -> str:
    fused = dataclasses.replace(ctx.fusion, mode="fused")
    if fused.resolve(flag) != "fused":
        return f"disabled by FusionConfig.fuse_{flag}"
    if is_quarantined(op, key_shape):
        return f"quarantined by the degradation policy ({op})"
    return ""


def _wire_gate(ctx, axis) -> str:
    if ctx.fusion.wire == "fp8" and not ctx.hw_for(axis).fp8_wire:
        return "wire constraint: fp8 payload pinned on a non-fp8 link"
    return ""


def _score_allgather_matmul(site, ctx) -> SiteReport:
    f = ctx.fusion
    x = site.in_shapes[site.detail["x_pos"]]
    w = site.in_shapes[site.detail["w_pos"]]
    b, s, k = x
    nout = w[1]
    axis, n = ctx.tp_axis, ctx.tp
    rpt = SiteReport(site.family, site.pathstr, site.axes, (x, w),
                     fusible=False, rewritten=False)
    if site.axes != (axis,):
        rpt.reason = f"unsupported axis: rings over {site.axes}, fused op " \
                     f"supports the tp axis {axis!r}"
        return rpt
    reason = (_gate_common(site, ctx, flag="ag_matmul",
                           op="allgather_matmul", key_shape=x + w)
              or _wire_gate(ctx, axis))
    if reason:
        rpt.reason = reason
        return rpt
    if s % n:
        rpt.reason = f"indivisible shape: seq {s} does not split over {n}"
        return rpt
    ds = _itemsize(site, "x_pos")
    dec = resolve_overlap(
        None, f.granularity, None, f.wire,
        lambda fq, wr: tune_allgather_matmul(
            b, s // n, k, nout // n, dtype_bytes=ds, n_dev=n, hw=ctx.hw,
            axis=axis, skew=f.skew, wire=wr, fixed_q=fq),
        dim=s // n, ring=1)
    flops = 2.0 * b * (s // n) * n * k * (nout // n)
    hbm = float(k * (nout // n) * ds)
    wire_b = float(b * (s // n) * k * ds) * (n - 1)
    return _finish(rpt, ctx, axis, dec, flops, hbm, wire_b, n * dec.q, ds)


def _score_matmul_reducescatter(site, ctx) -> SiteReport:
    f = ctx.fusion
    x = site.in_shapes[site.detail["x_pos"]]
    w = site.in_shapes[site.detail["w_pos"]]
    b, s, k = x
    nout = w[1]
    axis, n = ctx.tp_axis, ctx.tp
    rpt = SiteReport(site.family, site.pathstr, site.axes, (x, w),
                     fusible=False, rewritten=False)
    if site.axes != (axis,):
        rpt.reason = f"unsupported axis: rings over {site.axes}, fused op " \
                     f"supports the tp axis {axis!r}"
        return rpt
    reason = (_gate_common(site, ctx, flag="matmul_rs",
                           op="matmul_reducescatter", key_shape=x + w)
              or _wire_gate(ctx, axis))
    if reason:
        rpt.reason = reason
        return rpt
    if s % n:
        rpt.reason = f"indivisible shape: seq {s} does not split over {n}"
        return rpt
    ds = _itemsize(site, "x_pos")
    dec = resolve_overlap(
        None, f.granularity, None, f.wire,
        lambda fq, wr: tune_matmul_allreduce(
            b * s, k // n, nout, dtype_bytes=ds, n_dev=n, chunk_dim=s,
            allgather_phase=False, hw=ctx.hw, axis=axis, skew=f.skew,
            wire=wr, fixed_q=fq),
        dim=s, ring=n)
    flops = 2.0 * (b * s) * (k // n) * nout
    hbm = float((k // n) * nout * ds)
    wire_b = float(b * s * nout * ds)
    return _finish(rpt, ctx, axis, dec, flops, hbm, wire_b, n * dec.q, ds)


def _score_matmul_allreduce(site, ctx) -> SiteReport:
    f = ctx.fusion
    x = site.in_shapes[site.detail["x_pos"]]
    w = site.in_shapes[site.detail["w_pos"]]
    rows, k = x
    nout = w[1]
    axis, n = ctx.tp_axis, ctx.tp
    rpt = SiteReport(site.family, site.pathstr, site.axes, (x, w),
                     fusible=False, rewritten=False)
    if site.axes != (axis,):
        rpt.reason = f"unsupported axis: psum over {site.axes}, fused op " \
                     f"supports the tp axis {axis!r}"
        return rpt
    reason = (_gate_common(site, ctx, flag="matmul_rs",
                           op="matmul_allreduce", key_shape=(rows, k, nout))
              or _wire_gate(ctx, axis))
    if reason:
        rpt.reason = reason
        return rpt
    dp = ctx.dp if rows % ctx.dp == 0 else 1
    rows_local = rows // dp
    use_rows = rows_local % n == 0 and rows_local >= n
    chunk_dim = rows_local if use_rows else nout
    if chunk_dim % n:
        rpt.reason = (f"indivisible shape: neither rows {rows_local} nor "
                      f"cols {nout} split over the {n}-rank ring")
        return rpt
    ds = _itemsize(site, "x_pos")
    dec = resolve_overlap(
        None, f.granularity, None, f.wire,
        lambda fq, wr: tune_matmul_allreduce(
            rows_local, k // n, nout, dtype_bytes=ds, n_dev=n,
            chunk_dim=chunk_dim, hw=ctx.hw, axis=axis, skew=f.skew,
            wire=wr, fixed_q=fq),
        dim=chunk_dim, ring=n)
    flops = 2.0 * rows_local * (k // n) * nout
    hbm = float((k // n) * nout * ds)
    wire_b = float(rows_local * nout * ds) * 2.0
    return _finish(rpt, ctx, axis, dec, flops, hbm, wire_b, n * dec.q, ds)


def _score_embedding(site, ctx) -> SiteReport:
    f = ctx.fusion
    idx = site.in_shapes[site.detail["indices_pos"]]
    tab = site.in_shapes[site.detail["tables_pos"]]
    B, T, L = idx
    D = tab[2]
    world_axes = tuple(ctx.dp_axes) + (ctx.tp_axis,)
    n = ctx.world
    rpt = SiteReport(site.family, site.pathstr, site.axes, (idx, tab),
                     fusible=False, rewritten=False)
    reason = (_gate_common(site, ctx, flag="embed_a2a",
                           op="embedding_a2a", key_shape=idx + tab)
              or _wire_gate(ctx, world_axes))
    if reason:
        rpt.reason = reason
        return rpt
    if B % n or T % n:
        rpt.reason = (f"indivisible shape: batch {B} / tables {T} do not "
                      f"split over the {n}-rank world")
        return rpt
    ds = _itemsize(site, "tables_pos")
    t_loc = T // n
    dec = resolve_overlap(
        None, f.granularity, None, f.wire,
        lambda fq, wr: tune_all_to_all(
            (B // n) * t_loc * D, float((B // n) * t_loc * L * D),
            dtype_bytes=ds, n_dev=n, sub_dim=B // n, hw=ctx.hw,
            axis=world_axes, skew=f.skew_world, wire=wr, fixed_q=fq),
        dim=B // n, ring=1)
    chunk = (B // n) * t_loc * D
    flops = float((B // n) * t_loc * L * D) * n
    hbm = float(chunk * ds * n)
    wire_b = float(chunk * ds) * (n - 1)
    return _finish(rpt, ctx, world_axes, dec, flops, hbm, wire_b,
                   n * dec.q, ds)


def _moe_kernel_note(ctx, key_shape) -> str:
    """Device-initiated dispatch-kernel availability for an MoE site:
    mesh-shape gate (the interpreter maps multi-axis meshes through the
    flattened world), degradation quarantine, and the wire constraint
    (the PUT payload has no per-chunk fp8 scale — fp8 clamps to bf16)."""
    from repro.kernels.fused_dispatch_a2a.ops import (
        fused_dispatch_a2a_kernel_available)
    if not fused_dispatch_a2a_kernel_available(ctx.mesh):
        return "unavailable — interpret mode needs a known mesh shape"
    if is_quarantined("moe_a2a_kernel", key_shape):
        return "unavailable — quarantined by the degradation policy"
    note = ("available — device-initiated dispatch PUT ring chained "
            "with the FFN+combine kernel (mode='kernel')")
    if ctx.fusion.wire == "fp8":
        note += "; wire='fp8' clamps to bf16 on the PUT payload"
    return note


def _score_moe(site, ctx) -> SiteReport:
    n_ring, e_loc, cap, d = site.detail["buf_shape"]
    d_ff = site.detail["d_ff"] or d
    axis, n = ctx.tp_axis, ctx.tp
    rpt = SiteReport(site.family, site.pathstr, site.axes, site.in_shapes,
                     fusible=False, rewritten=False)
    rpt.kernel = _moe_kernel_note(ctx, (n_ring, e_loc, cap, d))
    reason = (_gate_common(site, ctx, flag="moe_a2a", op="moe_a2a",
                           key_shape=(n_ring, e_loc, cap, d))
              or _wire_gate(ctx, axis))
    if reason:
        rpt.reason = reason
        return rpt
    if n_ring != n:
        rpt.reason = (f"unsupported axis: dispatch buffer splits {n_ring} "
                      f"ways, tp ring is {n}")
        return rpt
    ds = site.eqn.invars[0].aval.dtype.itemsize
    chunk = e_loc * cap * d
    flops = 6.0 * e_loc * cap * d * d_ff  # gate+up+down GEMMs per dest
    hbm = float(chunk * ds * n)
    wire_b = 2.0 * float(chunk * ds) * (n - 1)  # dispatch + combine
    # the MoE A2As ship whole per-destination blocks (no sub-chunking or
    # wire compression in the hand-fused op, so none here either)
    bulk_t, fused_t = model_pair(flops * n, hbm, wire_b, n,
                                 hw=ctx.hw, axis=axis)
    rpt.fusible = True
    rpt.bulk_us, rpt.fused_us = bulk_t * 1e6, fused_t * 1e6
    rpt.q, rpt.wire = 1, "f32"
    if fused_t >= bulk_t:
        rpt.fusible = False
        rpt.reason = "modeled no win: fused time >= bulk at this shape"
    return rpt


def _finish(rpt, ctx, axis, dec, flops, hbm, wire_b, chunks, ds) -> SiteReport:
    factor = wire_itemsize(dec.wire, ds) / float(ds)
    bulk_t, fused_t = model_pair(flops, hbm, wire_b, chunks,
                                 wire_factor=factor, hw=ctx.hw, axis=axis)
    rpt.fusible = True
    rpt.bulk_us, rpt.fused_us = bulk_t * 1e6, fused_t * 1e6
    rpt.q, rpt.wire = dec.q, dec.wire
    if fused_t >= bulk_t:
        rpt.fusible = False
        rpt.reason = "modeled no win: fused time >= bulk at this shape"
    return rpt


_SCORERS: dict[str, Callable] = {
    cg.ALLGATHER_MATMUL: _score_allgather_matmul,
    cg.MATMUL_REDUCESCATTER: _score_matmul_reducescatter,
    cg.MATMUL_ALLREDUCE: _score_matmul_allreduce,
    cg.EMBEDDING_A2A: _score_embedding,
    cg.MOE_DISPATCH_COMBINE: _score_moe,
}


# ---------------------------------------------------------------------------
# rewrite actions
# ---------------------------------------------------------------------------
class _WrapperCall:
    """Replace a whole matched shard_map eqn with a call to the real
    fused-op wrapper under a mode="fused" context — the same code path,
    tuner keys and degrade keys as hand-written fused model code."""

    def __init__(self, fn, arg_positions, fctx):
        self.fn, self.arg_positions, self.fctx = fn, arg_positions, fctx

    def apply(self, invals):
        return [self.fn(self.fctx, *(invals[p] for p in self.arg_positions))]


class _MoeRewrite:
    """Rebuild the MoE shard_map with the dispatch/combine all_to_alls
    replaced by per-destination direct sends; the FFN chain between them
    is replayed per destination (sunk into the combine producer)."""

    def __init__(self, site, fctx):
        self.site, self.fctx = site, fctx
        self.body = site.detail["body"]
        self.sink = _plan_sink(self.body.jaxpr, site.detail["dispatch"],
                               site.detail["combine"])

    def apply(self, invals):
        eqn = self.site.eqn
        in_specs = eqn.params["in_specs"]
        out_specs = eqn.params["out_specs"]
        single = len(eqn.outvars) == 1

        def local_fn(*largs):
            outs = _eval_moe_body(self.body, largs, self.site.detail,
                                  self.sink, self.fctx)
            return outs[0] if single else tuple(outs)

        out = shard_map(local_fn, mesh=self.fctx.mesh, in_specs=in_specs,
                        out_specs=out_specs[0] if single else out_specs,
                        check_vma=False)(*invals)
        return [out] if single else list(out)


# -- combine-producer sinking ------------------------------------------------
@dataclasses.dataclass
class _SinkPlan:
    ok: bool
    chain: tuple[int, ...] = ()      # body eqn indices feeding the combine
    why: str = ""


# replay-safe primitives: shape-polymorphic under a size-1 slice of the
# tracked (per-destination) dimension
_SLICE_POLY = frozenset({
    "dot_general", "transpose", "broadcast_in_dim", "convert_element_type",
    "add", "sub", "mul", "div", "max", "min", "pow", "neg", "exp", "log",
    "tanh", "logistic", "sign", "integer_pow", "select_n", "custom_jvp_call",
    "jit",
})


def _track_through(eqns, in_dims: dict) -> "dict | None":
    """Propagate the tracked (destination) dim through a chain of eqns.
    ``in_dims`` maps Var -> dim index; returns the extended map, or None
    when any eqn cannot be replayed shape-polymorphically."""
    dims = dict(in_dims)
    for eqn in eqns:
        nm = eqn.primitive.name
        tracked = [(i, dims[v]) for i, v in enumerate(eqn.invars)
                   if isinstance(v, jcore.Var) and v in dims]
        if not tracked:
            continue
        if nm not in _SLICE_POLY:
            return None
        if nm == "dot_general":
            if len(tracked) != 1:
                return None
            pos, t = tracked[0]
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            if pos == 0:
                if t in lc or t in lb:
                    return None
                free = [i for i in range(len(lhs.shape))
                        if i not in lc and i not in lb]
                out_t = len(lb) + free.index(t)
            else:
                if t in rc or t in rb:
                    return None
                lfree = [i for i in range(len(lhs.shape))
                         if i not in lc and i not in lb]
                rfree = [i for i in range(len(rhs.shape))
                         if i not in rc and i not in rb]
                out_t = len(lb) + len(lfree) + rfree.index(t)
            dims[eqn.outvars[0]] = out_t
        elif nm == "transpose":
            pos, t = tracked[0]
            dims[eqn.outvars[0]] = eqn.params["permutation"].index(t)
        elif nm == "broadcast_in_dim":
            pos, t = tracked[0]
            dims[eqn.outvars[0]] = eqn.params["broadcast_dimensions"][t]
        elif nm in ("jit", "custom_jvp_call"):
            sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            sub_dims = {}
            for i, v in enumerate(eqn.invars):
                if isinstance(v, jcore.Var) and v in dims:
                    sub_dims[sub.jaxpr.invars[i]] = dims[v]
            inner = _track_through(sub.jaxpr.eqns, sub_dims)
            if inner is None:
                return None
            for ov, bv in zip(eqn.outvars, sub.jaxpr.outvars):
                if isinstance(bv, jcore.Var) and bv in inner:
                    dims[ov] = inner[bv]
        else:
            # elementwise: every non-scalar operand must carry the same
            # tracked dim (lax elementwise ops do not broadcast)
            t0 = tracked[0][1]
            for i, v in enumerate(eqn.invars):
                if isinstance(v, jcore.Var) and len(v.aval.shape):
                    if v not in dims or dims[v] != t0:
                        return None
            for ov in eqn.outvars:
                dims[ov] = t0
    return dims


def _plan_sink(body, dispatch_idx: int, combine_idx: int) -> _SinkPlan:
    recv = body.eqns[dispatch_idx].outvars[0]
    y = body.eqns[combine_idx].invars[0]
    split_axis = body.eqns[combine_idx].params["split_axis"]
    downstream = {recv}
    chain = []
    for i in range(dispatch_idx + 1, combine_idx):
        eqn = body.eqns[i]
        if any(isinstance(v, jcore.Var) and v in downstream
               for v in eqn.invars):
            chain.append(i)
            downstream.update(eqn.outvars)
    # chain values must not escape: anything outside the chain (or the
    # combine itself) reading them would go uncomputed after sinking
    chain_set = set(chain)
    for i, eqn in enumerate(body.eqns):
        if i in chain_set or i == dispatch_idx or i == combine_idx:
            continue
        for v in eqn.invars:
            if isinstance(v, jcore.Var) and v in downstream:
                return _SinkPlan(False, why="chain value escapes")
    for v in body.outvars:
        if isinstance(v, jcore.Var) and v in downstream:
            return _SinkPlan(False, why="chain value escapes to outputs")
    dims = _track_through([body.eqns[i] for i in chain], {recv: 0})
    if dims is None:
        return _SinkPlan(False, why="chain not slice-polymorphic")
    if dims.get(y) != split_axis:
        return _SinkPlan(False, why="tracked dim does not reach split axis")
    return _SinkPlan(True, chain=tuple(chain))


def _replay_eqn(eqn, invals):
    """Bind one chain eqn with per-destination (size-1 tracked dim)
    operands.  jit/custom_jvp bodies are inlined (their stored jaxprs
    carry baked full-size avals); broadcast_in_dim re-derives its shape
    from the live operand; everything else is shape-polymorphic."""
    nm = eqn.primitive.name
    if nm in ("jit", "custom_jvp_call"):
        sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
        return _replay_jaxpr(sub.jaxpr, sub.consts, invals)
    if nm == "broadcast_in_dim":
        shape = list(eqn.params["shape"])
        for i, bd in enumerate(eqn.params["broadcast_dimensions"]):
            shape[bd] = invals[0].shape[i]
        out = lax.broadcast_in_dim(
            invals[0], tuple(shape), eqn.params["broadcast_dimensions"])
        return [out]
    subfuns, bp = eqn.primitive.get_bind_params(eqn.params)
    ans = eqn.primitive.bind(*subfuns, *invals, **bp)
    return list(ans) if eqn.primitive.multiple_results else [ans]


def _replay_jaxpr(jaxpr, consts, args):
    env = {}
    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for eqn in jaxpr.eqns:
        outs = _replay_eqn(eqn, [read(v) for v in eqn.invars])
        for ov, o in zip(eqn.outvars, outs):
            env[ov] = o
    return [read(v) for v in jaxpr.outvars]


def _eval_moe_body(closed, args, detail, sink: _SinkPlan, fctx):
    """Interpret the MoE shard_map body with fused dispatch/combine."""
    jaxpr = closed.jaxpr
    di, ci = detail["dispatch"], detail["combine"]
    axis = detail["axis"]
    schedule = fctx.fusion.schedule
    env: dict = {}
    for v, c in zip(jaxpr.constvars, closed.consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    skip = set(sink.chain) if sink.ok else set()
    for i, eqn in enumerate(jaxpr.eqns):
        if i in skip:
            continue
        if i == di:
            buf = read(eqn.invars[0])

            def produce_d(dest):
                return lax.dynamic_index_in_dim(buf, dest, axis=0,
                                                keepdims=False)

            recv = direct_all_to_all_compute(
                produce_d, jax.ShapeDtypeStruct(buf.shape[1:], buf.dtype),
                axis, schedule=schedule)
            env[eqn.outvars[0]] = recv
            continue
        if i == ci:
            if sink.ok:
                # replay the FFN chain per destination so each output
                # block is produced right before its direct send
                recv_var = jaxpr.eqns[di].outvars[0]
                y_var = eqn.invars[0]
                chain_eqns = [jaxpr.eqns[j] for j in sink.chain]
                recv_full = env[recv_var]
                chunk_shape = tuple(s for d, s in
                                    enumerate(y_var.aval.shape) if d != 0)

                def produce_c(dest):
                    local = {recv_var: lax.dynamic_slice_in_dim(
                        recv_full, dest, 1, axis=0)}

                    def rd(v):
                        if isinstance(v, jcore.Literal):
                            return v.val
                        return local[v] if v in local else env[v]

                    for ce in chain_eqns:
                        outs = _replay_eqn(ce, [rd(v) for v in ce.invars])
                        for ov, o in zip(ce.outvars, outs):
                            local[ov] = o
                    return lax.squeeze(local[y_var], dimensions=(0,))
            else:
                y_full = read(eqn.invars[0])
                chunk_shape = tuple(y_full.shape[1:])

                def produce_c(dest):
                    return lax.dynamic_index_in_dim(y_full, dest, axis=0,
                                                    keepdims=False)

            comb = direct_all_to_all_compute(
                produce_c,
                jax.ShapeDtypeStruct(chunk_shape,
                                     eqn.invars[0].aval.dtype),
                axis, schedule=schedule)
            env[eqn.outvars[0]] = comb
            continue
        subfuns, bp = eqn.primitive.get_bind_params(eqn.params)
        ans = eqn.primitive.bind(*subfuns, *[read(v) for v in eqn.invars],
                                 **bp)
        outs = list(ans) if eqn.primitive.multiple_results else [ans]
        for ov, o in zip(eqn.outvars, outs):
            env[ov] = o
    return [read(v) for v in jaxpr.outvars]


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------
def plan_rewrites(graph: cg.CommGraph, ctx: ParallelContext) -> FusionPlan:
    """Score every site of ``graph``; build actions for the winners."""
    from repro.core.allgather_matmul import (allgather_matmul,
                                             matmul_reducescatter)
    from repro.core.embedding_all_to_all import embedding_all_to_all
    from repro.core.matmul_allreduce import matmul_allreduce

    fctx = ctx.with_fusion(dataclasses.replace(ctx.fusion, mode="fused"))
    actions: dict[int, Any] = {}
    rebuild: set[int] = set()
    reports: list[SiteReport] = []
    wrappers = {
        cg.ALLGATHER_MATMUL: allgather_matmul,
        cg.MATMUL_REDUCESCATTER: matmul_reducescatter,
        cg.MATMUL_ALLREDUCE: matmul_allreduce,
    }
    for site in graph.sites:
        scorer = _SCORERS.get(site.family)
        if scorer is None:
            reports.append(SiteReport(
                site.family, site.pathstr, site.axes, site.in_shapes,
                fusible=False, rewritten=False,
                reason=site.detail.get("why", "")))
            continue
        rpt = scorer(site, ctx)
        if rpt.fusible and not site.rewritable:
            opaque = [c.primitive.name for c in site.containers
                      if c.primitive.name not in cg.REBUILDABLE_CONTAINERS]
            rpt.fusible = False
            rpt.reason = (f"inside a {opaque[0]} boundary — container "
                          "cannot be rebuilt")
        if rpt.fusible:
            if site.family in wrappers:
                pos = (site.detail["x_pos"], site.detail["w_pos"])
                actions[id(site.eqn)] = _WrapperCall(
                    wrappers[site.family], pos, fctx)
            elif site.family == cg.EMBEDDING_A2A:
                pos = (site.detail["indices_pos"],
                       site.detail["tables_pos"])
                actions[id(site.eqn)] = _WrapperCall(
                    embedding_all_to_all, pos, fctx)
            else:
                actions[id(site.eqn)] = _MoeRewrite(site, fctx)
            rpt.rewritten = True
            for c in site.containers:
                rebuild.add(id(c))
        reports.append(rpt)
    return FusionPlan(closed=graph.closed, actions=actions,
                      rebuild=rebuild, reports=reports)


# ---------------------------------------------------------------------------
# plan execution (the rewritten step)
# ---------------------------------------------------------------------------
def _rebuild_container(eqn, invals, plan, fctx):
    nm = eqn.primitive.name
    if nm == "jit":
        closed = eqn.params["jaxpr"]
        return _eval_jaxpr(closed.jaxpr, closed.consts, invals, plan, fctx)
    if nm in ("remat2", "checkpoint"):
        jx = eqn.params["jaxpr"]
        consts = ()
        if isinstance(jx, jcore.ClosedJaxpr):
            jx, consts = jx.jaxpr, jx.consts

        def fn(*a):
            return tuple(_eval_jaxpr(jx, consts, a, plan, fctx))

        out = jax.checkpoint(fn, policy=eqn.params.get("policy"),
                             prevent_cse=eqn.params.get("prevent_cse", True))(
            *invals)
        return list(out)
    if nm == "scan":
        closed = eqn.params["jaxpr"]
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        consts_v = tuple(invals[:nc])
        init = tuple(invals[nc:nc + ncar])
        xs = tuple(invals[nc + ncar:])

        def body_fn(carry, x):
            outs = _eval_jaxpr(closed.jaxpr, closed.consts,
                               list(consts_v) + list(carry) + list(x),
                               plan, fctx)
            return tuple(outs[:ncar]), tuple(outs[ncar:])

        carry_out, ys = lax.scan(body_fn, init, xs,
                                 length=eqn.params["length"],
                                 reverse=eqn.params["reverse"],
                                 unroll=eqn.params.get("unroll", 1))
        return list(carry_out) + list(ys)
    raise NotImplementedError(
        f"cannot rebuild a {nm} container around a rewritten site")


def _eval_jaxpr(jaxpr, consts, args, plan: FusionPlan, fctx):
    env: dict = {}
    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        act = plan.actions.get(id(eqn))
        if act is not None:
            outs = act.apply(invals)
        elif id(eqn) in plan.rebuild:
            outs = _rebuild_container(eqn, invals, plan, fctx)
        else:
            subfuns, bp = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *invals, **bp)
            outs = list(ans) if eqn.primitive.multiple_results else [ans]
        for ov, o in zip(eqn.outvars, outs):
            env[ov] = o
    return [read(v) for v in jaxpr.outvars]


def run_plan(plan: FusionPlan, ctx: ParallelContext, flat_args):
    """Evaluate the planned rewrite over flat arguments (jit-traced)."""
    closed = plan.closed
    return _eval_jaxpr(closed.jaxpr, closed.consts, flat_args, plan, ctx)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------
def auto_fuse(ctx: ParallelContext, fn, *, reports: "list | None" = None):
    """Wrap ``fn`` (a loss/decode callable whose collectives trace bulk —
    ``FusionConfig(mode="auto")`` arranges that) so matched subgraphs run
    through the fused ops.  Tracing/planning happens once per distinct
    argument structure; the wrapped callable is differentiable and must
    run under ``jax.jit``.  ``reports`` (optional list) receives the
    per-trace ``list[SiteReport]`` for introspection."""
    cache: dict = {}

    def wrapped(*args):
        import numpy as np

        leaves, treedef = jax.tree.flatten(args)
        key = (treedef,
               tuple((tuple(np.shape(l)), str(np.result_type(l)))
                     for l in leaves))
        entry = cache.get(key)
        if entry is None:
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
            graph = cg.build_comm_graph(closed, ctx)
            plan = plan_rewrites(graph, ctx)
            out_tree = jax.tree.structure(out_shape)
            cache[key] = entry = (plan, out_tree)
            if reports is not None:
                reports.append(plan.reports)
        plan, out_tree = entry
        out_flat = run_plan(plan, ctx, leaves)
        return jax.tree.unflatten(out_tree, out_flat)

    wrapped.cache = cache
    return wrapped

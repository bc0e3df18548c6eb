"""Comm-graph construction: classify collectives in a traced jaxpr.

The walker recurses through every sub-jaxpr a container equation carries
(``jit``/``scan``/``remat2``/``custom_vjp``/``while``/``cond``/...), so
collectives buried inside a remat'd layer stack under ``lax.scan`` are
found at any depth.  Each ``shard_map`` equation is fingerprinted against
the fused-op pattern families this repo implements:

  matmul_allreduce       dot_general -> psum          (row-parallel layer)
  allgather_matmul       all_gather -> dot_general    (SP qkv/up proj)
  matmul_reducescatter   dot_general -> reduce_scatter (SP down proj)
  moe_dispatch_combine   dispatch A2A -> expert FFN -> combine A2A
  embedding_a2a          per-table pooling -> world-axis A2A (DLRM)

plus two recognized-but-not-rewritten classes: bodies already running a
ring schedule (``ppermute`` — the hand-fused ops and the vocab-sharded
CE/embedding rings) and the bulk KV all-gather attention (a ring rewrite
would reassociate the online softmax, so it is opt-in, never automatic).

Classification is deliberately conservative: a body that does not match a
family *exactly* (equation counts, feed edges, collective layout params)
is reported ``unmatched`` rather than guessed at — the rewriter only ever
touches sites whose replacement is bit-identical by construction.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any

from jax._src import core as jcore

from repro.parallel.sharding import ParallelContext

# Collective primitives tracked by the analyzer.  ``pmax``/``pmin`` ride
# along for reporting (the attention stat merge) but match no family.
COLLECTIVE_PRIMS = frozenset({
    "psum", "all_to_all", "all_gather", "reduce_scatter", "psum_scatter",
    "ppermute", "pmax", "pmin",
})

# Containers the rewriter knows how to rebuild around a rewritten site.
REBUILDABLE_CONTAINERS = frozenset({"jit", "scan", "remat2", "checkpoint"})

# family tags
MATMUL_ALLREDUCE = "matmul_allreduce"
ALLGATHER_MATMUL = "allgather_matmul"
MATMUL_REDUCESCATTER = "matmul_reducescatter"
MOE_DISPATCH_COMBINE = "moe_dispatch_combine"
EMBEDDING_A2A = "embedding_a2a"
ALREADY_FUSED = "already_fused"
KV_ALLGATHER = "kv_allgather"
BARE_COLLECTIVE = "bare_collective"
UNMATCHED = "unmatched"

FUSIBLE_FAMILIES = frozenset({
    MATMUL_ALLREDUCE, ALLGATHER_MATMUL, MATMUL_REDUCESCATTER,
    MOE_DISPATCH_COMBINE, EMBEDDING_A2A,
})


@dataclasses.dataclass
class CollectiveSite:
    """One collective occurrence: a ``shard_map`` equation (or a bare
    collective), where it sits, and what family it matched."""

    family: str
    eqn: Any                          # the shard_map / collective eqn
    containers: tuple                 # container eqns from root to site
    path: tuple[str, ...]             # container primitive names
    prims: tuple[tuple[str, int], ...]  # recursive collective histogram
    axes: tuple[str, ...]             # mesh axes the collectives span
    in_shapes: tuple[tuple[int, ...], ...]  # global invar shapes
    rewritable: bool                  # every container can be rebuilt
    detail: dict = dataclasses.field(default_factory=dict)

    @property
    def pathstr(self) -> str:
        return "/".join(self.path) or "top"


@dataclasses.dataclass
class CommGraph:
    """Every collective site of one traced function, in trace order.
    Holds the ``ClosedJaxpr`` so equation identities stay stable for the
    rewrite plan keyed on them."""

    closed: Any
    sites: list[CollectiveSite]

    def families(self) -> Counter:
        return Counter(s.family for s in self.sites)


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------
def sub_jaxprs(eqn) -> list:
    """Every sub-jaxpr an equation's params carry (generic: any
    ``Jaxpr``/``ClosedJaxpr`` value, or tuple thereof — covers jit, scan,
    remat2, shard_map, cond branches, custom_vjp/jvp calls)."""
    out = []
    for v in eqn.params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jcore.ClosedJaxpr):
                    out.append(x.jaxpr)
                elif isinstance(x, jcore.Jaxpr):
                    out.append(x)
    return out


def _axis_tuple(val) -> tuple[str, ...]:
    if val is None:
        return ()
    if isinstance(val, str):
        return (val,)
    return tuple(val)


def collective_axes(eqn) -> tuple[str, ...]:
    """Mesh axes one collective equation runs over (``axes`` for psum-family,
    ``axis_name`` for the permute/gather/a2a family)."""
    p = eqn.params
    return _axis_tuple(p.get("axes", p.get("axis_name")))


def _collect_collectives(jaxpr) -> tuple[Counter, set]:
    """Recursive (collective histogram, axis set) under one jaxpr."""
    prims: Counter = Counter()
    axes: set = set()
    for eqn in jaxpr.eqns:
        nm = eqn.primitive.name
        if nm in COLLECTIVE_PRIMS:
            prims[nm] += 1
            axes.update(collective_axes(eqn))
        for sj in sub_jaxprs(eqn):
            p, a = _collect_collectives(sj)
            prims.update(p)
            axes.update(a)
    return prims, axes


def _body_jaxpr(eqn):
    body = eqn.params["jaxpr"]
    if isinstance(body, jcore.ClosedJaxpr):
        body = body.jaxpr
    return body


def _invar_pos(body, var) -> int:
    for i, v in enumerate(body.invars):
        if v is var:
            return i
    return -1


def _first(body, name):
    for i, e in enumerate(body.eqns):
        if e.primitive.name == name:
            return i, e
    return -1, None


# ---------------------------------------------------------------------------
# shard_map fingerprinting
# ---------------------------------------------------------------------------
def _unmatched(why: str) -> tuple[str, dict]:
    return UNMATCHED, {"why": why}


def _match_allgather_matmul(body, ctx) -> tuple[str, dict]:
    _, ag = _first(body, "all_gather")
    _, dot = _first(body, "dot_general")
    if (ag.params.get("all_gather_dimension") != 1
            or not ag.params.get("tiled", False)):
        return _unmatched("all_gather layout is not the tiled seq-dim "
                          "gather the fused op implements")
    if dot.invars[0] is not ag.outvars[0]:
        return _unmatched("all_gather output does not feed the matmul lhs")
    x_pos = _invar_pos(body, ag.invars[0])
    w_pos = _invar_pos(body, dot.invars[1])
    if x_pos < 0 or w_pos < 0:
        return _unmatched("matmul operands are not shard_map inputs")
    return ALLGATHER_MATMUL, {"x_pos": x_pos, "w_pos": w_pos}


def _match_matmul_reducescatter(body, ctx) -> tuple[str, dict]:
    _, dot = _first(body, "dot_general")
    rs = next((e for e in body.eqns
               if e.primitive.name in ("reduce_scatter", "psum_scatter")), None)
    if (rs.params.get("scatter_dimension") != 1
            or not rs.params.get("tiled", False)):
        return _unmatched("reduce_scatter layout is not the tiled seq-dim "
                          "scatter the fused op implements")
    if rs.invars[0] is not dot.outvars[0]:
        return _unmatched("matmul output does not feed the reduce_scatter")
    x_pos = _invar_pos(body, dot.invars[0])
    w_pos = _invar_pos(body, dot.invars[1])
    if x_pos < 0 or w_pos < 0:
        return _unmatched("matmul operands are not shard_map inputs")
    return MATMUL_REDUCESCATTER, {"x_pos": x_pos, "w_pos": w_pos}


def _match_matmul_allreduce(body, ctx) -> tuple[str, dict]:
    _, dot = _first(body, "dot_general")
    _, ps = _first(body, "psum")
    if ps.invars[0] is not dot.outvars[0]:
        return _unmatched("matmul output does not feed the psum")
    x_pos = _invar_pos(body, dot.invars[0])
    w_pos = _invar_pos(body, dot.invars[1])
    if x_pos < 0 or w_pos < 0:
        return _unmatched("matmul operands are not shard_map inputs")
    return MATMUL_ALLREDUCE, {"x_pos": x_pos, "w_pos": w_pos}


def _a2a_layout_ok(eqn) -> bool:
    p = eqn.params
    return (p.get("split_axis") == 0 and p.get("concat_axis") == 0
            and not p.get("tiled", False)
            and p.get("axis_index_groups") is None)


def _match_moe(eqn, body, ctx) -> tuple[str, dict]:
    a2as = [(i, e) for i, e in enumerate(body.eqns)
            if e.primitive.name == "all_to_all"]
    if len(a2as) != 2:
        return _unmatched(f"{len(a2as)} all_to_alls in an MoE-shaped body "
                          "(expected dispatch + combine)")
    (di, disp), (ci, comb) = a2as
    for e in (disp, comb):
        if not _a2a_layout_ok(e):
            return _unmatched("all_to_all layout is not the leading-axis "
                              "per-destination exchange the fused op "
                              "implements")
        if collective_axes(e) != (ctx.tp_axis,):
            return _unmatched(f"all_to_all rings over "
                              f"{collective_axes(e)}, not the tp axis")
    buf_shape = tuple(disp.invars[0].aval.shape)
    if len(buf_shape) != 4:
        return _unmatched("dispatch payload is not the [n, E_loc, C, D] "
                          "capacity buffer")
    d_ff = 0
    for e in body.eqns[di + 1:ci]:
        if e.primitive.name == "dot_general":
            d_ff = int(e.invars[1].aval.shape[-1])
            break
    return MOE_DISPATCH_COMBINE, {
        "dispatch": di, "combine": ci, "axis": ctx.tp_axis,
        "buf_shape": buf_shape, "d_ff": d_ff,
        "body": jcore.ClosedJaxpr(body, ()),
    }


def _match_embedding(eqn, body, sharded, ctx) -> tuple[str, dict]:
    _, a2a = _first(body, "all_to_all")
    if not _a2a_layout_ok(a2a):
        return _unmatched("all_to_all layout is not the leading-axis "
                          "per-destination exchange the fused op implements")
    world_axes = tuple(ctx.dp_axes) + (ctx.tp_axis,)
    if set(collective_axes(a2a)) != set(world_axes):
        return _unmatched(f"all_to_all rings over {collective_axes(a2a)}, "
                          f"not the flattened world axes {world_axes}")
    if len(eqn.invars) != 2:
        return _unmatched("expected exactly (indices, tables) inputs")
    idx_pos = next((i for i, d in enumerate(sharded) if d == {1}), -1)
    tab_pos = next((i for i, d in enumerate(sharded) if d == {0}), -1)
    if idx_pos < 0 or tab_pos < 0 or idx_pos == tab_pos:
        return _unmatched("input shardings do not match the table-parallel "
                          "embedding layout")
    return EMBEDDING_A2A, {"indices_pos": idx_pos, "tables_pos": tab_pos}


def _classify_shard_map(eqn, ctx, containers, path) -> CollectiveSite:
    body = _body_jaxpr(eqn)
    top = Counter(e.primitive.name for e in body.eqns)
    colls, axes = _collect_collectives(body)
    # per input: the dims its PartitionSpec shards over some mesh axis
    sharded = tuple({i for i, ax in enumerate(spec) if ax is not None}
                    for spec in eqn.params["in_specs"])
    rewritable = all(c.primitive.name in REBUILDABLE_CONTAINERS
                     for c in containers)

    if colls.get("ppermute"):
        family, detail = ALREADY_FUSED, {
            "why": "already fused: body runs a ppermute ring schedule"}
    elif top.get("all_to_all", 0) >= 2 and top.get("top_k", 0) >= 1:
        family, detail = _match_moe(eqn, body, ctx)
    elif (top.get("all_to_all") == 1 and not colls.get("dot_general")
          and "dot_general" not in top
          and len(collective_axes(body.eqns[_first(body, "all_to_all")[0]])) > 1):
        family, detail = _match_embedding(eqn, body, sharded, ctx)
    elif (top.get("all_gather") == 1 and top.get("dot_general") == 1
          and sum(colls.values()) == 1):
        family, detail = _match_allgather_matmul(body, ctx)
    elif (top.get("dot_general") == 1 and sum(colls.values()) == 1
          and (top.get("reduce_scatter", 0) + top.get("psum_scatter", 0)) == 1):
        family, detail = _match_matmul_reducescatter(body, ctx)
    elif (top.get("dot_general") == 1 and top.get("psum") == 1
          and sum(colls.values()) == 1):
        family, detail = _match_matmul_allreduce(body, ctx)
    elif colls.get("all_gather", 0) >= 2:
        family, detail = KV_ALLGATHER, {
            "why": "bulk KV all-gather attention: a ring rewrite "
                   "reassociates the online softmax (not value-preserving; "
                   "opt in via FusionConfig.fuse_kv_ag)"}
    else:
        family, detail = _unmatched(
            "no fusible compute/collective adjacency matched")

    return CollectiveSite(
        family=family, eqn=eqn, containers=containers, path=path,
        prims=tuple(sorted(colls.items())), axes=tuple(sorted(axes)),
        in_shapes=tuple(tuple(v.aval.shape) for v in eqn.invars),
        rewritable=rewritable, detail=detail)


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------
def build_comm_graph(closed, ctx: ParallelContext) -> CommGraph:
    """Walk ``closed`` (a ``jax.make_jaxpr`` result) and classify every
    collective site against the fused-op families."""
    sites: list[CollectiveSite] = []

    def walk(jaxpr, containers, path):
        for eqn in jaxpr.eqns:
            nm = eqn.primitive.name
            if nm == "shard_map":
                sites.append(_classify_shard_map(eqn, ctx, containers, path))
            elif nm in COLLECTIVE_PRIMS:
                sites.append(CollectiveSite(
                    family=BARE_COLLECTIVE, eqn=eqn, containers=containers,
                    path=path, prims=((nm, 1),),
                    axes=tuple(sorted(collective_axes(eqn))),
                    in_shapes=tuple(tuple(v.aval.shape)
                                    for v in eqn.invars),
                    rewritable=False,
                    detail={"why": f"bare {nm} outside shard_map (left to "
                                   "the partitioner)"}))
            else:
                subs = sub_jaxprs(eqn)
                if subs:
                    for sj in subs:
                        walk(sj, containers + (eqn,), path + (nm,))

    walk(closed.jaxpr, (), ())
    return CommGraph(closed=closed, sites=sites)

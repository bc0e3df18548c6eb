"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data, model);
multi-pod: 2x16x16 = 512 chips (pod, data, model) — the ``pod`` axis
composes with ``data`` into the DP/FSDP dimension everywhere, so the
same model code runs on both meshes and the multi-pod dry-run proves the
pod axis shards (its collectives cross the DCN boundary in the HLO).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.models.common import split_params
from repro.parallel.sharding import FusionConfig, ParallelContext


# Topology registry: name -> (shape, axis names).  ``assert_production_
# topology`` and the dry-run launchers size themselves from here, so a
# new slice shape is one registry entry instead of scattered constants.
PRODUCTION_TOPOLOGIES = {
    "v5e-256": ((16, 16), ("data", "model")),
    "v5e-2pod-512": ((2, 16, 16), ("pod", "data", "model")),
}
DEFAULT_TOPOLOGY = "v5e-256"
DEFAULT_MULTI_POD_TOPOLOGY = "v5e-2pod-512"


def production_topology(*, multi_pod: bool = False,
                        topology: str | None = None):
    """(shape, axes) for a registered production topology."""
    if topology is None:
        topology = DEFAULT_MULTI_POD_TOPOLOGY if multi_pod else DEFAULT_TOPOLOGY
    try:
        return PRODUCTION_TOPOLOGIES[topology]
    except KeyError:
        raise KeyError(f"unknown topology {topology!r}; registered: "
                       f"{sorted(PRODUCTION_TOPOLOGIES)}") from None


def production_mesh_shape(*, multi_pod: bool = False,
                          topology: str | None = None):
    return production_topology(multi_pod=multi_pod, topology=topology)[0]


def make_production_mesh(*, multi_pod: bool = False,
                         topology: str | None = None):
    shape, axes = production_topology(multi_pod=multi_pod, topology=topology)
    return make_mesh(shape, axes)


def make_context(*, multi_pod: bool = False,
                 fusion: FusionConfig | None = None) -> ParallelContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    return ParallelContext.from_mesh(mesh, fusion=fusion)


def make_host_mesh(shape=None, axes=("data", "model"),
                   fusion: FusionConfig | None = None) -> ParallelContext:
    """Mesh over the devices of one host: (1, 1) on one chip, (1, 4) on
    a four-chip v5e host, (2, 4) on the 8 virtual CPU devices of the
    tests."""
    n = len(jax.devices())
    if shape is None:
        model = min(4, n)
        shape = (n // model, model)
    mesh = make_mesh(shape, axes)
    return ParallelContext.from_mesh(mesh, fusion=fusion)


def param_placements(bundle, ctx: ParallelContext):
    """(weights, logical specs) without drawing a weight: each weight a
    ``jax.ShapeDtypeStruct`` carrying its placement on ``ctx.mesh``, as
    :func:`init_params_on_mesh` places it.  What an ahead-of-time lowering
    of a step at the served shardings takes."""
    struct, specs = split_params(jax.eval_shape(bundle.init_params,
                                                jax.random.PRNGKey(0)))
    is_spec = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)

    def placement(logical, leaf):
        # a dim the mesh axes do not divide stays whole (reduced configs)
        axes = [ax if ax is None or leaf.shape[i] % math.prod(
                    ctx.mesh.shape[a] for a in
                    ((ax,) if isinstance(ax, str) else ax)) == 0 else None
                for i, ax in enumerate(ctx.spec(*logical))]
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(ctx.mesh, P(*axes)))

    return jax.tree.map(placement, specs, struct, is_leaf=is_spec), specs


def init_params_on_mesh(bundle, ctx: ParallelContext, seed: int = 0):
    """(params, logical specs), each weight drawn straight into its
    placement on ``ctx.mesh``.

    One jitted program draws, scales and casts every weight on the
    devices that hold it, so the full-size float32 draws of an eager init
    never sit in device memory (at chatglm3-6b width the stacked
    ``w_up`` draw alone is a 6.3 GB transient) and nothing lands on one
    device first to be resharded later.  The values are those of
    ``bundle.init_params(PRNGKey(seed))`` to within one unit in the last
    place: XLA folds the constant factors of a normal draw, which can
    round its final bit differently from the op-by-op eager run."""
    placed, specs = param_placements(bundle, ctx)
    init = jax.jit(lambda k: split_params(bundle.init_params(k))[0],
                   out_shardings=jax.tree.map(lambda s: s.sharding, placed))
    return init(jax.random.PRNGKey(seed)), specs

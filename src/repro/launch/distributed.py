"""Multi-host initialization for TPU pods (and the CPU test lane).

On a multi-host v5e slice every host runs the same binary with an
explicit coordinator address, world size and rank (flags or the
``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``
environment); ``jax.distributed.initialize`` then wires the hosts
together, ``jax.devices()`` spans the slice and `make_production_mesh()`
builds the global mesh exactly as the dry-run proved it.  The same entry
point wires the multi-process CPU lane (:mod:`repro.runtime.
multiprocess`).  A single-host run (one chip, or the four chips of one
v5e host) configures nothing and initializes nothing.
"""
from __future__ import annotations

import logging
import os

import jax

log = logging.getLogger("repro.launch")

# Idempotency is tracked explicitly: ``jax.process_count() > 1`` only
# detects *multi*-process init, so a single-process distributed init
# (world of 1 — the shrunk-to-one elastic tail) used to re-initialize
# and crash on the second call.
_initialized = False


def _env_int(name: str) -> int | None:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def initialize_distributed(coordinator: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None, *,
                           initialization_timeout: float | None = None
                           ) -> bool:
    """Idempotent multi-host init.  Returns True when this call (or an
    earlier one) actually initialized the distributed runtime.

    The runtime is initialized only when a coordinator is configured:
    pass coordinator ("host:port"), num_processes and process_id, or set
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID.  With
    none of them this is single-process mode and nothing is initialized
    — never cluster auto-detection, which on a machine without a
    metadata server can hang or build a wrong world.

    With a coordinator configured, any failure is a genuine
    misconfiguration — bad address, port in use, a peer missing — and
    **propagates**; silently degrading a configured multi-host run to
    single-host mode would train on 1/Nth of the data while looking
    healthy.
    """
    global _initialized
    if _initialized:
        return True
    # Probe for an out-of-band init through the distributed client, NOT
    # jax.process_count(): the latter initializes the backend, which
    # fails outright when gloo collectives are configured but the
    # distributed client does not exist yet (the exact state this
    # function is about to fix).
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        _initialized = True
        return True
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not coordinator:
        log.info("single-process mode (no coordinator configured)")
        return False
    if num_processes is None:
        num_processes = _env_int("JAX_NUM_PROCESSES")
    if process_id is None:
        process_id = _env_int("JAX_PROCESS_ID")
    if num_processes is None or process_id is None:
        raise ValueError(
            "coordinator address set but num_processes/process_id "
            "missing (pass them or set JAX_NUM_PROCESSES / "
            "JAX_PROCESS_ID)")
    kwargs = {}
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = initialization_timeout
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    _initialized = True
    log.info("distributed init: process %d/%d, %d devices (%d local)",
             jax.process_index(), jax.process_count(),
             len(jax.devices()), len(jax.local_devices()))
    return True


def add_distributed_cli_args(ap) -> None:
    """Coordinator + liveness knobs shared by the train/serve launchers."""
    g = ap.add_argument_group("distributed / liveness")
    g.add_argument("--coordinator", default=None,
                   help="host:port of the jax.distributed coordinator "
                        "(or set JAX_COORDINATOR_ADDRESS); omit for a "
                        "single-process run")
    g.add_argument("--num-processes", type=int, default=None)
    g.add_argument("--process-id", type=int, default=None)
    g.add_argument("--heartbeat-dir", default=None,
                   help="shared directory for per-process heartbeat files; "
                        "enables the liveness watchdog — a dead peer "
                        "raises RankLost and the launcher exits with the "
                        "elastic-respawn protocol code instead of hanging")
    g.add_argument("--heartbeat-interval", type=float, default=0.25,
                   help="seconds between heartbeats")
    g.add_argument("--stall-after", type=float, default=2.0,
                   help="heartbeat staleness that marks a peer stalled/lost")
    g.add_argument("--step-deadline", type=float, default=None,
                   help="hard per-step deadline even with peers "
                        "heartbeating (deadlocked-collective backstop)")


def init_distributed_from_args(args) -> bool:
    """CLI/env-driven :func:`initialize_distributed` (no-op when nothing
    is configured — the single-host dev path)."""
    return initialize_distributed(args.coordinator, args.num_processes,
                                  args.process_id)


def build_liveness_from_args(args):
    """(HeartbeatWriter, LivenessMonitor) when ``--heartbeat-dir`` is
    set, else (None, None).  The writer is started; the monitor starts
    *disarmed* — arm it (``monitor.enabled = True``) after the first
    successful step so compile time is never misread as a stall."""
    if not getattr(args, "heartbeat_dir", None):
        return None, None
    from repro.runtime.watchdog import HeartbeatWriter, LivenessMonitor

    rank = jax.process_index()
    world = jax.process_count()
    writer = HeartbeatWriter(args.heartbeat_dir, rank,
                             interval_s=args.heartbeat_interval).start()
    monitor = LivenessMonitor(args.heartbeat_dir, rank, world,
                              stall_after_s=args.stall_after,
                              step_deadline_s=args.step_deadline)
    monitor.enabled = False
    return writer, monitor


def assert_production_topology(multi_pod: bool = False,
                               topology: str | None = None):
    """Fail fast when the visible chip count is not the target mesh's.

    The expected count comes from the topology registry
    (:data:`repro.launch.mesh.PRODUCTION_TOPOLOGIES`) — pass
    ``topology`` to check a non-default entry (dry-running a new slice
    shape needs a registry entry, not a code edit here)."""
    from repro.launch.mesh import production_mesh_shape

    shape = production_mesh_shape(multi_pod=multi_pod, topology=topology)
    want = 1
    for dim in shape:
        want *= dim
    have = len(jax.devices())
    if have != want:
        name = topology or ("multi-pod" if multi_pod else "single-pod")
        raise RuntimeError(
            f"expected {want} chips for the {name} mesh {shape}, found "
            f"{have}; adjust --mesh, the slice size, or register the "
            f"topology in repro.launch.mesh.PRODUCTION_TOPOLOGIES")

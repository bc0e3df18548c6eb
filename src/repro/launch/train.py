"""Training launcher: end-to-end driver with fault tolerance.

Runs any registry architecture (reduced or full config) on the available
devices with the fused operators, synthetic data, async checkpointing and
restart-on-failure supervision.

  PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b \
      --reduced --steps 200 --batch 16 --seq 64
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import numpy as np

from repro.runtime.chaos import CollectiveTimeout, RankLost

from repro.configs.registry import get_arch
from repro.core.autotune import (add_granularity_cli_args,
                                 load_cache_if_exists, save_cache)
from repro.core.calibrate import (add_calibration_cli_args,
                                  warmup_and_calibrate)
from repro.core.degrade import DegradationPolicy, set_degradation_policy
from repro.data.synthetic import DLRMBatches, LMBatches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.distributed import (add_distributed_cli_args,
                                      build_liveness_from_args,
                                      init_distributed_from_args)
from repro.launch.mesh import (init_params_on_mesh, make_context,
                               make_host_mesh)
from repro.parallel.sharding import FusionConfig
from repro.runtime.chaos import add_chaos_cli_args, build_fault_plan
from repro.runtime.elastic import reshard_tree, shrink_context
from repro.runtime.fault_tolerance import SupervisorConfig, TrainSupervisor
from repro.runtime.straggler import SkewEstimator, SkewScheduler
from repro.train.optimizer import OptimizerConfig
from repro.train.step import TrainConfig, build_train_step, init_train_state, train_state_specs


def _shardings(ctx, logical_tree):
    is_spec = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)
    return jax.tree.map(lambda s: ctx.sharding(*s), logical_tree, is_leaf=is_spec)


def make_batches(bundle, batch: int, seq: int, seed: int = 0):
    cfg = bundle.config
    if bundle.family == "dlrm":
        return DLRMBatches(cfg.n_tables, cfg.table_vocab, cfg.pooling,
                           cfg.n_dense, batch, seed)
    base = LMBatches(cfg.vocab, batch, seq, seed)
    fe = getattr(cfg, "frontend", None)
    if fe is None:
        return base

    def gen():
        rng = np.random.default_rng(seed + 7)
        for b in base:
            if fe == "audio":
                b["frame_embeds"] = rng.standard_normal(
                    (batch, seq, cfg.d_model)).astype(np.float32) * 0.02
            if fe == "vision":
                b["vision_embeds"] = rng.standard_normal(
                    (batch, seq, cfg.d_model)).astype(np.float32) * 0.02
                b["vision_mask"] = np.arange(seq) < min(8, seq)
                b["positions_thw"] = np.tile(
                    np.arange(seq, dtype=np.int32)[None, None], (3, batch, 1))
            yield b

    return gen()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--fusion", default="fused",
                    choices=["fused", "bulk", "kernel", "auto"])
    ap.add_argument("--auto-fuse", action="store_true",
                    help="trace the model with bulk collectives and let the "
                         "jaxpr comm-graph analyzer rewrite profitable "
                         "matches to the fused ops (same as --fusion auto)")
    ap.add_argument("--explain-comm", action="store_true",
                    help="report-only: print every collective in the step, "
                         "its fused-op family, the modeled bulk->fused "
                         "savings and the reason when not fusible, then "
                         "exit without training")
    add_granularity_cli_args(ap)
    add_calibration_cli_args(ap)
    ap.add_argument("--skew-schedule", action="store_true",
                    help="close the Fig. 14 loop: feed per-step telemetry "
                         "to the cross-rank skew estimator and re-jit the "
                         "fused-op schedules when the straggler bucket "
                         "changes (single-process runs see uniform times, "
                         "so the bucket stays 0 unless a cluster telemetry "
                         "provider is plugged in)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    add_distributed_cli_args(ap)
    add_chaos_cli_args(ap)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    if args.auto_fuse:
        args.fusion = "auto"

    init_distributed_from_args(args)
    hb_writer, liveness = build_liveness_from_args(args)

    load_cache_if_exists(args.tune_cache)
    fusion = FusionConfig(mode=args.fusion, granularity=args.granularity,
                          wire=args.wire)
    ctx = (make_context(fusion=fusion) if args.production_mesh
           else make_host_mesh(fusion=fusion))
    bundle = get_arch(args.arch)
    if args.reduced:
        bundle = bundle.reduced()

    params, param_specs = init_params_on_mesh(bundle, ctx)
    tc = TrainConfig(
        optimizer=OptimizerConfig(name=bundle.optimizer, lr=args.lr,
                                  warmup_steps=max(args.steps // 20, 5),
                                  total_steps=args.steps))
    state = init_train_state(tc, params)
    state_sh = _shardings(ctx, train_state_specs(tc, param_specs))
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, state_sh)

    if args.explain_comm:
        from repro.analysis import explain_comm
        import jax.numpy as jnp
        # the report always analyzes the bulk-traced graph ("auto"): that
        # is the form the rewrite pass sees, whatever --fusion says
        ectx = ctx.with_fusion(dataclasses.replace(fusion, mode="auto"))
        batch0 = jax.tree.map(
            jnp.asarray, next(iter(make_batches(bundle, args.batch, args.seq))))
        print(explain_comm(ectx, bundle.loss_fn(ectx), params, batch0))
        return []

    def build_step(skew: int = 0):
        c = ctx.with_fusion(dataclasses.replace(fusion, skew=skew))
        loss = bundle.loss_fn(c)
        if fusion.mode == "auto":
            from repro.analysis import auto_fuse
            loss = auto_fuse(c, loss)
        return jax.jit(build_train_step(loss, tc),
                       donate_argnums=(0,))

    step_fn = build_step()
    batches = make_batches(bundle, args.batch, args.seq)

    if args.calibrate:
        batch0 = next(iter(make_batches(bundle, args.batch, args.seq)))
        warmup_and_calibrate(ctx, step_fn, state, batch0,
                             iters=args.calibrate_iters,
                             granularity=args.granularity)
        step_fn = build_step()  # measured decisions are read at trace time

    skew_sched = None
    if args.skew_schedule:
        skew_sched = SkewScheduler(build_step,
                                   SkewEstimator(dict(ctx.mesh.shape)),
                                   axis=ctx.tp_axis)

    fault_plan = build_fault_plan(args.chaos, num_steps=args.steps)
    degradation = None
    if args.degrade:
        degradation = DegradationPolicy()
        set_degradation_policy(degradation)

    def on_rank_loss(st, exc):
        # Elastic shrink: halve the dp axis, keep going on the survivors.
        nonlocal ctx, state_sh
        ctx = shrink_context(ctx)
        st, state_sh = reshard_tree(st, train_state_specs(tc, param_specs),
                                    ctx)
        sup.state_shardings = state_sh
        return st, build_step()

    sup = TrainSupervisor(
        SupervisorConfig(checkpoint_dir=args.ckpt_dir,
                         checkpoint_every=args.ckpt_every),
        step_fn, state_shardings=state_sh, skew_scheduler=skew_sched,
        # multi-host: all-gather the local monitor's EWMA per process so
        # the estimator sees measured cross-rank times (single-process
        # runs degrade to the replicated local time — rotation stays 0)
        per_rank_times="process" if skew_sched is not None else None,
        fault_plan=fault_plan, degradation=degradation,
        rebuild_step=build_step, liveness=liveness,
        # With real liveness the in-process shrink cannot survive a dead
        # gloo world: RankLost must propagate so this process can exit
        # with the elastic-respawn protocol code for its driver.
        on_rank_loss=None if liveness is not None else on_rank_loss)

    t0 = time.time()
    losses = []

    def on_metrics(step, metrics):
        losses.append(float(metrics["loss"]))
        if liveness is not None:
            hb_writer.beat(step=step)
            liveness.enabled = True   # armed once the first step lands
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({(time.time() - t0) / max(step, 1):.2f}s/step)",
                  flush=True)

    try:
        state, step = sup.run(state, batches, args.steps,
                              on_metrics=on_metrics)
        if hb_writer is not None:
            hb_writer.stop()
    except (RankLost, CollectiveTimeout) as e:
        if liveness is None:
            raise
        # Elastic respawn protocol: a real peer death/stall was detected
        # by the heartbeat watchdog.  Leave with the protocol exit code
        # so the driver relaunches the survivors (shrunk or same-size
        # world); training resumes from --ckpt-dir.
        from repro.runtime.multiprocess import EXIT_RESHARD, EXIT_RESTART

        code = EXIT_RESHARD if isinstance(e, RankLost) else EXIT_RESTART
        print(f"liveness failure: {e}; exiting with respawn code {code}",
              flush=True)
        hb_writer.stop()
        os._exit(code)
    span = (f"loss {losses[0]:.4f} -> {losses[-1]:.4f}" if losses
            else "no steps run (resumed at or past num_steps)")
    print(f"done at step {step}; {span}; "
          f"straggler stats {sup.straggler.summary()}")
    if fault_plan is not None:
        print(f"chaos: plan {fault_plan.summary()}; injected "
              f"{sup.faults_injected}, restarts {sup.restarts}, "
              f"rank losses {sup.rank_losses}, backoffs "
              f"{[round(b, 3) for b in sup.backoffs]}")
    if degradation is not None:
        print(f"degradation: {degradation.summary()}")
    if args.tune_cache:
        save_cache(args.tune_cache)
    return losses


if __name__ == "__main__":
    main()

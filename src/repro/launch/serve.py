"""Serving launcher: batched decode with the fused GEMV+AllReduce FFN.

  PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --reduced \
      --requests 8 --max-new 16

:func:`serve` is the same server as a callable: it takes the parsed
arguments and, optionally, weights already placed on the host mesh, so
one process can serve one set of weights under several ``--fusion``
settings (``chip_smoke.py`` does).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.core.autotune import (add_granularity_cli_args,
                                 load_cache_if_exists, save_cache)
from repro.core.calibrate import (add_calibration_cli_args,
                                  warmup_and_calibrate)
from repro.core.degrade import DegradationPolicy, set_degradation_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.distributed import (add_distributed_cli_args,
                                      build_liveness_from_args,
                                      init_distributed_from_args)
from repro.launch.mesh import (init_params_on_mesh, make_context,
                               make_host_mesh)
from repro.models.common import split_params
from repro.parallel.sharding import FusionConfig
from repro.runtime.chaos import (CollectiveTimeout, RankLost,
                                 add_chaos_cli_args, build_fault_plan)
from repro.runtime.elastic import reshard_tree, shrink_context
from repro.serve.engine import (DecodeEngine, PagedDecodeEngine, Request,
                                request_journal, resubmit_journal,
                                serve_with_chaos)
from repro.serve.kv_cache import dense_cache_hbm_bytes, pool_hbm_bytes


def _bind(fn, params):
    """``fn(params, *args)`` jitted with the weights as an argument: jit
    bakes a closed-over array into the program as a constant, which at
    chatglm3-6b width would be 12 GB of literals in the HLO."""
    return functools.partial(jax.jit(fn), params)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--fusion", default="fused",
                    choices=["fused", "bulk", "kernel", "auto"])
    ap.add_argument("--auto-fuse", action="store_true",
                    help="trace decode with bulk collectives and let the "
                         "jaxpr comm-graph analyzer rewrite profitable "
                         "matches to the fused ops (same as --fusion auto)")
    ap.add_argument("--explain-comm", action="store_true",
                    help="report-only: print every collective in one decode "
                         "step with its family, modeled savings and "
                         "not-fusible reasons, then exit without serving")
    ap.add_argument("--paged", action="store_true",
                    help="paged/block KV cache + chunked prefill "
                         "(continuous batching over a shared block pool)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged mode)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool blocks; 0 = half the dense B x S_max budget")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk width C (paged mode)")
    add_granularity_cli_args(ap)
    add_calibration_cli_args(ap)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--journal", default=None,
                    help="request-journal path: unfinished requests are "
                         "persisted here on a liveness failure and "
                         "resubmitted (tokens intact) on the next launch "
                         "— the cross-process drain-reshard-resume story")
    add_distributed_cli_args(ap)
    add_chaos_cli_args(ap)
    return ap


def serve(args, params=None):
    """Serve the requests ``args`` describe; returns (finished, engine).

    ``params``: weight values already placed on the host mesh (as
    :func:`~repro.launch.mesh.init_params_on_mesh` makes them for this
    arch); ``None`` draws them from seed 0."""
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
    cfg = bundle.config

    if params is None:
        params, param_specs = init_params_on_mesh(bundle, ctx)
    else:
        _, param_specs = split_params(
            jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0)))
    decode = bundle.decode_fn(ctx)

    if args.explain_comm:
        from repro.analysis import explain_comm
        # analyze the bulk-traced decode graph, whatever --fusion says
        ectx = ctx.with_fusion(dataclasses.replace(fusion, mode="auto"))
        tok0 = np.zeros((args.batch, 1), np.int32)
        print(explain_comm(ectx, bundle.decode_fn(ectx), params, tok0,
                           bundle.init_cache(args.batch), 0))
        return [], None

    if args.fusion == "auto":
        from repro.analysis import auto_fuse
        decode = auto_fuse(ctx, decode)
    decode_jit = _bind(decode, params)

    if args.calibrate:
        warm_cache = bundle.init_cache(args.batch)
        warm_tok = np.zeros((args.batch, 1), np.int32)
        warmup_and_calibrate(ctx, decode_jit, warm_tok, warm_cache, 0,
                             iters=args.calibrate_iters,
                             granularity=args.granularity)
        # measured decisions are read at trace time: re-jit for steady state
        decode_jit = _bind(decode, params)

    if args.degrade:
        set_degradation_policy(DegradationPolicy())

    if args.paged:
        if not bundle.supports_paged:
            raise SystemExit(f"--paged requires a GQA transformer "
                             f"({args.arch} is {bundle.family}/"
                             f"{getattr(cfg, 'attn_type', '?')})")
        num_blocks = args.num_blocks
        if not num_blocks:
            # half the dense budget, rounded to a tp-divisible block count
            num_blocks = max(ctx.tp, (args.batch * cfg.max_seq // 2)
                             // args.block_size // ctx.tp * ctx.tp)
        serve_jit = _bind(bundle.serve_step_fn(ctx), params)
        engine = PagedDecodeEngine(
            serve_jit, bundle.init_paged_pool, args.batch,
            num_blocks=num_blocks, block_size=args.block_size,
            max_seq=cfg.max_seq, chunk=args.chunk, n_stripes=ctx.tp)
        paged_b = pool_hbm_bytes(engine.pool)
        dense_b = dense_cache_hbm_bytes(
            jax.eval_shape(lambda: bundle.init_cache(args.batch)))
        print(f"paged pool: {num_blocks} x {args.block_size}-token blocks "
              f"= {paged_b / 2**20:.1f} MiB vs dense B x S_max "
              f"{dense_b / 2**20:.1f} MiB")
    else:
        engine = DecodeEngine(decode_jit, bundle.init_cache, args.batch,
                              max_seq=cfg.max_seq)
    if args.journal and os.path.exists(args.journal):
        with open(args.journal) as f:
            n = resubmit_journal(engine, json.load(f))
        print(f"journal: resubmitted {n} unfinished requests "
              f"(tokens intact) from {args.journal}")
    else:
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            prompt = rng.integers(0, cfg.vocab,
                                  size=rng.integers(2, 6)).tolist()
            engine.submit(Request(uid=i, prompt=prompt,
                                  max_new=args.max_new))

    max_steps = args.requests * (getattr(cfg, "max_seq", 512) - 1)
    plan = build_fault_plan(args.chaos, num_steps=max_steps)

    def reshard_fn(eng):
        # drain-reshard-resume: shrink the mesh, re-jit for the surviving
        # devices, replay in-flight requests through the new cache/pool
        # (they keep their generated tokens; the paged engine rebuilds
        # their block tables through the chunked-prefill path)
        nonlocal ctx, params
        ctx = shrink_context(ctx)
        params, _ = reshard_tree(params, param_specs, ctx)
        if args.paged:
            sfn = bundle.serve_step_fn(ctx)
            new_jit = _bind(sfn, params)
            n = eng.reshard(new_jit, bundle.init_paged_pool, args.batch,
                            n_stripes=ctx.tp)
        else:
            dec = bundle.decode_fn(ctx)
            if args.fusion == "auto":
                from repro.analysis import auto_fuse
                dec = auto_fuse(ctx, dec)
            new_jit = _bind(dec, params)
            n = eng.reshard(new_jit, bundle.init_cache, args.batch)
        print(f"rank lost: mesh -> {dict(ctx.mesh.shape)}, "
              f"{n} in-flight requests re-queued")

    t0 = time.time()
    if liveness is not None:
        liveness.enabled = True   # serving has no compile-length steps
    try:
        if plan is not None:
            finished, stats = serve_with_chaos(engine, plan,
                                               reshard_fn=reshard_fn,
                                               max_steps=max_steps)
            print(f"chaos: plan {plan.summary()}; ticks {stats['ticks']}, "
                  f"dropped {stats['dropped']}, reshards "
                  f"{stats['reshards']}, drained {stats['drained']}")
        else:
            finished = engine.run_until_drained(max_steps=max_steps,
                                                liveness=liveness)
            if not finished.drained:
                print(f"WARNING: stopped at max_steps={max_steps} before "
                      f"draining — results truncated")
        if hb_writer is not None:
            hb_writer.stop()
    except (RankLost, CollectiveTimeout) as e:
        if liveness is None:
            raise
        # Real liveness failure mid-drain: journal the unfinished
        # requests (tokens intact) and leave with the respawn protocol
        # code — the relaunched engine resubmits them and every request
        # still drains to completion.
        from repro.runtime.multiprocess import EXIT_RESHARD, EXIT_RESTART

        if args.journal:
            with open(args.journal, "w") as f:
                json.dump(request_journal(engine), f)
            print(f"journal: persisted {len(request_journal(engine))} "
                  f"unfinished requests to {args.journal}")
        code = EXIT_RESHARD if isinstance(e, RankLost) else EXIT_RESTART
        print(f"liveness failure: {e}; exiting with respawn code {code}",
              flush=True)
        hb_writer.stop()
        os._exit(code)
    dt = time.time() - t0
    total_tokens = sum(len(r.tokens) for r in finished)
    print(f"served {len(finished)} requests, {total_tokens} tokens in "
          f"{dt:.1f}s ({total_tokens / max(dt, 1e-9):.1f} tok/s, "
          f"batch={args.batch}, fusion={args.fusion})")
    for r in finished[:4]:
        print(f"  req {r.uid}: prompt {r.prompt} -> {r.tokens[:12]}")
    if args.paged:
        pool = engine.kv.stats()
        counts = " ".join(f"{k} {v}" for k, v in
                          dataclasses.asdict(engine.stats).items())
        st = engine.stats
        read = st.kv_blocks_read / max(st.kv_blocks_table, 1)
        print(f"engine: {counts}; table blocks read {read:.1%}; pool "
              f"{pool.used_blocks} of {pool.num_blocks} blocks in use, peak "
              f"{pool.peak_blocks}")
    if args.tune_cache:
        save_cache(args.tune_cache)
    return finished, engine


def main(argv=None):
    enable_compile_cache()
    finished, _ = serve(build_parser().parse_args(argv))
    return finished


if __name__ == "__main__":
    main()

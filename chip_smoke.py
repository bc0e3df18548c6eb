"""Smoke test on the chip: chatglm3-6b served at full published width.

  python3 chip_smoke.py             # one v5e chip
  python3 chip_smoke.py --chips 4   # one v5e 2x2 host, tp=4

One chip: the paged server of ``repro.launch.serve`` (batch 4, four short
requests, 8 new tokens each) runs once with ``--fusion bulk`` and once
with ``--fusion fused`` on one set of randomly initialised weights
(28 layers, d_model 4096, vocab 65024, bf16; seed 0).  Four chips: the
same server on the (1, 4) ``("data", "model")`` mesh, so every decode
layer's row-parallel matmuls reduce over tp=4, with ``--fusion bulk``,
``fused`` and ``kernel``; the kernel-mode step must hold the Pallas
GEMV+AllReduce kernel (``tpu_custom_call``).

Every mode must produce the bulk mode's greedy tokens.  Where a mode's
tokens differ, its first-step logits must agree with bulk's to a
relative L2 error of at most ``REL_L2_TOL``: at tp=4 the modes add the
four bf16 partial products in different orders with different
intermediate rounding, which can flip a near-tied greedy choice and so
every later token of that request.

All phases run in this one process (a chip belongs to one process).
The script exits nonzero, printing no result, when JAX finds no TPU or
when the repository is not beside it.  Otherwise its last stdout line is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "chatglm3-6b"
SERVE_ARGS = ["--arch", ARCH, "--paged", "--requests", "4", "--batch", "4",
              "--max-new", "8"]
# bf16 carries 8 significant bits; 2**-5 is four units in its last place
REL_L2_TOL = 2.0 ** -5
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def serve_mode(mode, params, compile_s):
    """Serve the requests under ``--fusion mode``; then replay them on the
    compiled step for steady-state tokens/s and the first-step logits."""
    import numpy as np

    from repro.launch import serve
    from repro.serve.engine import PagedDecodeEngine, Request

    args = serve.build_parser().parse_args(SERVE_ARGS + ["--fusion", mode])
    c0, t0 = compile_s[0], time.perf_counter()
    finished, engine = serve.serve(args, params=params)
    cold_s = time.perf_counter() - t0
    tokens = {r.uid: list(r.tokens) for r in finished}

    first_logits = []

    def recording(*step_args):
        out = engine.serve_fn(*step_args)
        if not first_logits:
            first_logits.append(np.asarray(out[0]))
        return out

    replay = PagedDecodeEngine(
        recording, engine.init_pool_fn, engine.batch,
        num_blocks=engine.num_blocks, block_size=engine.block_size,
        max_seq=engine.max_seq, chunk=engine.chunk,
        n_stripes=engine.n_stripes)
    for r in sorted(finished, key=lambda r: r.uid):
        replay.submit(Request(uid=r.uid, prompt=list(r.prompt),
                              max_new=args.max_new))
    t1 = time.perf_counter()
    warm = replay.run_until_drained()
    warm_s = time.perf_counter() - t1
    n_tok = sum(len(t) for t in tokens.values())
    if {r.uid: list(r.tokens) for r in warm} != tokens:
        raise SystemExit(f"{mode}: replaying the requests on the compiled "
                         f"step changed the greedy tokens")
    print(f"{mode}: served {len(finished)} requests, {n_tok} tokens; "
          f"first pass {cold_s:.2f} s with {compile_s[0] - c0:.2f} s of "
          f"compilation; compiled replay {warm_s:.3f} s = "
          f"{n_tok / warm_s:.1f} tokens/s", flush=True)
    return tokens, first_logits[0]


def check_against_bulk(results, vocab):
    import numpy as np

    ref_tokens, ref_logits = results["bulk"]
    for mode, (tokens, logits) in results.items():
        if logits.shape != (4, vocab) or not np.isfinite(logits).all():
            raise SystemExit(f"{mode}: first-step logits are not finite "
                             f"[4, {vocab}] (got {logits.shape})")
        if sorted(tokens) != [0, 1, 2, 3] or any(
                len(t) != 8 or not all(0 <= x < vocab for x in t)
                for t in tokens.values()):
            raise SystemExit(f"{mode}: expected 8 in-vocabulary tokens for "
                             f"each of 4 requests, got {tokens}")
        rel = float(np.linalg.norm(logits - ref_logits)
                    / np.linalg.norm(ref_logits))
        same = tokens == ref_tokens
        print(f"{mode} vs bulk: greedy tokens "
              f"{'identical' if same else 'differ'}; first-step logits "
              f"max |diff| {float(np.abs(logits - ref_logits).max()):.6g}, "
              f"relative L2 {rel:.6g} (limit {REL_L2_TOL:.6g} where "
              f"tokens differ)", flush=True)
        if not same and rel > REL_L2_TOL:
            raise SystemExit(f"{mode}: tokens differ from bulk and the "
                             f"first-step logits are {rel:.3g} apart")


def check_kernel_in_step(params, ctx):
    """The kernel-mode decode step must hold the Pallas kernel, not the
    XLA fallback it takes where the kernel is unavailable."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_arch

    bundle = get_arch(ARCH)
    cfg = bundle.config
    blocks = -(-cfg.max_seq // 16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    # the server's default pool at batch 4: half of B x S_max, 16-token
    # blocks -- the same program the kernel-mode pass compiled
    num_blocks = 4 * cfg.max_seq // 2 // 16
    pool = jax.eval_shape(lambda: bundle.init_paged_pool(num_blocks, 16))
    text = jax.jit(bundle.serve_step_fn(ctx)).lower(
        params, i32(4, 1), pool, i32(4, blocks), i32(4), i32(4)
    ).compile().as_text()
    if "tpu_custom_call" not in text:
        raise SystemExit("kernel: the compiled decode step holds no "
                         "tpu_custom_call (fell back to the XLA path)")
    print("kernel: compiled decode step holds the Pallas kernel "
          "(tpu_custom_call)", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tp=4 decode path on a 2x2 host only")
    opts = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.configs.registry import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import init_params_on_mesh, make_host_mesh
    from repro.parallel.sharding import FusionConfig

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    if len(devices) != opts.chips:
        raise SystemExit(f"chip_smoke: --chips {opts.chips} but JAX sees "
                         f"{len(devices)} devices")
    print(f"device: {dev.device_kind} x {len(devices)}", flush=True)

    cache_dir = enable_compile_cache()
    events = collections.Counter()
    compile_s = [0.0]
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update([event]))

    def on_duration(event, duration_secs, **_):
        if event == COMPILE_EVENT:
            compile_s[0] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    bundle = get_arch(ARCH)
    t0 = time.perf_counter()
    params, _ = init_params_on_mesh(bundle, make_host_mesh())
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"{ARCH}: {n_params} parameters drawn in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    modes = ("bulk", "fused", "kernel") if opts.chips == 4 else (
        "bulk", "fused")
    results = {m: serve_mode(m, params, compile_s) for m in modes}
    check_against_bulk(results, bundle.config.vocab)
    if opts.chips == 4:
        check_kernel_in_step(
            params, make_host_mesh(fusion=FusionConfig(mode="kernel")))

    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"peak_bytes_in_use per device: {peaks}", flush=True)
    print(f"persistent compilation cache {cache_dir}: "
          f"{events[CACHE_HIT_EVENT]} hits, {events[CACHE_MISS_EVENT]} "
          f"misses; {compile_s[0]:.2f} s compiling", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

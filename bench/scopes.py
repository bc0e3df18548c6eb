"""The step programs' device time by the program's named scopes.

The served step names its parts with ``jax.named_scope`` (``embed``,
``attn.qkv``, ``attn.kv_write``, ``attn.paged``, ``attn.out``, ``mlp``,
``lm_head``; ``models/transformer.py``) and each Pallas PUT kernel with
its family's name (``kernels/<family>/ops.py``).  The scopes reach the
compiled program as the ``op_name`` metadata of its HLO instructions,
and an op on the device trace is named after its instruction
(``%fusion.312 = ...``).  So the ops of a traced window are mapped to
scopes through each step program's compiled HLO text.

The programs are compiled here afresh, with the metadata in the key of
JAX's persistent compilation cache: the key leaves metadata out by
default, so an executable loaded from the cache may carry the op names
of another build of the same program (one from before the scopes).
They are lowered at the shapes and placements the engine ran them with:
the weights as ``init_params_on_mesh`` places them, host inputs as the
engine hands them over, and the pool as a step returns it, the placement
every tick after the first sees.
"""
from __future__ import annotations

import re

from bench.xplane import CONTAINERS, HLO_OP, ops_in

SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.paged", "attn.out",
          "mlp", "lm_head", "fused_gemv_allreduce", "fused_gemm_a2a",
          "fused_dispatch_a2a", "fused_embedding_a2a")
HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")
OP_NAME = re.compile(r'\bmetadata=\{[^{}]*?\bop_name="([^"]*)"')
STEP_PROGRAM = re.compile(r"serve_step_c(\d+)")
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


def scope_of(op_name: str) -> str:
    """The known scopes in an HLO ``op_name``, outermost first and joined
    by "/" (``mlp/fused_gemv_allreduce``; a kernel's scope and its
    ``pallas_call`` name count once), or "other" where it has none."""
    found = []
    for part in op_name.split("/"):
        if part in SCOPES and (not found or found[-1] != part):
            found.append(part)
    return "/".join(found) or "other"


def scope_map(hlo_text: str) -> dict:
    """{instruction name: scope} of every instruction in a compiled
    program's HLO text (``compiled.as_text()``).  An instruction's text
    runs on to the next instruction: a Pallas call's attributes hold
    line breaks, and its metadata comes after them."""
    out, name, text = {}, None, []
    for line in hlo_text.splitlines() + [""]:
        m = HLO_INSTR.match(line)
        if (m or line.rstrip() in ("", "}") or line.startswith(("%", "ENTRY"))
                ) and name:
            op = OP_NAME.search("\n".join(text))
            out[name] = scope_of(op.group(1)) if op else "other"
            name = None
        if m:
            name, text = m.group(1), [line]
        elif name:
            text.append(line)
    return out


def scope_seconds(trace, scopes: dict, dev: int = 0) -> list:
    """(scope, seconds) of the step programs' device time on ``dev`` in
    the traced window, most first.  An op of the program
    ``serve_step_c<C>`` counts under the scope that ``scopes[C]`` (its
    program's :func:`scope_map`) gives its HLO instruction, or under
    "unmapped"; ops that only contain others (a scan's ``while``) are
    left out."""
    total = {}
    for op, prog in ops_in(trace, "", dev):
        m = prog and STEP_PROGRAM.search(prog.name)
        if not m:
            continue
        h = HLO_OP.match(op.name)
        if h and h.group(3) in CONTAINERS:
            continue
        name = h.group(1) if h else op.name
        k = scopes.get(int(m.group(1)), {}).get(name, "unmapped")
        total[k] = total.get(k, 0.0) + op.dur * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])


def step_programs_hlo(cell, make_ctx=None) -> dict:
    """Chunk width -> compiled HLO text of the cell's step programs, built
    as ``bench.harness.build`` builds them and lowered at the placements
    the window ran them with; {} for a program that cannot place its
    weights without drawing them."""
    import jax

    from bench.harness import StepPrograms, program_bundle
    from repro.launch import mesh
    from repro.parallel.sharding import FusionConfig

    placements = getattr(mesh, "param_placements", None)
    if placements is None:
        return {}
    h = cell.config["harness"]
    ctx = (make_ctx or mesh.make_host_mesh)(
        fusion=FusionConfig(mode=h["fusion"]))
    bundle = program_bundle(cell)
    cfg = bundle.config
    batch, block, chunk = h["batch"], h["block_size"], h["chunk"]
    # the harness's pool rule: the configuration's, else the server's
    num_blocks = h.get("num_blocks") or max(
        ctx.tp, (batch * cfg.max_seq // 2) // block // ctx.tp * ctx.tp)
    params, _ = placements(bundle, ctx)
    steps = StepPrograms(bundle.serve_step_fn(ctx), params, chunk)

    def host(*shape):
        return jax.ShapeDtypeStruct(shape, "int32")

    def args(c, pool):
        mb = -(-cfg.max_seq // block)
        return (params, host(batch, c), pool, host(batch, mb), host(batch),
                host(batch))

    # the engine's first pool, made eagerly on one device, and the pool
    # as the wide step returns it
    pool = jax.eval_shape(lambda: bundle.init_paged_pool(num_blocks, block))
    first = steps.jits[chunk].lower(*args(chunk, pool)).compile()
    pool = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        pool, first.output_shardings[1])
    keyed = getattr(jax.config, METADATA_IN_KEY)
    jax.config.update(METADATA_IN_KEY, True)
    try:
        return {c: fn.lower(*args(c, pool)).compile().as_text()
                for c, fn in steps.jits.items()}
    finally:
        jax.config.update(METADATA_IN_KEY, keyed)

"""The harness takes a program of another architecture with no edit: a
configuration's overrides reach into the program's nested config groups,
and a tiny MoE transformer from the registry (``dbrx-132b``) is built
with seeded weights and served through ``harness.build``."""
import dataclasses

import jax
import numpy as np

from bench import harness, weights
from bench.tests.tiny import MIX, TINY, make_ctx
from repro.configs.registry import get_arch

MOE = {"n_experts": 8, "d_model": TINY["d_model"], "d_ff": TINY["d_ff"]}


def dbrx_cell():
    over = dict(TINY, moe=MOE, param_dtype="bfloat16",
                compute_dtype="bfloat16")
    cfg = {"name": "dbrx-132b",
           "harness": {"arch": "dbrx-132b", "overrides": over, "chips": 1,
                       "mesh": [1, 1], "fusion": "fused", "batch": 4,
                       "chunk": 8, "block_size": 16}}
    return harness.Cell("tiny.dbrx", cfg, dict(MIX), 1, {})


def test_a_nested_override_replaces_fields_of_its_group():
    published = get_arch("dbrx-132b").config.moe
    cfg = harness.program_bundle(dbrx_cell()).config
    assert cfg.d_model == TINY["d_model"]
    assert cfg.moe == dataclasses.replace(published, **MOE)
    assert (cfg.moe.top_k, cfg.moe.capacity_factor) == (4, 1.25)


def test_tiny_moe_transformer_is_built_and_served():
    from repro.serve.engine import Request

    served = harness.build(dbrx_cell(), 2147483653, make_ctx(1))
    experts = {weights.path_name(p): x.shape for p, x in
               jax.tree_util.tree_flatten_with_path(served.params)[0]
               if x.ndim == 4}
    # stacked over layers: [L, E, d, f] and [L, E, f, d]
    assert experts and all(s[:2] == (TINY["n_layers"], MOE["n_experts"])
                           for s in experts.values())
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(1, TINY["vocab"], n).tolist(), m)
            for n, m in ((3, 6), (9, 4), (17, 7), (26, 5))]
    for uid, (prompt, max_new) in enumerate(reqs):
        served.engine.submit(Request(uid=uid, prompt=prompt,
                                     max_new=max_new))
    done = served.engine.run_until_drained()
    assert done.drained
    assert sorted((r.uid, len(r.tokens)) for r in done) == [
        (uid, m) for uid, (_, m) in enumerate(reqs)]
    assert all(0 <= t < TINY["vocab"] for r in done for t in r.tokens)

"""The float32 reference agrees with the program's chunked prefill and
paged decode through PagedDecodeEngine, for both RoPE styles."""
import numpy as np
import pytest

from bench import families, harness
from bench.tests.tiny import make_ctx, tiny_cell


def serve(cell, seed, requests):
    """Serve (prompt, max_new) pairs through the engine to the end."""
    from repro.serve.engine import Request

    served = harness.build(cell, seed, make_ctx(cell.chips))
    for uid, (prompt, max_new) in enumerate(requests):
        served.engine.submit(Request(uid=uid, prompt=prompt,
                                     max_new=max_new))
    done = served.engine.run_until_drained()
    assert done.drained
    return {r.uid: r.tokens for r in done}


@pytest.mark.parametrize("config,extra", [
    ("chatglm3-6b", {}),                  # 2D RoPE over half of each head
    ("phi3-medium-14b", {"window": 16}),  # full RoPE, a window the
                                          # requests outgrow
])
def test_engine_agrees_with_reference(config, extra):
    cell = tiny_cell(config, dtype="float32", **extra)
    reference, shape = families.load(cell.config)
    rng = np.random.default_rng(3)
    # prompts of 1, 3, 8 and 21 tokens: decode only, a short chunk, one
    # whole chunk, and chunks of 8, 8 and 5; then 12-19 decoded tokens
    reqs = [(rng.integers(0, shape.vocab, n).tolist(), m)
            for n, m in ((1, 19), (3, 12), (8, 15), (21, 14))]
    tokens = serve(cell, 11, reqs)
    gaps, _ = reference.gaps(11, shape, [(p, tokens[i])
                                         for i, (p, _) in enumerate(reqs)])
    assert [len(g) for g in gaps] == [m for _, m in reqs]
    # float32 on both sides: each served token is the reference's best
    assert max(float(g.max()) for g in gaps) < 1e-3


def test_reference_tells_wrong_tokens_apart():
    cell = tiny_cell("chatglm3-6b", dtype="float32")
    reference, shape = families.load(cell.config)
    prompt = list(range(5, 25))
    tokens = serve(cell, 11, [(prompt, 10)])[0]
    wrong = [(t + 1) % shape.vocab for t in tokens]
    (good,), _ = reference.gaps(11, shape, [(prompt, tokens)])
    (bad,), _ = reference.gaps(11, shape, [(prompt, wrong)])
    assert good.max() < 1e-3 < bad.min()

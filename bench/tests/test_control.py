"""The float8 control reads wider gaps than the bf16 program does, on
the same served requests: the comparison can tell the step below the
served precision from the program."""
import pytest

from bench import families
from bench.tests.test_reference import serve
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("seed", [2147483650, 2147483651, 2147483652])
def test_control_reads_wider_gaps_than_the_program(seed):
    import numpy as np

    cell = tiny_cell("chatglm3-6b")
    reference, shape = families.load(cell.config)
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, shape.vocab, n).tolist(), 24)
            for n in (5, 13, 30, 40)]
    tokens = serve(cell, seed, reqs)
    prog, ctl = reference.gaps(seed, shape, [(p, tokens[i]) for i, (p, _)
                                             in enumerate(reqs)],
                               control=True)
    prog_max = max(float(g.max()) for g in prog)
    ctl_max = max(float(g.max()) for g in ctl)
    assert ctl_max > 3 * prog_max

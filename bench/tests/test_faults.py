"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a benchmark run (set-up, window, metrics,
the check against the reference with the cell's own limit) at a size the
CPU holds, past the look for a chip, once sound and once for each fault
a served cell can have.
"""
import jax.numpy as jnp
import pytest

from bench import check
from bench.peaks import PEAKS
from bench.run import execute
from bench.tests.tiny import make_ctx, tiny_cell

LIMITS = check.load_limits("glm6b.decode")


def run(wrap_steps=None, *, chips=1, seed=2147483650, mix=None):
    cell = tiny_cell("chatglm3-6b", chips=chips)
    cell.mix.update(mix or {})
    import jax

    return execute(cell, seed, 2.0, False, devices=jax.devices()[:chips],
                   limits=LIMITS, peaks=PEAKS["TPU v5 lite"],
                   make_ctx=make_ctx(chips), wrap_steps=wrap_steps)


def break_steps(fault):
    """Wrap each jitted step program so that ``fault`` rewrites its call
    or its result."""
    def wrap(served):
        for c, fn in list(served.steps.jits.items()):
            served.steps.jits[c] = fault(fn)
    return wrap


def state_unchanged(fn):
    """The step hands back the pool it was given: nothing is written."""
    def f(params, tokens, pool, tables, pos, n_new):
        logits, _ = fn(params, tokens, pool, tables, pos, n_new)
        return logits, pool
    return f


def half_batch(fn):
    """The upper half of the slots is left out of the step."""
    def f(params, tokens, pool, tables, pos, n_new):
        keep = jnp.arange(n_new.shape[0]) < n_new.shape[0] // 2
        return fn(params, tokens, pool, tables, pos,
                  jnp.where(keep, n_new, 0))
    return f


def token_altered(fn):
    """Slot 0's logits are rotated by one, so its token is the next id."""
    def f(*args):
        logits, pool = fn(*args)
        return logits.at[0].set(jnp.roll(logits[0], 1)), pool
    return f


def test_sound_run_is_correct():
    assert run()["correct"] is True


def test_sound_open_loop_run_is_correct():
    out = run(mix={"loop": "open", "rate": 40.0, "warmup_s": 1.0})
    assert out["correct"] is True
    assert out["attempted"] > 40


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_fault_is_not_correct(fault):
    out = run(break_steps(fault))
    assert out["correct"] is False
    assert (out["compared"]["max_logit_gap"]["value"]
            > out["compared"]["max_logit_gap"]["limit"])


def test_exchange_between_chips_left_out(monkeypatch):
    """At tp=4 the row-parallel FFN-down's partial sums are not reduced
    over the chips."""
    from jax.sharding import PartitionSpec as P

    import repro.models.layers as layers
    from repro.compat import shard_map

    def no_allreduce(ctx, x, w, **_):
        lead = x.shape[:-1]
        y = shard_map(lambda xl, wl: xl @ wl, mesh=ctx.mesh,
                      in_specs=(P(None, ctx.tp_axis), P(ctx.tp_axis, None)),
                      out_specs=P(None, None), check_vma=False)(
            x.reshape(-1, x.shape[-1]), w)
        return y.reshape(lead + (w.shape[1],))

    assert run(chips=4)["correct"] is True
    monkeypatch.setattr(layers, "matmul_allreduce", no_allreduce)
    out = run(chips=4)
    assert out["correct"] is False

"""Seeded weights drawn by rank: the leaves of both configurations draw
the values the rule by name gave, and the leaves a new family brings (a
vector such as a qk-norm's scale, a stack of expert matrices) draw
without a rule of their own."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.tests.tiny import tiny_cell
from repro.models.common import split_params


def by_name(key, name, shape, d_model):
    """The rule ``draw`` kept before it went by rank: norm scales by
    name."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith(("ln1", "ln2", "final_norm")):
        return 1.0 + 0.1 * x
    if name == "embed/table":
        return x * d_model ** -0.5
    return x * shape[-2] ** -0.5


def struct(cell):
    bundle = harness.program_bundle(cell)
    tree = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    return split_params(tree)[0], bundle.config.d_model


def leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights.path_name(p): x for p, x in flat}


@pytest.mark.parametrize("config", ["chatglm3-6b", "phi3-medium-14b"])
def test_todays_leaves_draw_as_the_rule_by_name_drew(config, monkeypatch):
    tiny, d_model = struct(tiny_cell(config))
    # the tiny cell's tree has the names and ranks of the full one's
    full_cell = harness.make_cell(config, config, "decode", 1, {})
    full, _ = struct(full_cell)
    assert ({k: x.ndim for k, x in leaves(full).items()}
            == {k: x.ndim for k, x in leaves(tiny).items()})
    root = weights.root_key(2147483650)
    new = leaves(weights.tree_init(root, tiny, d_model))
    monkeypatch.setattr(weights, "draw", by_name)
    old = leaves(weights.tree_init(root, tiny, d_model))
    assert new.keys() == old.keys()
    for name in new:
        assert (np.asarray(new[name]).tobytes()
                == np.asarray(old[name]).tobytes()), name


def test_vectors_and_expert_stacks_draw_by_rank():
    root = weights.root_key(2147483651)
    q_norm = weights.layer_weight(root, "layers/l0/attn/q_norm", 3, (128,),
                                  64, "bfloat16")
    assert q_norm.shape == (128,)
    assert abs(float(q_norm.mean()) - 1.0) < 0.05
    assert 0.05 < float(q_norm.std()) < 0.15
    up = weights.layer_weight(root, "layers/l0/moe/w_up", 3, (8, 64, 256),
                              64, "bfloat16")
    down = weights.layer_weight(root, "layers/l0/moe/w_down", 3,
                                (8, 256, 64), 64, "bfloat16")
    # normal at fan-in, the second-to-last axis: d_model up, d_ff down
    assert float(up.std()) == pytest.approx(64 ** -0.5, rel=0.05)
    assert float(down.std()) == pytest.approx(256 ** -0.5, rel=0.05)

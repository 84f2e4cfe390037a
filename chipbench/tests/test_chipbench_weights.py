"""The reference draws each layer's weights exactly as the tree the
program serves was drawn, and the tree has the program's layout."""

import jax
import pytest

from chipbench import reference, weights
from chipbench.tests.tiny import config


@pytest.mark.parametrize("engine", ["slot", "continuous"])
def test_reference_redraws_the_served_weights(engine):
    m = reference.Model.from_config(config(engine)["model"])
    key = jax.random.PRNGKey(2**31 - 3)
    tree = weights.params(m, key)
    g = tree["groups"]
    for i in range(m.n_layers):
        w = reference.layer_weights(m, key, i)
        for name, a in w.items():
            part = g["attn"] if name.startswith("w") else g["mlp"]
            assert (a == part[name]["w"][i]).all(), (name, i)
    embed, head = weights.embed_and_head(m, key)
    assert (embed == tree["embed"]["w"]).all()
    assert (head == tree["lm_head"]["w"]).all()


@pytest.mark.parametrize("engine", ["slot", "continuous"])
def test_tree_has_the_programs_layout(engine):
    import dataclasses

    from repro.models import transformer as T
    from repro.models.registry import get_config

    conf = config(engine)
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    want = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg,
                                                jax.numpy.bfloat16))
    m = reference.Model.from_config(conf["model"])
    got = jax.eval_shape(lambda: weights.params(m, jax.random.PRNGKey(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)

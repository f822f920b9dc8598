"""The seeded weights and the plain reference of the dense family
(bench/weights.py, bench/families/dense.py) at toy widths on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.families.dense import Shape


@pytest.fixture(params=["stablelm", "yi"])
def tiny(request, tiny_config):
    from repro.models import build_model
    conf = tiny_config(request.param)
    conf["name"] = request.param
    s = Shape.from_config(conf)
    return conf, s, build_model(spec.model_config(conf, s, strict=False))


def test_served_weights_are_the_reference_weights(tiny):
    _, s, model = tiny
    seed = 2**33 + 1
    params = weights.program_params(s, seed, model)
    key = weights.base_key(seed)
    for layer in range(s.layers):
        w = weights.layer_weights(s, key, layer)
        got = params["layers"]["b0"]["attn"]["wq"][layer]
        assert jnp.array_equal(got.reshape(w["q"].shape), w["q"])
        assert jnp.array_equal(params["layers"]["b0"]["mlp"]["w_down"][layer],
                               w["down"])
    g = weights.global_weights(s, key)
    assert jnp.array_equal(params["embed"][:s.vocab], g["embed"])


def test_reference_is_the_models_forward(tiny):
    """The model in float32 on the same weights gives the reference's
    logits: the reference computes what the configuration states."""
    conf, s, model = tiny
    seed = 7
    from repro.models import build_model
    m32 = build_model(model.cfg.replace(dtype="float32"))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.program_params(s, seed, model))
    toks = np.random.default_rng(0).integers(0, s.vocab, (1, 40))
    with jax.default_matmul_precision("highest"):
        logits, _ = m32.forward(params, {"tokens": jnp.asarray(toks)})
    rows = [(0, p) for p in range(40)]
    ref = s.logits_at(seed, [list(toks[0])], rows)
    np.testing.assert_allclose(np.asarray(logits)[0, :, :s.vocab], ref,
                               rtol=1e-4, atol=1e-4)


def test_seed_above_31_bits():
    a = weights.base_key(2**31 + 5)
    b = weights.base_key(5)
    assert not jnp.array_equal(a, b)

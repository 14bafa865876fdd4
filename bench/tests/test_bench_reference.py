"""The float32 reference against the program's own forward pass, at
small widths, for both configurations' block shapes."""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import small  # noqa: E402

from bench import cell, reference, weights  # noqa: E402


@pytest.mark.parametrize("name,kv,gated", [
    ("minitron-4b", 2, False),     # grouped-query, plain MLP
    ("granite-34b", 1, False),     # multi-query, plain MLP
    ("minitron-4b", 4, True),      # one KV head per query head, gated MLP
])
def test_reference_matches_program(name, kv, gated):
    import jax.numpy as jnp
    from repro.models import registry
    conf = small.config(name)
    conf["program"] = dict(conf["program"], mlp_gated=gated)
    m = cell.model_dims(conf)
    m["n_kv_heads"] = kv
    cfg = dataclasses.replace(cell.model_config(conf, name), n_kv_heads=kv,
                              dtype="float32")
    params = weights.make(m, 2 ** 31 + 5, "float32")
    toks = np.random.default_rng(0).integers(0, m["vocab"], (2, 40))
    want = np.asarray(registry.forward(cfg, params,
                                       {"tokens": jnp.asarray(toks)}))
    got = np.asarray(reference.logits(
        m, float(conf["program"]["rope_theta"]), params, toks))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_gaps_read_the_served_token():
    """A served token that is the reference's best has gap 0; any other
    has the difference of the two logits."""
    conf = small.config("minitron-4b")
    m = cell.model_dims(conf)
    params = weights.make(m, 9, "float32")
    prompt = np.arange(5, 25, dtype=np.int32)
    lg = np.asarray(reference.logits(m, 1e4, params, prompt[None]))[0]
    best = int(lg[-1].argmax())
    other = int(lg[-1].argmin())
    g, _ = reference.logit_gaps(m, 1e4, params, [
        (prompt, np.array([best], np.int32)),
        (prompt, np.array([other], np.int32))], 64)
    assert g[0][0] == 0.0
    np.testing.assert_allclose(g[1][0], lg[-1].max() - lg[-1].min(),
                               rtol=1e-5)

"""Chunked (flash-style) attention vs the vanilla path — train,
prefill-into-cache, and decode; plus GQA grouping invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.approx.layers import EXACT_POLICY
from repro.configs import get_config
from repro.models import common


def _setup(arch="qwen1.5-0.5b", **over):
    cfg_v = get_config(arch).reduced(**over)
    cfg_c = dataclasses.replace(cfg_v, attn_impl="chunked", kv_chunk=8)
    params = common.init_attention(jax.random.PRNGKey(0), cfg_v)
    return cfg_v, cfg_c, params


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(st.integers(1, 40), st.integers(1, 3), st.integers(0, 2 ** 16))
def test_chunked_equals_vanilla_selfattn(s, b, seed):
    cfg_v, cfg_c, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, cfg_v.d_model),
                          jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    ya, _ = common.attention(params, x, cfg_v, EXACT_POLICY, positions=pos)
    yb, _ = common.attention(params, x, cfg_c, EXACT_POLICY, positions=pos)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), rtol=1e-4,
                               atol=1e-5)


def test_chunked_equals_vanilla_cache_paths():
    cfg_v, cfg_c, params = _setup()
    b, s = 2, 13
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg_v.d_model),
                          jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    cache = common.init_attention_cache(cfg_v, b, s + 5)
    ya, ca = common.attention(params, x, cfg_v, EXACT_POLICY,
                              positions=pos, cache=cache)
    yb, cb = common.attention(params, x, cfg_c, EXACT_POLICY,
                              positions=pos, cache=cache)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), rtol=1e-4,
                               atol=1e-5)
    x1 = jax.random.normal(jax.random.PRNGKey(2), (b, 1, cfg_v.d_model),
                           jnp.float32)
    pos1 = jnp.asarray([s], jnp.int32)
    ya, _ = common.attention(params, x1, cfg_v, EXACT_POLICY,
                             positions=pos1, cache=ca)
    yb, _ = common.attention(params, x1, cfg_c, EXACT_POLICY,
                             positions=pos1, cache=cb)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.slow
def test_chunked_gradients_finite():
    cfg_v, cfg_c, params = _setup()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 17, cfg_v.d_model),
                          jnp.float32)
    pos = jnp.arange(17, dtype=jnp.int32)

    def loss(p, cfg):
        y, _ = common.attention(p, x, cfg, EXACT_POLICY, positions=pos)
        return jnp.sum(y ** 2)

    gv = jax.grad(lambda p: loss(p, cfg_v))(params)
    gc = jax.grad(lambda p: loss(p, cfg_c))(params)
    for a, b in zip(jax.tree.leaves(gv), jax.tree.leaves(gc)):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_causality():
    """Future tokens must not influence earlier positions."""
    for impl in ("vanilla", "chunked"):
        cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                                  attn_impl=impl, kv_chunk=4)
        params = common.init_attention(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 10, cfg.d_model),
                              jnp.float32)
        pos = jnp.arange(10, dtype=jnp.int32)
        y1, _ = common.attention(params, x, cfg, EXACT_POLICY,
                                 positions=pos)
        x2 = x.at[0, -1].set(123.0)   # perturb the LAST token only
        y2, _ = common.attention(params, x2, cfg, EXACT_POLICY,
                                 positions=pos)
        np.testing.assert_allclose(np.asarray(y1[:, :-1]),
                                   np.asarray(y2[:, :-1]), rtol=1e-4,
                                   atol=1e-5)

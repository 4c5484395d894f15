"""Gradient compression + error feedback invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist.compression import (
    CompressionConfig, compress, decompress, init_residual, wire_fraction,
)


def _grads(R=4, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(R, 64)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(R, 8, 16)), jnp.float32),
    }


def test_none_is_identity():
    g = _grads()
    r = init_residual(g)
    p, r2 = compress(g, r, CompressionConfig("none"))
    assert p is g and r2 is r


def test_topk_keeps_largest_and_residual_holds_rest():
    g = _grads()
    r = init_residual(g)
    cfg = CompressionConfig("topk", topk_fraction=0.25)
    p, r2 = compress(g, r, cfg)
    for k in g:
        sent = np.asarray(p[k])
        res = np.asarray(r2[k])
        # sent + residual == original (exact decomposition)
        np.testing.assert_allclose(sent + res, np.asarray(g[k]), atol=1e-6)
        flat = sent.reshape(sent.shape[0], -1)
        nz = (flat != 0).sum(axis=1)
        kk = max(1, int(0.25 * flat.shape[1]))
        assert (nz <= kk + 1).all() and (nz >= 1).all()


def test_int8_quantization_error_bounded():
    g = _grads()
    cfg = CompressionConfig("int8")
    p, r2 = compress(g, init_residual(g), cfg)
    for k in g:
        gmax = np.abs(np.asarray(g[k])).max()
        err = np.abs(np.asarray(p[k]) - np.asarray(g[k])).max()
        assert err <= gmax / 127.0 + 1e-6
    assert wire_fraction(cfg) == 0.25


def test_error_feedback_converges_mean():
    """With error feedback, repeated compressed averaging still moves all
    mass eventually: sum of (sent_t) over steps -> sum of grads."""
    g = _grads(seed=3)
    cfg = CompressionConfig("topk", topk_fraction=0.1)
    res = init_residual(g)
    total_sent = jax.tree.map(jnp.zeros_like, g)
    for _ in range(60):
        sent, res = compress(g, res, cfg)
        total_sent = jax.tree.map(lambda a, s: a + s, total_sent, sent)
        # note: same g each step, so total_sent ~ t*g - residual
    for k in g:
        drift = np.abs(np.asarray(res[k])).max()
        scale = np.abs(np.asarray(g[k])).max()
        assert drift <= 12 * scale  # residual stays bounded (EF property)


@pytest.mark.parametrize("scheme", ["topk", "int8"])
def test_error_feedback_conserves_accumulator_bitwise(scheme):
    """Sent tensor + new residual reconstructs the (gradient + residual)
    accumulator BITWISE: topk entries are exact copies/leftovers; int8's
    acc - dequant subtraction is Sterbenz-exact (nonzero quantization
    levels satisfy dequant/2 <= acc <= 2*dequant; zero levels leave acc
    itself as residual).  No mass is created or destroyed by a sync."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        g = _grads(seed=seed)
        res = jax.tree.map(
            lambda x: jnp.asarray(
                rng.normal(size=x.shape) * 0.1, jnp.float32), g,
        )
        p, r2 = compress(g, res, CompressionConfig(scheme, topk_fraction=0.25))
        for k in g:
            acc = np.asarray(g[k]) + np.asarray(res[k])
            recon = np.asarray(p[k]) + np.asarray(r2[k])
            np.testing.assert_array_equal(recon, acc)


@given(frac=st.floats(0.05, 0.9), seed=st.integers(0, 50))
@settings(max_examples=10)
def test_property_decomposition_exact(frac, seed):
    g = _grads(seed=seed)
    cfg = CompressionConfig("topk", topk_fraction=frac)
    p, r2 = compress(g, init_residual(g), cfg)
    for k in g:
        np.testing.assert_allclose(
            np.asarray(p[k]) + np.asarray(r2[k]), np.asarray(g[k]), atol=1e-6
        )
    assert wire_fraction(cfg) <= 1.0

"""Fused ReLU+mask and pool/unpool Pallas kernels vs oracles (paper Fig. 4/5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rules
from repro.kernels.pool import ops as pops, ref as pref
from repro.kernels.pool.pool import maxpool_fwd_pallas, unpool_bwd_pallas
from repro.kernels.relu_mask import ops as rops, ref as rref
from repro.kernels.relu_mask.relu_mask import relu_bwd_pallas, relu_fwd_pallas


@pytest.mark.parametrize("shape", [(8, 128), (50, 200), (3, 1024), (17, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_relu_fwd_mask(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    y, m = relu_fwd_pallas(x)
    y2, m2 = rref.relu_fwd(x)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y2, np.float32))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))


@pytest.mark.parametrize("method", ["saliency", "deconvnet", "guided"])
def test_relu_bwd_dataflows(method):
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 168))
    g = jax.random.normal(jax.random.PRNGKey(1), (40, 168))
    _, m = relu_fwd_pallas(x)
    got = relu_bwd_pallas(m, g, method)
    want = rref.relu_bwd(m, g, method)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("method", ["saliency", "deconvnet", "guided"])
def test_relu_ops_match_core_rules(method):
    """Kernel path == pure-jnp rules path, end to end through vjp."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 10, 136))
    g = jax.random.normal(jax.random.PRNGKey(3), (4, 10, 136))
    dx_k = jax.vjp(lambda v: rops.relu(v, method), x)[1](g)[0]
    dx_r = jax.vjp(lambda v: rules.relu(v, method), x)[1](g)[0]
    np.testing.assert_array_equal(np.asarray(dx_k), np.asarray(dx_r))


@pytest.mark.parametrize("shape", [(1, 4, 4, 4), (3, 16, 16, 37),
                                   (2, 32, 32, 64)])
def test_pool_fwd_and_indices(shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    y, i = maxpool_fwd_pallas(x)
    y2, i2 = pref.maxpool_fwd(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i2))


def test_unpool_routes_to_argmax():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 12))
    _, idx = maxpool_fwd_pallas(x)
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4, 12))
    got = unpool_bwd_pallas(idx, g)
    want = pref.unpool_bwd(idx, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # routed grads preserve total mass
    np.testing.assert_allclose(float(got.sum()), float(g.sum()), rtol=1e-5)


def test_pool_ops_vjp_matches_rules():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16, 20))
    g = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 8, 20))
    d_k = jax.vjp(lambda v: pops.maxpool2x2(v, "saliency"), x)[1](g)[0]
    d_r = jax.vjp(lambda v: rules.maxpool2x2(v, "saliency"), x)[1](g)[0]
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))


@pytest.mark.parametrize("width", [1, 2])
def test_pack_fields_matches_core_masks(width):
    """The in-kernel MXU pack reproduces core.masks' byte layout exactly."""
    from repro.core import masks
    from repro.kernels.relu_mask.relu_mask import pack_fields
    rng = np.random.RandomState(width)
    v = rng.randint(0, 1 << width, (40, 64)).astype(np.int32)
    want = (masks.pack_mask(jnp.asarray(v, bool)) if width == 1
            else masks.pack_crumbs(jnp.asarray(v)))
    got = pack_fields(jnp.asarray(v), width).astype(jnp.uint8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_interpret_refused_on_tpu_backend(monkeypatch):
    """On a TPU backend a kernel never runs interpreted: None resolves to
    compiled, and an explicit interpret=True raises."""
    import repro.kernels as kernels
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    assert kernels.interpret_mode() is False
    assert kernels.interpret_mode(False) is False
    with pytest.raises(RuntimeError, match="never interpreted"):
        kernels.interpret_mode(True)
    x = jnp.ones((8, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="never interpreted"):
        relu_fwd_pallas(x, interpret=True)


def test_interpret_default_off_tpu():
    import repro.kernels as kernels
    assert kernels.interpret_mode() is True
    assert kernels.interpret_mode(True) is True

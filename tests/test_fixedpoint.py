"""Q-format codec + requantizer: properties (hypothesis) and pinned units.

Covers the paper's §IV numeric contract: round-trip error bounded by the
grid step, SYMMETRIC saturation at the Q7.8 limits (the two's-complement
minimum is never produced — pinned here so the clip can't silently go
asymmetric again), quantizer idempotence, and the straight-through
gradient identity of the fake quantizer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import fixedpoint as fxp

LIM78 = (2 ** 15 - 1) / 2 ** 8        # 127.99609375


# ---------------------------------------------------------------------------
# pinned units: symmetric clip (the make_quantizer range fix)
# ---------------------------------------------------------------------------


def test_quantizer_clip_is_symmetric():
    """Both rails saturate at ±(2^15 - 1) grid steps — NOT the asymmetric
    two's-complement [-2^15, 2^15 - 1]."""
    q = fxp.make_quantizer(7, 8)
    assert float(q(jnp.float32(1e6))) == LIM78
    assert float(q(jnp.float32(-1e6))) == -LIM78
    # integer codec saturates identically
    assert int(fxp.to_fixed(jnp.float32(1e6))) == 2 ** 15 - 1
    assert int(fxp.to_fixed(jnp.float32(-1e6))) == -(2 ** 15 - 1)
    np.testing.assert_array_equal(
        np.asarray(fxp.requantize(jnp.int32(-(2 ** 30)), 8)), -(2 ** 15 - 1))


def test_quantizer_negation_closed():
    """Symmetric saturation keeps negation exact: q(-x) == -q(x)."""
    x = jnp.linspace(-300.0, 300.0, 101)
    np.testing.assert_array_equal(np.asarray(fxp.fxp16(-x)),
                                  np.asarray(-fxp.fxp16(x)))
    np.testing.assert_array_equal(np.asarray(fxp.to_fixed(-x)),
                                  np.asarray(-fxp.to_fixed(x)))


def test_codec_matches_fake_quantizer_on_grid():
    """from_fixed(to_fixed(x)) lands on exactly the fake-quantized value."""
    x = jax.random.normal(jax.random.PRNGKey(0), (512,)) * 40.0
    np.testing.assert_array_equal(
        np.asarray(fxp.from_fixed(fxp.to_fixed(x))),
        np.asarray(fxp.fxp16(x)))


def test_requantize_matches_numpy_mirror():
    acc = jax.random.randint(jax.random.PRNGKey(1), (4096,),
                             -2 ** 28, 2 ** 28, dtype=jnp.int32)
    for shift in (8, 14):
        np.testing.assert_array_equal(
            np.asarray(fxp.requantize(acc, shift)),
            fxp.requantize_np(np.asarray(acc), shift))


def test_requantize_rounds_half_up():
    # (acc + 2^(s-1)) >> s: +0.5 steps round up, -0.5 steps round toward 0
    got = fxp.requantize(jnp.array([128, -128, 127, -129], jnp.int32), 8)
    np.testing.assert_array_equal(np.asarray(got), [1, 0, 0, -1])


def test_sat_add_saturates():
    a = jnp.array([30000, -30000, 100], jnp.int16)
    b = jnp.array([30000, -30000, -50], jnp.int16)
    np.testing.assert_array_equal(np.asarray(fxp.sat_add(a, b)),
                                  [2 ** 15 - 1, -(2 ** 15 - 1), 50])


def test_quantize_params_int_formats():
    params = {"conv": [{"w": jnp.full((2, 2), 0.5), "b": jnp.full((2,), 0.5)}]}
    q = fxp.quantize_params_int(params)
    assert int(q["conv"][0]["w"][0, 0]) == 1 << (fxp.WGT_FRAC - 1)
    assert int(q["conv"][0]["b"][0]) == 1 << (fxp.ACT_FRAC - 1)


def test_quantize_params_int_rejects_unknown_leaves():
    """Unknown leaf names must raise, not silently pick a Q format."""
    with pytest.raises(ValueError, match="'w'/'b'"):
        fxp.quantize_params_int({"conv": [{"w": jnp.ones((2,)),
                                           "scale": jnp.ones(())}]})
    with pytest.raises(ValueError, match="'w'/'b'"):
        fxp.quantize_params_int([jnp.ones((2,))])


def test_ste_gradient_identity():
    """The fake quantizer's VJP is the identity (straight-through)."""
    g = jax.grad(lambda v: jnp.sum(fxp.fxp16(v) * 3.0))(
        jax.random.normal(jax.random.PRNGKey(2), (64,)))
    np.testing.assert_array_equal(np.asarray(g), np.full(64, 3.0, np.float32))


# ---------------------------------------------------------------------------
# hypothesis properties (skip cleanly without hypothesis)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_roundtrip_within_half_step(seed):
    """|q(x) - x| <= 2^-9 (half a Q7.8 step) inside the representable range."""
    x = jax.random.uniform(jax.random.PRNGKey(seed), (256,),
                           minval=-127.9, maxval=127.9)
    err = np.abs(np.asarray(fxp.from_fixed(fxp.to_fixed(x)) - x))
    assert err.max() <= 2.0 ** -9 + 1e-7


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_quantizer_idempotent(seed):
    """q(q(x)) == q(x) bitwise — grid points are fixed points."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (256,)) * 200.0
    once = fxp.fxp16(x)
    np.testing.assert_array_equal(np.asarray(fxp.fxp16(once)),
                                  np.asarray(once))
    qi = fxp.to_fixed(x)
    np.testing.assert_array_equal(
        np.asarray(fxp.to_fixed(fxp.from_fixed(qi))), np.asarray(qi))


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([8, 14]))
@settings(max_examples=30, deadline=None)
def test_requantizer_property(seed, shift):
    """requantize == round-half-up(acc / 2^shift) with symmetric saturation,
    and the jnp and numpy implementations agree bitwise."""
    acc = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (512,), -2 ** 30, 2 ** 30, dtype=jnp.int32))
    want = np.clip(np.floor((acc.astype(np.int64) + (1 << (shift - 1)))
                            / (1 << shift)),
                   -(2 ** 15 - 1), 2 ** 15 - 1).astype(np.int16)
    np.testing.assert_array_equal(fxp.requantize_np(acc, shift), want)
    np.testing.assert_array_equal(
        np.asarray(fxp.requantize(jnp.asarray(acc), shift)), want)


@pytest.mark.slow
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_ste_gradient_identity_property(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (128,)) * 50.0
    ct = jax.random.normal(jax.random.PRNGKey(seed + 1), (128,))
    g = jax.vjp(fxp.fxp16, x)[1](ct)[0]
    np.testing.assert_array_equal(np.asarray(g), np.asarray(ct))


# ---------------------------------------------------------------------------
# int16 dot as four int8 MXU dots (the TPU v5e lowering of the fxp kernels)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("values", ["random", "rails", "digit_edges"])
@pytest.mark.parametrize("k", [1, 7, 576, 4096])
def test_dot_i16_matches_wrapped_int32_dot(values, k):
    """dot_i16 equals the int32-accumulated int16 dot bit for bit, including
    int32 wraparound (the int64 product reduced mod 2^32)."""
    rng = np.random.RandomState(k)
    if values == "random":
        a = rng.randint(-32767, 32768, (9, k))
        b = rng.randint(-32767, 32768, (k, 5))
    elif values == "rails":
        a = rng.choice([-32767, 32767], (9, k))
        b = rng.choice([-32767, 32767], (k, 5))
    else:
        edges = [-32768, -32640, -129, -128, -1, 0, 1, 127, 128, 255, 256,
                 32639, 32640, 32767]
        a, b = rng.choice(edges, (9, k)), rng.choice(edges, (k, 5))
    a, b = a.astype(np.int16), b.astype(np.int16)
    want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)
    got = np.asarray(fxp.dot_i16(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)

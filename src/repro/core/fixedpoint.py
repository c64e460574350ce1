"""16-bit fixed-point arithmetic (paper §IV: Q-format 16b weights/acts/grads).

The FPGA runs inference AND gradient backpropagation in 16-bit fixed point.
Two layers of support here:

* **Fake quantization** (:func:`make_quantizer`) — values snapped to a Qm.n
  grid in f32 carriers (straight-through estimator for BP), for quick
  precision studies on any float path.
* **True integer arithmetic** — the Q-format codec (:func:`to_fixed` /
  :func:`from_fixed`), the post-accumulation requantizer
  (:func:`requantize`), and the saturating int16 add (:func:`sat_add`).
  These are the numeric contract of the int16 Pallas kernels
  (``repro.kernels.*.fxp``): Q7.8 int16 operands, int32 MXU accumulation,
  round-half-up right-shift requantization with symmetric saturation.
  :func:`requantize_np` is the independent NumPy mirror the kernel tests
  pin bit-exactness against.

Q-format choices (per-tensor, all 16-bit as in the paper):

* activations / gradients / biases — **Q7.8** (range ±127.996, step 2^-8):
  the paper CNN's activations stay within ±tens.
* weights — **Q1.14** (``WGT_FRAC``): CNN weights live in (-2, 2), so
  spending the idle integer bits on fraction keeps the product scale
  2^(8+14) well inside int32 while giving weights 64x finer steps.
* backward seeds — Q7.8 scaled by ``SEED_GAIN`` (a power of two, i.e. a
  block exponent on the whole BP phase): gradients shrink multiplicatively
  through the layers, and pre-scaling the seed keeps them in the high bits
  of the grid; the final relevance is divided back out exactly.

Saturation is SYMMETRIC at ±(2^15 - 1) grid steps: -2^15 is never produced,
so negation/abs stay closed in int16 — the same convention saturating FPGA
arithmetic uses.  :func:`make_quantizer` deliberately clips to the same
symmetric range (NOT the asymmetric two's-complement [-2^15, 2^15 - 1]);
``tests/test_fixedpoint.py`` pins this.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

ACT_FRAC = 8          # Q7.8 activations / gradients / biases
WGT_FRAC = 14         # Q1.14 weights
SEED_GAIN_BITS = 6    # backward seed pre-scale: 2^6 (removed exactly at the end)
SEED_GAIN = float(1 << SEED_GAIN_BITS)
INT16_LIM = (1 << 15) - 1          # symmetric saturation, grid units


def make_quantizer(int_bits: int = 7, frac_bits: int = 8):
    """Q``int_bits``.``frac_bits`` symmetric fixed-point fake-quantizer.

    Default Q7.8 (1 sign + 7 int + 8 frac = 16 bits), range
    ±(2^15 - 1)/2^8 = ±127.99609375, resolution 2^-8.  The clip is
    symmetric by design — both rails sit at ``2^(int_bits+frac_bits) - 1``
    grid steps, matching the saturating integer kernels (which never emit
    the asymmetric two's-complement minimum).
    """
    scale = float(2 ** frac_bits)
    lim = float(2 ** (int_bits + frac_bits) - 1)

    @jax.custom_vjp
    def q(x):
        return jnp.clip(jnp.round(x * scale), -lim, lim) / scale

    # Straight-through: the FPGA truncates products but propagates gradient
    # signals at full local fidelity across the quantization.
    q.defvjp(lambda x: (q(x), None), lambda _, g: (g,))
    return q


fxp16 = make_quantizer(7, 8)


def quantize_tree(tree, int_bits: int = 7, frac_bits: int = 8):
    """Fake-quantize every leaf of a parameter pytree to Qm.n."""
    q = make_quantizer(int_bits, frac_bits)
    return jax.tree.map(q, tree)


# ---------------------------------------------------------------------------
# true int16 codec + requantizer (the fxp kernels' numeric contract)
# ---------------------------------------------------------------------------


def to_fixed(x: jnp.ndarray, frac_bits: int = ACT_FRAC) -> jnp.ndarray:
    """f32 -> int16 on the Q(15-n).n grid, round-to-nearest-even, saturated."""
    g = jnp.round(x.astype(jnp.float32) * (1 << frac_bits))
    return jnp.clip(g, -INT16_LIM, INT16_LIM).astype(jnp.int16)


def from_fixed(q: jnp.ndarray, frac_bits: int = ACT_FRAC) -> jnp.ndarray:
    """int16 grid values -> f32 (exact: every grid point is an f32)."""
    return q.astype(jnp.float32) / (1 << frac_bits)


def requantize(acc: jnp.ndarray, shift: int = WGT_FRAC) -> jnp.ndarray:
    """int32 accumulator -> int16, round-half-up right shift + saturation.

    ``(acc + 2^(shift-1)) >> shift`` with an arithmetic shift — the single
    rounding an FPGA MAC array applies when narrowing the wide accumulator
    back to the 16-bit datapath.  Usable inside Pallas kernel bodies (pure
    jnp integer ops).  Mirrored bit-for-bit by :func:`requantize_np`.
    """
    half = jnp.int32(1 << (shift - 1))
    return jnp.clip((acc.astype(jnp.int32) + half) >> shift,
                    -INT16_LIM, INT16_LIM).astype(jnp.int16)


def requantize_np(acc: np.ndarray, shift: int = WGT_FRAC) -> np.ndarray:
    """Independent NumPy mirror of :func:`requantize` (oracle side)."""
    half = np.int32(1 << (shift - 1))
    return np.clip((acc.astype(np.int32) + half) >> shift,
                   -INT16_LIM, INT16_LIM).astype(np.int16)


def _i8_digits(a: jnp.ndarray):
    """int16-range int32 ``a`` -> int8 digits with ``a = 256*hi + lo + 128``."""
    hi = (a >> 8).astype(jnp.int8)                  # [-128, 127]
    lo = ((a & 255) - 128).astype(jnp.int8)         # [-128, 127]
    return hi, lo


def dot_i16(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """int16 [M, K] @ int16 [K, N] -> int32, exact, as four int8 MXU dots.

    The TPU MXU takes int8 (not int16) operands.  With both operands split
    as ``256*hi + lo + 128`` (:func:`_i8_digits`), and ``a' = a - 128``:

        a @ b = a' @ b' + 128 * (rowsum(a) + colsum(b)) - 16384 * K
        a' @ b' = 65536 hi_a@hi_b + 256 (hi_a@lo_b + lo_a@hi_b) + lo_a@lo_b

    Every step is int32 arithmetic modulo 2^32, so the result equals the
    int32-accumulated int16 dot bit for bit (wraparound included).  Usable
    inside Pallas kernel bodies; operands may be int16 or their int32
    carriers.
    """
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    ha, la = _i8_digits(a)
    hb, lb = _i8_digits(b)

    def mm(x, y):       # explicit precision: never the float default's
        return jnp.dot(x, y, preferred_element_type=jnp.int32,
                       precision=jax.lax.Precision.DEFAULT)

    k = a.shape[-1]
    ab = ((mm(ha, hb) << 16) + ((mm(ha, lb) + mm(la, hb)) << 8)
          + mm(la, lb))
    rows = jnp.sum(a, axis=-1, keepdims=True)
    cols = jnp.sum(b, axis=0, keepdims=True)
    return ab + ((rows + cols) << 7) - (jnp.int32(k) << 14)


def sat_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Saturating int16 add (bias adds) — widen to int32, clip, narrow."""
    s = a.astype(jnp.int32) + b.astype(jnp.int32)
    return jnp.clip(s, -INT16_LIM, INT16_LIM).astype(jnp.int16)


def quantize_params_int(params):
    """f32 param pytree -> int16: weights Q1.14, biases Q7.8.

    Matches the layout of ``models.cnn`` params ({"conv": [{"w", "b"}...],
    "fc": [...]}) but works on any pytree of dicts with "w"/"b" leaves.
    """
    from jax.tree_util import tree_map_with_path

    def leaf(path, v):
        name = getattr(path[-1], "key", None) if path else None
        if name not in ("w", "b"):
            # Fail loudly: defaulting an unknown leaf to either format
            # would be a silent 2^6 scale error in the int16 model.
            raise ValueError(
                f"quantize_params_int expects 'w'/'b' dict leaves, got "
                f"leaf path {jax.tree_util.keystr(path)!r}")
        return to_fixed(v, WGT_FRAC if name == "w" else ACT_FRAC)

    return tree_map_with_path(leaf, params)
